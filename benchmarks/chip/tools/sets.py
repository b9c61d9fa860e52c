"""Sets of benchmark runs of one cell, and the spread of each metric.

    python3 -m benchmarks.chip.tools.sets --workload <cell> \
        --seeds 11,12,13,14,15,16 --sets 2 --seconds 50 \
        [--trace-seeds 21,22,23] [--out chiprun_out/sets.jsonl]

Each run is its own process (``python3 -m benchmarks.chip ...``), one
after another; this parent never touches JAX, so each child has the chip.
Every set runs the same seeds in the same order. Then, per metric and per
set, the spread: the distance between the first and third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median; also
the same with each set's run farthest from its median left out, and the
second set's median against the first's.
"""
from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
from pathlib import Path


def spread(values: list[float]) -> float:
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / abs(med) if med else float("nan")


def trimmed(values: list[float]) -> list[float]:
    med = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - med))
    return [v for i, v in enumerate(values) if i != far]


def one_run(workload: str, seed: int, seconds: float, trace: int) -> dict:
    cmd = [sys.executable, "-m", "benchmarks.chip", "--workload", workload,
           "--seed", str(seed), "--seconds", str(seconds), "--trace",
           str(trace)]
    p = subprocess.run(cmd, capture_output=True, text=True)
    last = p.stdout.strip().splitlines()[-1] if p.stdout.strip() else ""
    try:
        result = json.loads(last)
    except json.JSONDecodeError:
        result = None
    return {"seed": seed, "trace": trace, "rc": p.returncode,
            "result": result, "stdout": p.stdout[-1500:],
            "stderr": p.stderr[-1500:]}


def summarise(runs_by_set: list[list[dict]]) -> dict:
    names = set()
    for runs in runs_by_set:
        for r in runs:
            if r["result"]:
                names |= set(r["result"]["metrics"])
    out = {}
    for name in sorted(names):
        sets = [[r["result"]["metrics"][name]["value"] for r in runs
                 if r["result"] and name in r["result"]["metrics"]]
                for runs in runs_by_set]
        if any(len(v) < 4 for v in sets):
            continue
        out[name] = {
            "medians": [statistics.median(v) for v in sets],
            "spreads": [spread(v) for v in sets],
            "spreads_trimmed": [spread(trimmed(v)) for v in sets],
            "values": sets}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace-seeds", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]

    out = Path(args.out) if args.out else None
    if out is not None:
        out.parent.mkdir(parents=True, exist_ok=True)

    def emit(obj):
        line = json.dumps(obj)
        print(line, flush=True)
        if out is not None:              # line by line: a cut call keeps it
            with open(out, "a") as f:
                f.write(line + "\n")

    runs_by_set = []
    for k in range(args.sets if seeds else 0):
        runs = []
        for seed in seeds:
            r = one_run(args.workload, seed, args.seconds, 0)
            emit({"set": k, **r})
            runs.append(r)
        runs_by_set.append(runs)
    for seed in (int(s) for s in args.trace_seeds.split(",") if s):
        emit({"set": "trace", **one_run(args.workload, seed, args.seconds,
                                        1)})
    if runs_by_set:
        emit({"summary": summarise(runs_by_set)})
    return 0


if __name__ == "__main__":
    sys.exit(main())
