"""Define one cell on the chip in one call: its rate, then its readings.

    python3 -m benchmarks.chip.tools.define_cell --workload <cell> \
        --rates 0.4,0.6,0.8 --control-seeds 1,2,3 \
        --set-seeds 11,12,13,14,15,16 --trace-seeds 21,22,23

This parent never touches JAX; each step is a child process that holds
the chip alone, and writes its lines under ``chiprun_out/``:

1. a sweep (``tools/calibrate.py``, after a traced probe with
   ``--probe``) under the configuration's SLO limits; the knee is the
   highest rate whose attainment is >= 0.9 (the sweep stops at the first
   rate under one half);
2. the cell's traffic file gets ``rate_rps`` = 0.8 x knee;
3. control readings (``tools/control.py``) at that rate;
4. two sets of runs and the traced runs (``tools/sets.py``).
"""
from __future__ import annotations

import argparse
import json
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
OUT = HERE.parents[1] / "chiprun_out"


def knee(sweep: list[dict]) -> float | None:
    ok = [s["rate"] for s in sweep if s["slo_attainment"] >= 0.9]
    return max(ok) if ok else None


def child(args: list[str]) -> int:
    print("+", " ".join(args), flush=True)
    return subprocess.run([sys.executable, "-m", *args]).returncode


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--sweep-window", type=float, default=30.0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--control-seconds", type=float, default=20.0)
    ap.add_argument("--set-seeds", default="")
    ap.add_argument("--trace-seeds", default="")
    ap.add_argument("--probe", action="store_true",
                    help="trace a probe window first (planes, excerpt)")
    args = ap.parse_args(argv)
    bench = json.loads((HERE.parents[1] / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    seconds = args.seconds or bench["run_seconds"]
    cfg = json.loads((HERE / "configs" / f"{cell['config']}.json")
                     .read_text())
    tpath = HERE / "traffic" / f"{cell['traffic']}.json"
    traffic = json.loads(tpath.read_text())
    slo = cfg["slo"]
    tag = cell["name"]
    OUT.mkdir(exist_ok=True)

    sweep_out = OUT / f"sweep_{tag}.jsonl"
    child(["benchmarks.chip.tools.calibrate", "--config", cell["config"],
           "--traffic", cell["traffic"], "--seed", "7",
           "--slo", f"{slo['ttft_base_s']},{slo['ttft_per_token_s']},"
                    f"{slo['tpot_s']}",
           "--rates", args.rates, "--window", str(args.sweep_window),
           "--warm", str(traffic["warm_s"]), "--out", str(sweep_out)]
          + (["--probe-rate", args.rates.split(",")[0], "--excerpt",
              str(OUT / f"trace_excerpt_{tag}.json")] if args.probe else []))
    sweep = [json.loads(line) for line in sweep_out.read_text().splitlines()
             if '"phase": "sweep"' in line] if sweep_out.exists() else []
    k = knee(sweep)
    rates = sorted(float(r) for r in args.rates.split(","))
    rate = round(0.8 * (k if k is not None else rates[0]), 4)
    traffic["rate_rps"] = rate
    tpath.write_text(json.dumps(traffic, indent=2) + "\n")
    print(json.dumps({"workload": tag, "knee": k, "rate_rps": rate}),
          flush=True)
    (OUT / f"rate_{tag}.json").write_text(json.dumps(
        {"workload": tag, "knee": k, "rate_rps": rate, "sweep": sweep}))

    if args.control_seeds:
        child(["benchmarks.chip.tools.control", "--workload", tag,
               "--seeds", args.control_seeds,
               "--seconds", str(args.control_seconds),
               "--out", str(OUT / f"control_{tag}.jsonl")])
    if args.set_seeds or args.trace_seeds:
        child(["benchmarks.chip.tools.sets", "--workload", tag,
               "--seeds", args.set_seeds, "--trace-seeds", args.trace_seeds,
               "--seconds", str(seconds),
               "--out", str(OUT / f"sets_{tag}.jsonl")])
    return 0


if __name__ == "__main__":
    sys.exit(main())
