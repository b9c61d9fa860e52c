"""Readings that set a cell's correctness limit, on the chip.

    python3 -m benchmarks.chip.tools.control --workload <cell> \
        --seeds 1,2,3 --seconds 20 [--out chiprun_out/control.jsonl]

For each seed, in one process: the cell's served cluster with that seed's
weights, a window of the cell's own traffic at its own load, the same
seeded sample a benchmark run checks, the program's state freed, then the
float32 reference over the sample. Two numbers per seed:

* ``program``: the widest gap of a served token below the reference's
  best logit (what a run compares; its largest over the seeds is the
  lower reading);
* ``control``: the same gap for the token that the reference computed in
  float8 e4m3 puts first at each of those positions (its smallest over the
  seeds is the upper reading).

Benchmark runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    from benchmarks.chip import check, program, run
    from benchmarks.chip.traffic import plan
    program.use_compile_cache()
    import jax
    _, cell, cfg, traffic = run.load_cell(args.workload)
    devices = jax.devices()[:cell["chips"]]
    c = cfg["check"]
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        s = run.Session(cfg, traffic, seed, devices)
        planned = plan(traffic, cfg["deployment"]["max_len"], seed,
                       args.seconds)
        rec = s.serve(planned, args.seconds, float(traffic["drain_s"]),
                      float(traffic["warm_s"]))
        dtype = s.dtype
        s.close()
        picked = check.sample(rec.finished, seed, c["sample_tokens"],
                              c["max_requests"])
        ref = check.Reference(cfg, seed, dtype)
        gap, covered = check.widest_gap(ref, picked)
        ctrl = check.control_gap(ref, picked)
        del ref
        line = json.dumps({
            "workload": cell["name"], "seed": seed, "program": gap,
            "control": ctrl, "tokens": covered,
            "requests": [[p[0], p[1], len(p[2]), p[3]] for p in picked],
            "short_streams": rec.short_streams,
            "seconds": time.perf_counter() - t})
        print(line, flush=True)
        if args.out:
            Path(args.out).parent.mkdir(parents=True, exist_ok=True)
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
