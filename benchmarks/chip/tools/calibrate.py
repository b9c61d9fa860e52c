"""Light-load latencies and a rate sweep of one configuration on the chip.

    python3 -m benchmarks.chip.tools.calibrate --config qwen2-1.5b \
        --traffic mooncake.w1 --seed 11 --light 64,512,2048,4096,7936 \
        --rates 1.5,2.5,3.5 --window 30 --warm 20

One process; each phase on a fresh served cluster (``run.Session``):

1. probe (``--probe-rate``): a traced 12 s window; lists the trace's
   planes and lines and writes a 50 ms excerpt (``--excerpt``).
2. light load: one request at a time (``--light`` prompt lengths, twice
   each, ``--light-output`` tokens, ``--gap`` seconds apart). TTFT is
   fitted as ``a + b * prompt_len`` by least squares; the TPOT reading is
   the median token gap. The SLO printed is ``--slo-scale`` times these
   (paper §V-A: 5x the light-load latency). Skipped with ``--slo``.
3. sweep: for each rate, in rising order, the traffic mix's open loop
   (warm phase, window, drain) under that SLO, with the end-to-end
   numbers and how long the window's requests took to drain; it stops
   after the first rate whose attainment is under one half.

Prints one JSON line per phase; with ``--out`` also writes them there.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parents[1]


def light(session, lengths, output, gap):
    from benchmarks.chip.traffic import Planned
    planned = [Planned(i, i * gap, L, output, "window")
               for i, L in enumerate(lengths)]
    rec = session.serve(planned, len(planned) * gap, 60.0, 2.0)
    rows = [(o.prompt_len, o.ttft, o.tpot) for o in rec.outcomes]
    x = np.array([r[0] for r in rows], float)
    y = np.array([r[1] for r in rows], float)
    b, a = np.polyfit(x, y, 1)
    tpot = float(np.median([r[2] for r in rows if r[2] is not None]))
    return {"rows": rows, "ttft_a_s": float(a), "ttft_b_s_per_token": float(b),
            "tpot_s": tpot, "compiles_in_window": rec.compiles_in_window}


def sweep_one(session, traffic, rate, window, warm, seed, trace_dir=None):
    from benchmarks.chip.traffic import plan
    t = dict(traffic, rate_rps=rate, warm_s=warm)
    planned = plan(t, session.cfg["deployment"]["max_len"], seed, window)
    rec = session.serve(planned, window, float(t["drain_s"]), warm,
                        trace=trace_dir is not None, trace_dir=trace_dir)
    return {"rate": rate, **rec.e2e, "requests": len(rec.outcomes),
            "failed": sum(not o.finished for o in rec.outcomes),
            "cut_s": rec.cut, "drain_s_used": rec.cut - window,
            "compiles_in_window": rec.compiles_in_window,
            "lateness_max": rec.lateness_max,
            "decode_iter_ms": 1e3 * float(np.mean(
                [it.seconds for it in rec.iterations if it.kind == "decode"]
                or [np.nan])),
            "mixed_iter_ms": 1e3 * float(np.mean(
                [it.seconds for it in rec.iterations if it.kind == "mixed"]
                or [np.nan])),
            "iterations": len(rec.iterations),
            "migrated": len(rec.migrated_rids & rec.window_rids),
            "restarted": rec.restarted, "trace": rec.trace}


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--light", default="")
    ap.add_argument("--light-output", type=int, default=32)
    ap.add_argument("--gap", type=float, default=2.0)
    ap.add_argument("--slo-scale", type=float, default=5.0)
    ap.add_argument("--slo", default=None,
                    help="a,b,tpot: skip the light phase, use these limits")
    ap.add_argument("--rates", default="")
    ap.add_argument("--window", type=float, default=30.0)
    ap.add_argument("--warm", type=float, default=20.0)
    ap.add_argument("--workers", type=int, default=None)
    ap.add_argument("--out", default=None)
    ap.add_argument("--excerpt", default=None,
                    help="with --probe-rate: write the first 50 ms of the "
                         "traced window here (a small recorded trace)")
    ap.add_argument("--probe-rate", type=float, default=None,
                    help="also trace a 12 s window at this rate and list "
                         "the trace's planes and lines")
    args = ap.parse_args(argv)

    from benchmarks.chip import program, run
    program.use_compile_cache()
    import jax
    cfg = json.loads((HERE / "configs" / f"{args.config}.json").read_text())
    traffic = json.loads(
        (HERE / "traffic" / f"{args.traffic}.json").read_text())
    if args.workers:
        traffic["workers"] = args.workers
    devices = jax.devices()[:traffic["workers"]]
    lines = []

    def emit(obj):
        line = json.dumps(obj)
        print(line, flush=True)
        lines.append(line)

    if args.slo:
        a, b, tp = (float(v) for v in args.slo.split(","))
        cfg["slo"] = {"ttft_base_s": a, "ttft_per_token_s": b, "tpot_s": tp}
    else:
        cfg["slo"] = {"ttft_base_s": 1e3, "ttft_per_token_s": 0.0,
                      "tpot_s": 1e3}
    def session():
        s = run.Session(cfg, traffic, args.seed, devices)
        emit({"phase": "setup", "seconds": time.perf_counter() - t_start,
              "compiles": s.compiles.count,
              "device": devices[0].device_kind, "count": len(devices)})
        return s

    peak = 0
    if args.probe_rate:
        import tempfile
        from benchmarks.chip import devtrace
        d = Path(tempfile.mkdtemp(prefix="probe_"))
        s = session()
        try:
            emit({"phase": "probe", **sweep_one(
                s, traffic, args.probe_rate, 12.0, args.warm, args.seed,
                trace_dir=d)})
            if args.excerpt:
                ops, spans, win = devtrace.load(d)
                devtrace.write_excerpt(args.excerpt, ops, spans, win)
        except Exception as e:         # the sweep still runs: report it
            emit({"phase": "probe_error", "error": repr(e)})
        emit({"phase": "probe_planes", "planes": devtrace.describe(d)})
        peak = max(peak, s.memory_peak())
        s.close()
    if not args.slo and args.light:
        s = session()
        res = light(s, [int(v) for v in args.light.split(",")] * 2,
                    args.light_output, args.gap)
        peak = max(peak, s.memory_peak())
        s.close()
        k = args.slo_scale
        cfg["slo"] = {"ttft_base_s": k * res["ttft_a_s"],
                      "ttft_per_token_s": k * res["ttft_b_s_per_token"],
                      "tpot_s": k * res["tpot_s"]}
        emit({"phase": "light", **res, "slo": cfg["slo"]})
    for rate in sorted(float(r) for r in args.rates.split(",") if r):
        s = session()
        res = sweep_one(s, traffic, rate, args.window, args.warm, args.seed)
        peak = max(peak, s.memory_peak())
        s.close()
        emit({"phase": "sweep", **res})
        if res["slo_attainment"] < 0.5:
            break
    emit({"phase": "done", "memory_peak_bytes": peak,
          "seconds": time.perf_counter() - t_start})
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text("\n".join(lines) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
