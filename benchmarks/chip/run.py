"""One run of one benchmark cell on the chips of this machine.

    python3 -m benchmarks.chip --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json``; its configuration,
traffic mix and per-layer metric readers are the files of those names
under ``configs/``, ``traffic/`` and ``metrics/`` beside this file.

A run: set-up (compile cache, the served cluster with weights made on the
device from the seed and the program's own warmup, then the warm phase of
open-loop traffic) -> the measured window of ``--seconds`` -> drain until
the window's requests are done (``stats`` states the rule) -> the peak of
device memory -> the program's state freed -> the correctness check
(``check``). ``--trace 0`` reports the cell's end-to-end metrics;
``--trace 1`` traces a slice of the window with the profiler and reports
its per-layer metrics.

With no TPU, or fewer chips than the cell asks for, it exits 1 with no
result line. The last line of standard output is the result.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
import types
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
TRACE_SLICE_S = 5.0          # seconds the profiler records, from mid-window


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: Path = ROOT) -> tuple[dict, dict, dict, dict]:
    """(BENCHMARK.json, its workload entry, configuration, traffic)."""
    bench = _load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    cfg = _load_json(HERE / "configs" / f"{cell['config']}.json")
    traffic = _load_json(HERE / "traffic" / f"{cell['traffic']}.json")
    return bench, cell, cfg, traffic


def reader(metric: str):
    path = HERE / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "benchmarks.chip.metrics." + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench: dict, cell: str, trace: bool) -> list[dict]:
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


class Session:
    """The served cluster of one cell (set-up) and one planned load
    served on it."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, devices,
                 faults=None):
        import jax.numpy as jnp

        from benchmarks.chip import driver, program
        from benchmarks.chip.reference.dense_lm import spec_of
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.devices = list(devices)
        self.dtype = jnp.dtype(cfg["deployment"]["dtype"])
        self.spec = spec_of(cfg)
        self.compiles = driver.CompileClock()
        self.sched, self.execs = program.serve(cfg, traffic, seed, devices,
                                               self.dtype)
        self.inner = self.sched.backend
        if faults is not None:
            faults(self.execs)

    def serve(self, planned: list, seconds: float, drain_s: float,
              warm_s: float, trace: bool = False,
              trace_dir: Path | None = None):
        """Offer ``planned`` on the host's clock, the window opening
        ``warm_s`` from now; returns the window's record. Work still in
        flight at the cut is abandoned: serve once per session."""
        import jax

        from benchmarks.chip import devtrace, driver, program, stats
        slo = self.cfg["slo"]
        reqs = program.requests(planned, slo)
        by_rid = {r.rid: r for r in reqs}
        window_rids = {p.rid for p in planned if p.phase == "window"}
        t0 = time.perf_counter() + warm_s
        backend = driver.TimedBackend(self.inner, time.perf_counter, t0,
                                      annotate=trace)
        drv = driver.WallDriver(self.sched, backend, annotate=trace)
        marks: dict[str, tuple] = {}
        tracing: dict = {}
        slice_lo = max(0.0, 0.5 * (seconds - TRACE_SLICE_S))

        def snapshot():
            return (self.compiles.count, drv.handle_seconds,
                    backend.backend_seconds, len(backend.iterations))

        def hook(now: float) -> None:
            if "open" not in marks and now >= 0.0:
                marks["open"] = snapshot()
            if trace and "on" not in tracing and now >= slice_lo:
                jax.profiler.start_trace(str(trace_dir))
                tracing["end"] = time.perf_counter() - t0 + TRACE_SLICE_S
                tracing["on"] = jax.profiler.TraceAnnotation(
                    devtrace.WINDOW_SPAN)
                tracing["on"].__enter__()
            if "on" in tracing and "off" not in tracing \
                    and now >= tracing["end"]:
                tracing["on"].__exit__(None, None, None)
                jax.profiler.stop_trace()
                tracing["off"] = True
            if "close" not in marks and now >= seconds:
                marks["close"] = snapshot()

        def done(now: float) -> bool:
            if now < seconds:
                return False
            return now >= seconds + drain_s or all(
                by_rid[r].finish_time is not None for r in window_rids)

        cut = drv.run(reqs, t0, done, hook)
        hook(max(cut, seconds, tracing.get("end", 0.0)))
        open_, close = marks["open"], marks["close"]
        served = program.served_tokens(self.execs)
        migrated = {rid for rid, _, _ in self.execs.migrations}
        outcomes = [stats.outcome(p.rid, p.due, p.prompt_len,
                                  by_rid[p.rid].first_token_time,
                                  by_rid[p.rid].finish_time,
                                  by_rid[p.rid].streamed_tokens, cut, slo)
                    for p in planned if p.rid in window_rids]
        finished = [(p.rid, p.prompt_len, served.get(p.rid, []),
                     p.rid in migrated) for p in planned
                    if by_rid[p.rid].finish_time is not None]
        lateness = sorted(late for _, late in drv.lateness)
        tokens_in_window = sum(it.tokens_out for it in backend.iterations
                               if 0.0 <= it.start + it.seconds < seconds)
        rec = types.SimpleNamespace(
            t0=t0, cut=cut, outcomes=outcomes, finished=finished,
            e2e=stats.end_to_end(outcomes, tokens_in_window, seconds),
            iterations=backend.iterations[open_[3]:close[3]],
            handle_s=close[1] - open_[1], backend_s=close[2] - open_[2],
            compiles_in_window=close[0] - open_[0],
            window_rids=window_rids, migrated_rids=migrated,
            short_streams=sum(1 for r, _, toks, _ in finished
                              if len(toks) != by_rid[r].output_len),
            restarted=sum(1 for r in window_rids
                          if by_rid[r].restarts or by_rid[r].preemptions),
            lateness_median=stats.percentile(lateness, 50),
            lateness_max=lateness[-1] if lateness else math.nan,
            spec=self.spec, trace=None)
        if trace:
            ops, spans, win = devtrace.load(trace_dir)
            rec.trace = devtrace.reduce(ops, spans, win, len(self.devices))
            if not ops:
                print("trace: no device operations; planes: "
                      + json.dumps(devtrace.describe(trace_dir, 1))[:4000],
                      file=sys.stderr)
        return rec

    def memory_peak(self) -> int:
        return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in self.devices)

    def close(self) -> None:
        """Drop the program's device state (caches, weights)."""
        self.sched = self.execs = self.inner = None
        gc.collect()


def check_record(rec, cfg: dict, seed: int, dtype) -> None:
    """Run the reference over a seeded sample of what the window served;
    sets ``rec.checks``, ``rec.correct``, ``rec.check_s``."""
    from benchmarks.chip import check
    c = cfg["check"]
    picked = check.sample(rec.finished, seed, c["sample_tokens"],
                          c["max_requests"])
    t = time.perf_counter()
    ref = check.Reference(cfg, seed, dtype)
    gap, covered = check.widest_gap(ref, picked)
    del ref
    gc.collect()
    rec.check_s = time.perf_counter() - t
    rec.checked_tokens = covered
    rec.checks = {
        "widest_logit_gap": {"value": gap, "limit": c["gap_limit"]},
        "short_streams": {"value": rec.short_streams, "limit": 0},
    }
    rec.correct = (gap <= c["gap_limit"] and rec.short_streams == 0
                   and covered > 0)


def run_cell(cfg: dict, traffic: dict, seed: int, seconds: float,
             trace: bool, devices, t_start: float,
             trace_dir: Path | None = None, faults=None):
    """Set up, serve one window, read the memory peak, free the program's
    state, and check. ``faults`` (tests only) breaks the timed path."""
    from benchmarks.chip.traffic import plan
    s = Session(cfg, traffic, seed, devices, faults)
    if trace:
        # the profiler's first start is slow (tens of seconds on a v5e):
        # pay it in set-up, not in the traced slice
        import jax
        jax.profiler.start_trace(str(trace_dir / "warm"))
        jax.profiler.stop_trace()
        shutil.rmtree(trace_dir / "warm", ignore_errors=True)
    planned = plan(traffic, cfg["deployment"]["max_len"], seed, seconds)
    rec = s.serve(planned, seconds, float(traffic["drain_s"]),
                  float(traffic["warm_s"]), trace, trace_dir)
    rec.setup_s = rec.t0 - t_start
    rec.mem_peak = s.memory_peak()
    rec.compiles_total = s.compiles.count
    dtype = s.dtype
    s.close()
    check_record(rec, cfg, seed, dtype)
    return rec


def result_line(bench: dict, cell: dict, rec, trace: bool,
                devices) -> dict:
    from benchmarks.chip import flops
    dev = devices[0]
    rec.peaks = flops.peaks(dev.device_kind)
    metrics = {}
    for m in cell_metrics(bench, cell["name"], trace):
        if trace:
            value = reader(m["name"])(rec)
        elif m["name"] == "setup_s":
            value = rec.setup_s
        else:
            value = rec.e2e[m["name"]]
        if value is None or (isinstance(value, float) and math.isnan(value)):
            continue
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": rec.mem_peak}
    out = {"correct": rec.correct, "attempted": len(rec.outcomes),
           "failed": sum(1 for o in rec.outcomes if o.tpot is None
                         and not o.finished),
           "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = rec.trace["busy_s"]
        device["window_s"] = rec.trace["window_s"]
        out["breakdown"] = {"device_ops": rec.trace["device_ops"],
                            "idle_gaps": rec.trace["idle_gaps"]}
    out["checks"] = rec.checks
    return out


def main(argv=None, t_start: float | None = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)
    bench, cell, cfg, traffic = load_cell(args.workload)

    from benchmarks.chip import program
    program.use_compile_cache()
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"benchmark: cell {cell['name']} needs {cell['chips']} TPU "
              f"chip(s); JAX found {len(devices)} {devices[0].platform} "
              "device(s). Nothing was run.", file=sys.stderr)
        return 1
    from benchmarks.chip import flops
    flops.peaks(devices[0].device_kind)        # unknown kind: an error
    devices = devices[:cell["chips"]]
    trace_dir = Path(tempfile.mkdtemp(prefix="bench_trace_")) \
        if args.trace else None
    try:
        rec = run_cell(cfg, traffic, args.seed, args.seconds,
                       bool(args.trace), devices, t_start, trace_dir)
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    out = result_line(bench, cell, rec, bool(args.trace), devices)
    print(f"lateness_s: median={rec.lateness_median} max={rec.lateness_max}")
    print(f"window: requests={len(rec.outcomes)} cut_s={rec.cut} "
          f"iterations={len(rec.iterations)} compiles_total="
          f"{rec.compiles_total} restarted={rec.restarted} "
          f"checked_tokens={rec.checked_tokens} check_s={rec.check_s}")
    for name, c in rec.checks.items():
        print(f"check {name}: {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(json.dumps(out))
    return 0
