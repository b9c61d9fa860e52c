"""Serving launcher.

Two modes:
  * ``--mode sim`` (default): cluster-scale discrete-event run with the
    analytical v5e executor — the configuration used for the paper-figure
    benchmarks; scales to hundreds of workers.
  * ``--mode real``: drives the same ``ClusterScheduler`` against REAL JAX
    model execution on this host's devices through the ``RealJaxBackend``
    — the published config at its full widths (``--smoke`` swaps in the
    reduced same-family config for CPU runs). Each worker holds a slotted
    KV cache of ``--max-slots`` x ``--max-len`` positions, and every
    request is clipped to fit one slot (``clip_to_cache``).

``--json`` prints one stable, versioned metrics object on stdout
(``schema_version`` bumps on breaking changes; keys are sorted) so scripts
can parse runs without scraping the human-readable table. ``--seed``
drives trace synthesis AND real-executor weight init, making whole runs
reproducible.

Multi-tenant runs: ``--scenario`` picks a named workload from
``repro.workload`` (bursty / diurnal / longctx / agentic / mixture / …)
and ``--slo-classes`` defines explicit SLO tiers, e.g.::

    --slo-classes "interactive:ttft=1.0,tpot=0.05,weight=2,frac=0.6;\
batch:ttft=10,tpot=0.5,frac=0.4"

(``ttft``/``tpot`` in seconds, or ``scale=K`` for K x the light-load
latency per §V-A; ``frac`` splits the arrival rate, default equal;
``weight`` enters the weighted attainment). The JSON object then carries a
``per_class`` block and ``weighted_attainment``.

``schema_version`` history: 2 added the per_class block +
weighted_attainment (breaking the v1 aggregate-only layout); 3 added the
tiered-KV / prefix-reuse counters (kv_offloads, kv_restores,
pages_offloaded, pages_restored, pages_reprefilled, prefix_lookups,
prefix_hits, prefix_hit_rate — all zero unless ``--host-kv-gb`` /
``--prefix-cache`` arm the features). v3 is additive over v2: every v2
key keeps its meaning.

Examples:
  PYTHONPATH=src python -m repro.launch.serve --arch internlm-20b \
      --policy tropical --rate 2.0 --duration 120
  PYTHONPATH=src python -m repro.launch.serve --mode real --smoke \
      --arch qwen2-1.5b --policy tropical --rate 2.0 --duration 20 \
      --workers 2 --max-slots 8 --max-len 256
  PYTHONPATH=src python -m repro.launch.serve --scenario mixture --json
"""
from __future__ import annotations

import argparse
import json
from typing import Optional, Sequence

METRICS_SCHEMA_VERSION = 3     # v3: tiered-KV + prefix-reuse counters


def parse_slo_classes(spec: str) -> list[dict]:
    """Parse ``name:key=val,...;name:key=val,...`` into class descriptors.

    Keys: ``ttft``/``tpot`` (seconds), ``scale`` (K x the light-load phase
    latency, resolved against the cost model later), ``weight`` (default
    1), ``frac`` (rate share, default equal split). Raises ValueError with
    the offending fragment on malformed input."""
    classes = []
    for part in filter(None, (p.strip() for p in spec.split(";"))):
        name, _, body = part.partition(":")
        name = name.strip()
        if not name or not body.strip():
            raise ValueError(f"malformed class spec {part!r} "
                             "(want name:key=val,...)")
        cls = {"name": name, "weight": 1.0, "frac": None,
               "ttft": None, "tpot": None, "scale": None}
        for kv in filter(None, (s.strip() for s in body.split(","))):
            key, eq, val = kv.partition("=")
            key = key.strip()
            if not eq or key not in ("ttft", "tpot", "scale", "weight",
                                     "frac"):
                raise ValueError(f"unknown key in class {name!r}: {kv!r}")
            try:
                cls[key] = float(val)
            except ValueError:
                raise ValueError(
                    f"class {name!r}: {key} must be a number, "
                    f"got {val!r}") from None
        has_any_abs = cls["ttft"] is not None or cls["tpot"] is not None
        has_abs = cls["ttft"] is not None and cls["tpot"] is not None
        if cls["scale"] is not None and has_any_abs:
            raise ValueError(
                f"class {name!r}: give ttft=+tpot= (seconds) OR scale=, "
                "not both")
        if not has_abs and cls["scale"] is None:
            raise ValueError(
                f"class {name!r} needs ttft=+tpot= (seconds) or scale=")
        for key in ("ttft", "tpot", "scale", "weight"):
            if cls[key] is not None and cls[key] <= 0:
                raise ValueError(f"class {name!r}: {key} must be > 0")
        if cls["frac"] is not None and not 0.0 < cls["frac"] <= 1.0:
            raise ValueError(f"class {name!r}: frac must be in (0, 1]")
        classes.append(cls)
    if not classes:
        raise ValueError("empty --slo-classes spec")
    names = [c["name"] for c in classes]
    if len(set(names)) != len(names):
        raise ValueError(f"duplicate class names in spec: {names}")
    assigned = sum(c["frac"] for c in classes if c["frac"] is not None)
    if assigned > 1.0 + 1e-9:
        raise ValueError(
            f"class rate fracs sum to {assigned:g} > 1 (they split --rate)")
    unassigned = [c for c in classes if c["frac"] is None]
    if unassigned:
        left = 1.0 - assigned
        if left <= 1e-9:
            raise ValueError(
                "explicit fracs consume the whole rate but "
                + ", ".join(c["name"] for c in unassigned)
                + " carries no frac= — it would get zero traffic")
        for c in unassigned:
            c["frac"] = left / len(unassigned)
    return classes


def _classes_scenario(classes: list[dict], cost) -> "object":
    """Build a mixture Scenario from parsed --slo-classes descriptors:
    every class shares the mooncake-like profile and arrival process, but
    carries its own SLO tier and rate share."""
    from repro.core.request import SLOClass
    from repro.workload import (GammaPoisson, MOONCAKE, Scenario,
                                ScenarioComponent)
    comps = []
    for c in classes:
        if c["ttft"] is not None and c["tpot"] is not None:
            ttft, tpot = c["ttft"], c["tpot"]
        else:
            k = c["scale"]
            ttft = k * cost.prefill_time(int(MOONCAKE.body_median * 4))
            tpot = k * cost.decode_iter_time(
                1, float(MOONCAKE.body_median * 4))
        slo = SLOClass(ttft=ttft, tpot=tpot, name=c["name"],
                       weight=c["weight"])
        comps.append(ScenarioComponent(
            name=c["name"], profile=MOONCAKE, arrivals=GammaPoisson(),
            rate_frac=c["frac"], slo=slo, weight=c["weight"]))
    return Scenario("slo-classes", tuple(comps))


def clip_to_cache(reqs, max_len: int) -> None:
    """Fit every request into one KV cache slot of ``max_len`` positions:
    the output keeps at most half the slot and the prompt takes the rest,
    so ``prompt_len + output_len <= max_len``."""
    for r in reqs:
        r.output_len = min(r.output_len, max_len // 2)
        r.prompt_len = min(r.prompt_len, max_len - r.output_len)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm-20b")
    ap.add_argument("--policy", default="tropical",
                    choices=["vllm", "sarathi", "distserve", "tropical",
                             "tropical++"])
    ap.add_argument("--mode", default="sim", choices=["sim", "real"])
    ap.add_argument("--smoke", action="store_true",
                    help="use the arch's reduced same-family config (CPU "
                         "test scale) instead of the published one")
    ap.add_argument("--max-slots", type=int, default=16,
                    help="real mode: KV cache slots (concurrent requests) "
                         "per worker")
    ap.add_argument("--max-len", type=int, default=2048,
                    help="real mode: KV cache positions per slot; each "
                         "request is clipped to prompt + output <= this")
    ap.add_argument("--backend", default="cost-model",
                    choices=["cost-model", "trace-replay"],
                    help="sim-mode execution backend: 'cost-model' "
                         "materialises the trace up front; 'trace-replay' "
                         "streams arrivals lazily through a "
                         "TraceReplayBackend (constant-memory replay of "
                         "recorded/synthesised traces; identical "
                         "decisions)")
    ap.add_argument("--rate", type=float, default=2.0)
    ap.add_argument("--duration", type=float, default=120.0)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--tp", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0,
                    help="trace synthesis + real-executor init seed")
    ap.add_argument("--scenario", default="mooncake",
                    help="named workload scenario (repro.workload.SCENARIOS"
                         "; 'mooncake' keeps the legacy §V-A trace)")
    ap.add_argument("--slo-classes", default=None, metavar="SPEC",
                    help="multi-tenant SLO tiers: 'name:ttft=S,tpot=S,"
                         "weight=W,frac=F;...' (or scale=K per §V-A); "
                         "defines its own mixture workload (mutually "
                         "exclusive with a non-default --scenario) or "
                         "maps a --trace-csv slo_class column")
    ap.add_argument("--trace-csv", default=None, metavar="PATH",
                    help="replay a recorded Mooncake-schema CSV instead of "
                         "synthesising (--rate/--duration/--scenario are "
                         "ignored for arrivals)")
    ap.add_argument("--fail-worker", type=int, default=None,
                    help="inject a worker failure at duration/2")
    ap.add_argument("--ici-bw", type=float, default=None, metavar="GBPS",
                    help="per-link KV migration bandwidth in GB/s "
                         "(default: hardware spec, 50 GB/s on v5e)")
    ap.add_argument("--ici-links", type=int, default=None,
                    help="usable P2P links per worker (default 2)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="KV block granularity in tokens")
    ap.add_argument("--no-transfer-engine", action="store_true",
                    help="legacy fixed-delay migrations (no link contention)")
    ap.add_argument("--host-kv-gb", type=float, default=0.0, metavar="GB",
                    help="per-worker host-DRAM KV tier: watermark victims "
                         "offload over the host DMA link instead of evict + "
                         "full re-prefill when the predictor prices restore "
                         "cheaper (default 0 = seed behaviour)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="per-worker cross-request prefix cache: requests "
                         "sharing a workload-tagged system prompt skip the "
                         "cached span of prefill")
    ap.add_argument("--online-predictor", action="store_true",
                    help="EWMA-correct the §IV-C predictor from observed "
                         "iteration durations (wall-clock in --mode real)")
    ap.add_argument("--recalibrate-every", type=int, default=None,
                    metavar="N",
                    help="online drift recalibration: every N observed "
                         "iterations re-fit the per-bucket interference "
                         "gamma and nudge the measured MFU/bandwidth "
                         "constants from residuals (default: off = "
                         "calibrate once at startup)")
    ap.add_argument("--no-rebalance", action="store_true",
                    help="keep the legacy dispatch-count role review "
                         "instead of windowed-attainment rebalancing")
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--reference", action="store_true",
                    help="run the scalar reference scheduler/engine "
                         "instead of the vectorized fast paths (decisions "
                         "and metrics are bit-identical; this is the "
                         "parity baseline, ~2-10x slower)")
    ap.add_argument("--profile", action="store_true",
                    help="run the simulation under cProfile; print the "
                         "top-25 cumulative-time entries to stderr")
    return ap


def main(argv: Optional[Sequence[str]] = None) -> dict:
    ap = build_parser()
    args = ap.parse_args(argv)
    if args.ici_bw is not None and args.ici_bw <= 0:
        ap.error("--ici-bw must be > 0 (migrated KV can never arrive "
                 "over a zero-bandwidth link)")
    if args.ici_links is not None and args.ici_links <= 0:
        ap.error("--ici-links must be > 0 (zero links stall every "
                 "migration forever)")
    if args.page_size <= 0:
        ap.error("--page-size must be a positive token count")
    if args.recalibrate_every is not None and args.recalibrate_every < 1:
        ap.error("--recalibrate-every must be >= 1 iteration "
                 "(omit the flag to disable online recalibration)")
    if args.host_kv_gb < 0:
        ap.error("--host-kv-gb must be >= 0 (0 disables the host tier)")
    if args.max_slots < 1:
        ap.error("--max-slots must be >= 1")
    if args.max_len < 2:
        ap.error("--max-len must be >= 2 (one prompt + one output token)")

    from repro.configs import get_config, get_smoke
    from repro.serving.costmodel import WorkerSpec
    from repro.serving.simulator import build_cluster
    from repro.workload import SCENARIOS, generate_trace, get_scenario, \
        load_csv, replay_csv

    if args.backend == "trace-replay" and args.mode == "real":
        ap.error("--backend trace-replay streams the simulated clock; "
                 "--mode real owns its own backend (drop one of the flags)")

    if args.scenario not in SCENARIOS:
        ap.error(f"--scenario must be one of {sorted(SCENARIOS)}")
    classes = None
    if args.slo_classes is not None:
        try:
            classes = parse_slo_classes(args.slo_classes)
        except ValueError as e:
            ap.error(f"--slo-classes: {e}")
        if args.scenario != "mooncake" and not args.trace_csv:
            # --slo-classes builds its own mixture workload (one mooncake
            # component per class); silently discarding the named
            # scenario's profiles would measure a different workload than
            # requested
            ap.error("--slo-classes defines its own workload and cannot "
                     "be combined with --scenario (use --trace-csv to "
                     "replay recorded traffic under these tiers, or drop "
                     "one of the flags)")

    cfg = (get_smoke if args.smoke else get_config)(args.arch)
    spec = WorkerSpec(tp=1 if args.mode == "real" else args.tp)

    sim, cost = build_cluster(
        cfg, args.policy, n_workers=args.workers, worker_spec=spec,
        use_transfer_engine=not args.no_transfer_engine,
        ici_bw=args.ici_bw * 1e9 if args.ici_bw is not None else None,
        ici_links=args.ici_links, page_size=args.page_size,
        online_predictor=args.online_predictor,
        recalibrate_every=args.recalibrate_every,
        role_rebalance=False if args.no_rebalance else "auto",
        host_kv_gb=args.host_kv_gb, prefix_cache=args.prefix_cache,
        vectorized=not args.reference)
    # one workload-source selection for both feeds: each leaf names the
    # (materialised, streaming) pair so --backend trace-replay can never
    # diverge from the default path on *which* workload runs
    streaming = args.backend == "trace-replay"
    if classes is not None:
        scenario = _classes_scenario(classes, cost)
        if args.trace_csv:
            feed = replay_csv(args.trace_csv, cost,
                              classes=scenario.classes) if streaming \
                else load_csv(args.trace_csv, cost, classes=scenario.classes)
        else:
            feed = (scenario.replay if streaming else scenario.generate)(
                args.rate, args.duration, cost, seed=args.seed)
    elif args.trace_csv:
        feed = replay_csv(args.trace_csv, cost) if streaming \
            else load_csv(args.trace_csv, cost)
    elif args.scenario != "mooncake":
        scenario = get_scenario(args.scenario)
        feed = (scenario.replay if streaming else scenario.generate)(
            args.rate, args.duration, cost, seed=args.seed)
    else:
        # legacy single-class path: RNG-stream identical to pre-workload
        # releases, so seeded runs reproduce bit-exactly
        trace = generate_trace(args.rate, args.duration, cost,
                               seed=args.seed)
        feed = ((r.arrival_time, r) for r in trace) if streaming else trace

    execs = None
    if streaming:
        sim.add_replay(feed)
    else:
        if args.mode == "real":
            import jax
            from repro.compile_cache import use_compile_cache
            from repro.serving.executor import ClusterRealExecutors
            use_compile_cache()
            clip_to_cache(feed, args.max_len)
            execs = ClusterRealExecutors(cfg, args.workers,
                                         max_slots=args.max_slots,
                                         max_len=args.max_len,
                                         rng=jax.random.PRNGKey(args.seed))
            sim.sched.backend = execs.as_backend(clock="wall")
        sim.add_trace(feed)
    if args.fail_worker is not None:
        sim.inject_failure(args.duration / 2, args.fail_worker,
                           recover_after=args.duration / 4)
    if args.profile:
        import cProfile
        import pstats
        import sys as _sys
        pr = cProfile.Profile()
        pr.enable()
        try:
            m = sim.run(until=args.duration * 10)
        finally:
            pr.disable()
            stats = pstats.Stats(pr, stream=_sys.stderr)
            stats.sort_stats("cumulative")
            print("# --profile: top 25 by cumulative time",
                  file=_sys.stderr)
            stats.print_stats(25)
    else:
        m = sim.run(until=args.duration * 10)

    # label the workload that actually ran: CSV replay and --slo-classes
    # both bypass the named generator, and the JSON is the machine-read
    # contract downstream consumers group runs by
    if args.trace_csv:
        scenario_label = "trace-csv"
    elif classes is not None:
        scenario_label = "slo-classes"
    else:
        scenario_label = args.scenario
    row = m.row()
    row.update(policy=args.policy, arch=cfg.name, mode=args.mode,
               backend=args.backend if args.mode == "sim" else "real-jax",
               rate=args.rate, workers=args.workers, seed=args.seed,
               scenario=scenario_label,
               schema_version=METRICS_SCHEMA_VERSION,
               per_class=m.per_class_rows())
    if sim.transfer is not None:
        row.update(kv_bytes_migrated=sim.transfer.bytes_moved,
                   transfer_seconds=sim.transfer.total_transfer_seconds)
    pred = sim.policy.predictor
    if hasattr(pred, "prefill_scale"):
        row.update(predictor_prefill_scale=round(pred.prefill_scale, 4),
                   predictor_decode_scale=round(pred.decode_scale, 4))
    if sim.sched.rebalancer is not None:
        row.update(role_transitions=len(sim.sched.rebalancer.transitions))
    if execs is not None:
        row.update(_real_counters(execs, feed, args))
    if sim.sched.drift_monitor is not None:
        lo, hi = sim.sched.drift_monitor.gamma_range()
        row.update(recalibrate_every=sim.sched.drift_monitor.every,
                   recalibrations=sim.sched.drift_monitor.recalibrations,
                   drift_gamma_min=round(lo, 4), drift_gamma_max=round(hi, 4))
    if args.json:
        print(json.dumps(row, indent=1, sort_keys=True, default=float))
    else:
        for k, v in row.items():
            if k == "per_class":
                continue
            print(f"{k:>22}: {v}")
        for name, cm in row["per_class"].items():
            cols = " ".join(f"{ck}={cv:.4g}" if isinstance(cv, float)
                            else f"{ck}={cv}" for ck, cv in cm.items())
            print(f"{'class ' + name:>22}: {cols}")
    return row


def _real_counters(execs, reqs, args) -> dict:
    """What a real-mode run served and compiled, and where it ran."""
    import jax
    generated: dict[int, int] = {}
    for e in execs.execs.values():
        for rid, toks in e.generated.items():
            generated[rid] = max(generated.get(rid, 0), len(toks))
    dev = jax.devices()[0]
    out = dict(platform=dev.platform, device_kind=dev.device_kind,
               device_count=len(jax.devices()),
               max_slots=args.max_slots, max_len=args.max_len,
               prompt_tokens=sum(r.prompt_len for r in reqs),
               max_prompt_len=max((r.prompt_len for r in reqs), default=0),
               output_tokens=sum(r.output_len for r in reqs),
               generated_tokens=sum(generated.values()))
    if execs.kernels is not None:
        out.update(prefill_traces=execs.kernels.prefill_traces,
                   decode_traces=execs.kernels.decode_traces)
    return out


if __name__ == "__main__":
    main()
