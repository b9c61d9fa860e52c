"""repro.perf: the unified interference-aware performance model.

Covers the shim contract (old import paths resolve to the same objects),
the §IV mixed-batch interference term (legacy bit-parity when disabled),
per-worker hardware pricing (ClusterPredictor, WorkerView.speed,
relative_speeds), the per-(worker, phase, bucket) online-calibration
hierarchy, the measured-MFU calibrated roofline over real Pallas kernels,
and the TraceReplayBackend streaming-arrival equivalence.
"""
import copy
import dataclasses

import pytest

from repro.configs import get_config, get_smoke
from repro.perf import (AnalyticalPredictor, CalibratedRooflineBackend,
                        ClusterPredictor, CostModel, HardwareSpec,
                        IterationCostModel, OnlinePredictor, V5E, WorkerSpec,
                        calibrate_hardware, relative_speeds)
from repro.serving.simulator import build_cluster


@pytest.fixture(scope="module")
def cfg():
    return get_config("internlm-20b")


@pytest.fixture(scope="module")
def cost(cfg):
    return CostModel(cfg, WorkerSpec(tp=8))


# ------------------------------------------------------------------- shims

def test_legacy_import_paths_resolve_to_the_same_objects():
    import repro.core.predictor as legacy_pred
    import repro.perf as perf
    import repro.serving.costmodel as legacy_cost

    assert legacy_cost.CostModel is perf.CostModel
    assert legacy_cost.HardwareSpec is perf.HardwareSpec
    assert legacy_cost.WorkerSpec is perf.WorkerSpec
    assert legacy_cost.V5E is perf.V5E
    assert legacy_cost.build_cost_spec is perf.build_cost_spec
    assert legacy_pred.AnalyticalPredictor is perf.AnalyticalPredictor
    assert legacy_pred.OnlinePredictor is perf.OnlinePredictor
    assert legacy_pred.ProfiledPredictor is perf.ProfiledPredictor


def test_cost_model_satisfies_the_iteration_cost_interface(cost):
    assert isinstance(cost, IterationCostModel)


# -------------------------------------------------------- interference term

def test_interference_disabled_is_bit_identical_to_legacy(cfg, cost):
    """γ = 0 (the default) must reproduce the pre-perf-package model
    exactly — the decision-parity guarantee every benchmark relies on."""
    explicit = CostModel(cfg, WorkerSpec(
        tp=8, hw=dataclasses.replace(V5E, interference=0.0)))
    for args in ((8, 8 * 2048.0, 2048, 0.0), (16, 16 * 512.0, 0, 0.0),
                 (0, 0.0, 8192, 0), (1, 131072.0, 256, 4096.0)):
        assert explicit.iteration_time(*args) == cost.iteration_time(*args)


def test_interference_penalises_only_mixed_batches(cfg, cost):
    gamma_hw = dataclasses.replace(V5E, interference=0.5)
    inter = CostModel(cfg, WorkerSpec(tp=8, hw=gamma_hw))
    # pure phases: identical to the additive model
    assert inter.prefill_time(4096) == cost.prefill_time(4096)
    assert inter.decode_iter_time(16, 16 * 2048.0) == \
        cost.decode_iter_time(16, 16 * 2048.0)
    # mixed batch: strictly super-additive, bounded by the serialised sum
    legacy = cost.iteration_time(8, 8 * 2048.0, 2048, 0.0)
    mixed = inter.iteration_time(8, 8 * 2048.0, 2048, 0.0)
    serialised = cost.prefill_time(2048) + cost.decode_iter_time(
        8, 8 * 2048.0)
    assert legacy < mixed < serialised


def test_interference_monotone_in_gamma(cfg):
    times = [
        CostModel(cfg, WorkerSpec(tp=8, hw=dataclasses.replace(
            V5E, interference=g))).iteration_time(8, 8 * 2048.0, 2048, 0.0)
        for g in (0.0, 0.25, 0.5, 1.0)]
    assert times == sorted(times) and len(set(times)) == 4


# ------------------------------------------------------ per-worker hardware

def test_slowed_spec_scales_compute_and_memory():
    hw = V5E.slowed(2.0)
    assert hw.peak_flops == V5E.peak_flops / 2.0
    assert hw.hbm_bw == V5E.hbm_bw / 2.0
    assert hw.hbm_bytes == V5E.hbm_bytes          # capacity is unchanged


def test_relative_speeds_homogeneous_is_exactly_one(cfg):
    c = CostModel(cfg, WorkerSpec(tp=8))
    speeds = relative_speeds({0: c, 1: c, 2: c})
    assert all(s == 1.0 for s in speeds.values())


def test_relative_speeds_orders_straggler(cfg):
    fast = CostModel(cfg, WorkerSpec(tp=8))
    slow = CostModel(cfg, WorkerSpec(tp=8, hw=V5E.slowed(2.0)))
    speeds = relative_speeds({0: fast, 1: slow})
    assert speeds[0] == 1.0
    assert 0.4 < speeds[1] < 0.6          # ~half the throughput


def test_cluster_predictor_prices_on_the_target_worker(cfg):
    fast = CostModel(cfg, WorkerSpec(tp=8))
    slow = CostModel(cfg, WorkerSpec(tp=8, hw=V5E.slowed(2.0)))
    pred = ClusterPredictor({0: fast, 1: slow})
    assert pred.predict_prefill(4096, wid=1) > \
        pred.predict_prefill(4096, wid=0)
    # wid=None prices on the reference (fastest) model
    assert pred.predict_prefill(4096) == pred.predict_prefill(4096, wid=0)
    assert pred.predict_decode_iter(8, 8 * 2048.0, wid=1) > \
        pred.predict_decode_iter(8, 8 * 2048.0, wid=0)


def test_build_cluster_heterogeneous_wires_speeds_and_predictor(cfg):
    fast = WorkerSpec(tp=8)
    slow = WorkerSpec(tp=8, hw=V5E.slowed(2.0))
    sim, _ = build_cluster(cfg, "tropical", n_workers=3,
                           worker_spec=fast, worker_specs=[fast, fast, slow])
    views = {w.wid: w.view for w in sim.workers.values()}
    assert views[0].speed == views[1].speed == 1.0
    assert 0.4 < views[2].speed < 0.6
    assert isinstance(sim.policy.predictor, ClusterPredictor)
    assert sim.workers[2].cost.worker.hw.peak_flops == V5E.peak_flops / 2.0
    # homogeneous default: speeds exactly 1.0, plain analytic predictor
    sim2, _ = build_cluster(cfg, "tropical", n_workers=2, worker_spec=fast)
    assert all(w.view.speed == 1.0 for w in sim2.workers.values())
    assert isinstance(sim2.policy.predictor, AnalyticalPredictor)


def test_build_cluster_rejects_mismatched_worker_specs(cfg):
    with pytest.raises(ValueError, match="worker_specs"):
        build_cluster(cfg, "tropical", n_workers=4,
                      worker_specs=[WorkerSpec(tp=8)] * 3)


def test_dispatch_prefers_fast_worker_under_slack_discipline(cfg):
    """Straggler routing: with empty queues everywhere, per-worker pricing
    sends the next prefill to a fast worker — the slow worker's predicted
    TTFT is strictly worse. The global predictor cannot tell them apart
    (ties break by iteration order)."""
    from repro.core.request import Request, SLOSpec

    fast = WorkerSpec(tp=8)
    slow = WorkerSpec(tp=8, hw=V5E.slowed(2.0))
    # worker 0 = slow PREFILL, worker 1 = fast PREFILL (n_prefill=2)
    sim, cost = build_cluster(cfg, "tropical", n_workers=4,
                              worker_spec=fast,
                              worker_specs=[slow, fast, fast, fast],
                              n_prefill=2)
    req = Request(rid=0, arrival_time=0.0, prompt_len=8192, output_len=64,
                  slo=SLOSpec(ttft=10.0, tpot=1.0))
    toggle = sim.policy.toggle
    views = {w.wid: w.view for w in sim.workers.values()}
    # the straggler's predicted TTFT is strictly worse at equal (empty) load
    assert toggle._predict_ttft_on_prefill(views[0], req) > \
        toggle._predict_ttft_on_prefill(views[1], req)
    wid = sim.policy.dispatch_prefill(req, 0.0)
    assert wid != 0, "per-worker pricing must avoid the straggler"


# --------------------------------------------------- per-worker calibration

def test_online_predictor_per_worker_converges_independently(cost):
    """Worker 1 runs 2x slower than the (shared, nominal) base profile;
    worker 0 matches it. Per-worker EWMAs converge to each worker's own
    bias instead of a blend."""
    pred = OnlinePredictor(AnalyticalPredictor(cost), per_worker=True)
    t = cost.prefill_time(2048)
    for _ in range(60):
        pred.observe_prefill(2048, 0, t, wid=0)
        pred.observe_prefill(2048, 0, 2.0 * t, wid=1)
    assert pred.predict_prefill(2048, wid=0) == \
        pytest.approx(t * 1.1, rel=0.1)
    assert pred.predict_prefill(2048, wid=1) == \
        pytest.approx(2.0 * t * 1.1, rel=0.1)
    # the global scale blends the two and fits neither
    assert pred.prefill_scale == pytest.approx(1.5, rel=0.2)
    # an unknown worker falls back to the blended global correction
    assert pred.predict_prefill(2048, wid=99) == \
        pytest.approx(pred.base.predict_prefill(2048) * pred.prefill_scale)


def test_online_predictor_per_worker_fallback_hierarchy(cost):
    """Below the evidence floors a worker borrows coarser scales:
    (wid, phase, bucket) -> (wid, phase) -> the global per-phase scale."""
    pred = OnlinePredictor(AnalyticalPredictor(cost), per_worker=True,
                           bucket_floor=8, worker_floor=8)
    t = cost.prefill_time(2048)
    for _ in range(20):
        pred.observe_prefill(2048, 0, 2.0 * t, wid=1)
    # warm (wid, phase, bucket): the worker's own bucket scale rules
    assert pred.predict_prefill(2048, wid=1) == \
        pytest.approx(2.0 * t * 1.1, rel=0.1)
    # same worker, never-seen size bucket: falls to the (wid, phase) scale
    small = pred.predict_prefill(64, wid=1)
    assert small == pytest.approx(
        pred.base.predict_prefill(64) * pred.worker_scales[(1, "prefill")])
    # cold worker (few observations): global per-phase scale governs
    pred.observe_prefill(2048, 0, 0.5 * t, wid=2)
    assert pred.worker_observations[(2, "prefill")] < pred.worker_floor
    assert pred.predict_prefill(2048, wid=2) == \
        pytest.approx(pred.base.predict_prefill(2048)
                      * pred._bucket_scale("prefill", 2048,
                                           pred.prefill_scale))


def test_online_predictor_per_worker_off_ignores_wid(cost):
    pred = OnlinePredictor(AnalyticalPredictor(cost), per_worker=False)
    for _ in range(20):
        pred.observe_prefill(2048, 0, 2.0 * cost.prefill_time(2048), wid=3)
    assert not pred.worker_scales and not pred.worker_bucket_scales
    assert pred.predict_prefill(2048, wid=3) == pred.predict_prefill(2048)


def test_scheduler_feeds_per_worker_scales_on_hetero_cluster(cfg):
    """End-to-end: a straggler cluster under the cost-model backend
    converges per-worker scales near each worker's true bias."""
    from repro.serving.trace import generate_trace

    fast = WorkerSpec(tp=8)
    slow = WorkerSpec(tp=8, hw=V5E.slowed(2.0))
    nominal = CostModel(cfg, fast)
    pred = OnlinePredictor(AnalyticalPredictor(nominal), per_worker=True)
    sim, _ = build_cluster(cfg, "tropical", n_workers=4, worker_spec=fast,
                           worker_specs=[fast, fast, fast, slow],
                           predictor=pred)
    sim.add_trace(generate_trace(2.0, 60.0, nominal, seed=7))
    m = sim.run(until=4000.0)
    assert m.n_finished == m.n_total
    slow_scales = [v for (wid, _ph), v in pred.worker_scales.items()
                   if wid == 3]
    fast_scales = [v for (wid, _ph), v in pred.worker_scales.items()
                   if wid != 3]
    assert slow_scales and fast_scales
    # the straggler learned its slowdown (mixed-iteration attribution keeps
    # the phases from landing exactly on 2.0; the dominant phase does)
    assert max(slow_scales) > 1.4
    assert max(fast_scales) < 1.25         # fast workers stay ~unbiased
    assert max(slow_scales) > max(fast_scales) + 0.3


# ------------------------------------------------- measured-MFU calibration

def test_calibrate_hardware_measures_sane_fractions():
    hw, cal = calibrate_hardware(V5E, seq=128, heads=2, head_dim=64,
                                 batch=2, page_size=16, pages_per_seq=2,
                                 repeats=1)
    for frac in (hw.mfu_prefill, hw.mfu_decode, hw.bw_eff):
        assert 0.0 < frac <= 1.0
    assert cal.prefill_seconds > 0.0 and cal.decode_seconds > 0.0
    assert hw.name.endswith("-measured")
    # capacity/links come from the spec, not the measurement
    assert hw.hbm_bytes == V5E.hbm_bytes and hw.ici_bw == V5E.ici_bw


def test_calibrate_compiled_refuses_an_unknown_device_kind(monkeypatch):
    """Compiled calibration divides by the spec's peaks, so it must run on
    the device they describe: an unknown device_kind raises before any
    kernel runs (it used to assume V5E peaks on anything)."""
    import types

    import jax

    fake = types.SimpleNamespace(platform="tpu", device_kind="TPU v99 fake")
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [fake])
    with pytest.raises(ValueError, match="device_kind"):
        calibrate_hardware(V5E, interpret=False)


def test_calibrate_compiled_fraction_above_peak_raises():
    from repro.perf.calibrate import _frac

    assert _frac(1e-9, True, "mfu") == pytest.approx(1e-6)  # interpret floor
    assert _frac(1.02, False, "mfu") == 1.0       # timing noise at the peak
    assert _frac(0.3, False, "mfu") == 0.3
    with pytest.raises(ValueError, match="exceeds"):
        _frac(1.2, False, "mfu")


def test_calibrated_roofline_backend_prices_iterations():
    cfg = get_smoke("deepseek-7b")
    backend = CalibratedRooflineBackend(
        cfg, WorkerSpec(tp=1), seq=128, heads=2, head_dim=64, batch=2,
        page_size=16, pages_per_seq=2, repeats=1)
    from repro.serving.engine import IterationPlan, Worker

    w = Worker(0, CostModel(cfg, WorkerSpec(tp=1)))
    plan = IterationPlan(decode_reqs=[], prefill_parts=[], n_decode=4,
                         sum_ctx=4 * 64.0, prefill_tokens=32,
                         prefill_ctx_offset=0.0, exclusive_prefill=False)
    dur = backend.run_iteration(w, plan)
    assert dur > 0.0
    cal = backend.calibration
    assert 0.0 < cal.mfu_prefill <= 1.0


# ------------------------------------------------------ trace-replay backend

def test_trace_replay_backend_matches_materialised_trace(cfg):
    """Streaming arrivals through TraceReplayBackend must reproduce the
    materialised add_trace run decision-for-decision."""
    from repro.sched import TraceReplayBackend
    from repro.serving.trace import generate_trace

    spec = WorkerSpec(tp=8)
    cost = CostModel(cfg, spec)
    trace = generate_trace(2.0, 40.0, cost, seed=9)

    sim_a, _ = build_cluster(cfg, "tropical", n_workers=2, worker_spec=spec,
                             record_decisions=True)
    sim_a.add_trace(copy.deepcopy(trace))
    m_a = sim_a.run(until=4000.0)

    sim_b, _ = build_cluster(cfg, "tropical", n_workers=2, worker_spec=spec,
                             record_decisions=True)
    replay = TraceReplayBackend(
        (r.arrival_time, r) for r in copy.deepcopy(trace))
    sim_b.add_replay(replay)
    m_b = sim_b.run(until=4000.0)

    assert replay.replayed == len(trace)
    assert m_a.n_finished == m_b.n_finished == len(trace)
    assert sim_a.decisions == sim_b.decisions
    assert m_a.slo_attainment == m_b.slo_attainment
    assert m_a.ttft_p90 == m_b.ttft_p90


def test_trace_replay_rejects_unsorted_feed(cfg):
    """Streaming keeps one pending arrival: an out-of-order item would
    move the driver clock backwards. The backend refuses loudly."""
    from repro.core.request import Request, SLOSpec
    from repro.sched import TraceReplayBackend

    slo = SLOSpec(ttft=10.0, tpot=1.0)
    reqs = [Request(rid=i, arrival_time=t, prompt_len=8, output_len=2,
                    slo=slo) for i, t in enumerate((1.0, 3.0, 2.0))]
    sim, _ = build_cluster(cfg, "tropical", n_workers=2,
                           worker_spec=WorkerSpec(tp=8))
    sim.add_replay(TraceReplayBackend((r.arrival_time, r) for r in reqs))
    with pytest.raises(ValueError, match="not sorted"):
        sim.run(until=100.0)


def test_add_replay_adopts_configured_clock(cfg):
    """A bare TraceReplayBackend(feed) must not silently swap a custom
    duration_fn for the default analytic clock — both call forms adopt
    the simulator's configured backend as the inner clock."""
    from repro.sched import CallableBackend, TraceReplayBackend
    from repro.serving.trace import generate_trace

    spec = WorkerSpec(tp=8)
    cost = CostModel(cfg, spec)
    trace = generate_trace(1.0, 10.0, cost, seed=2)
    calls = []

    def spy(worker, plan):
        calls.append(worker.wid)
        return worker.plan_duration(plan)

    sim, _ = build_cluster(cfg, "tropical", n_workers=2, worker_spec=spec,
                           backend=CallableBackend(spy))
    replay = TraceReplayBackend((r.arrival_time, r) for r in trace)
    sim.add_replay(replay)
    assert replay.inner is not None and isinstance(
        replay.inner, CallableBackend)
    m = sim.run(until=1000.0)
    assert m.n_finished == len(trace)
    assert calls, "the custom clock must keep supplying durations"


def test_serve_cli_trace_replay_backend_equivalent(capsys):
    import json

    from repro.launch import serve

    base = ["--mode", "sim", "--rate", "1.0", "--duration", "15",
            "--seed", "3", "--json"]
    row_a = serve.main(base)
    capsys.readouterr()
    row_b = serve.main(base + ["--backend", "trace-replay"])
    out = capsys.readouterr().out
    data = json.loads(out)
    assert data["backend"] == "trace-replay"
    assert row_b["n_total"] == row_a["n_total"] > 0
    for key in ("slo_attainment", "ttft_p90", "tpot_p90", "n_finished"):
        assert row_b[key] == row_a[key], key


def test_serve_cli_trace_replay_rejects_real_mode():
    from repro.launch import serve

    with pytest.raises(SystemExit):
        serve.main(["--mode", "real", "--backend", "trace-replay",
                    "--rate", "1.0", "--duration", "5"])


# ------------------------------------------------------- rebalancer decay

def test_rebalancer_window_ttl_expires_silent_class():
    from repro.core.request import Request, SLOSpec
    from repro.sched import RebalanceConfig, RoleRebalancer
    from repro.core.toggle import Role, WorkerView

    cfg = RebalanceConfig(min_samples=8, window_ttl=30.0, cooldown=0.0)
    rb = RoleRebalancer(cfg)
    views = {i: WorkerView(wid=i, role=r, kv_capacity_tokens=1e5)
             for i, r in enumerate(
                 [Role.PREFILL, Role.MULTIPLEX, Role.MULTIPLEX])}
    tight = SLOSpec(ttft=1.0, tpot=0.1, name="interactive")

    def _outcome(t, ok):
        r = Request(rid=0, arrival_time=0.0, prompt_len=8, output_len=4,
                    slo=tight)
        r.first_token_time = t if ok else t + 10.0 * tight.ttft
        r.arrival_time = t - (0.5 if ok else 2.0) * tight.ttft
        return r

    # the tenant breaches TTFT, then goes silent
    for i in range(12):
        rb.record_first_token(_outcome(10.0 + 0.1 * i, ok=False))
    for _ in range(12):
        rb.tpot_window.append(True)
    # inside the TTL the stale window still drives a role move
    assert rb._worst_attainment(rb.ttft_windows) == 0.0
    assert rb.step(views, now=20.0) is not None
    # well past the TTL the silent tenant's evidence expires: no review
    # keeps chasing a tenant that no longer sends traffic
    views2 = {i: WorkerView(wid=i, role=r, kv_capacity_tokens=1e5)
              for i, r in enumerate(
                  [Role.PREFILL, Role.MULTIPLEX, Role.MULTIPLEX])}
    assert rb.step(views2, now=100.0) is None
    assert len(rb.ttft_windows["interactive"]) == 0


def test_rebalancer_default_never_expires():
    from repro.sched import RebalanceConfig, RoleRebalancer

    rb = RoleRebalancer(RebalanceConfig(min_samples=8))
    assert rb.cfg.window_ttl is None
    rb.ttft_window.extend([False] * 12)
    rb.tpot_window.extend([True] * 12)
    rb._expire_stale_windows(now=1e9)
    assert len(rb.ttft_window) == 12       # legacy windows never decay


# --------------------------------------------------------- bench summary

def test_bench_summary_schema():
    from benchmarks.run import REF_RATE, SUMMARY_SCHEMA_VERSION, build_summary

    results = {
        "fig8": [{"policy": "tropical", "rate": REF_RATE,
                  "slo_attainment": 0.97}],
        "fig_multitenant": [{"policy": "tropical", "rate": REF_RATE,
                             "weighted_attainment": 0.95}],
        "fig_hetero": [{"config": "summary", "mean_hetero_global": 0.69,
                        "mean_hetero_pw": 0.76}],
        "fig_interference": [{"config": "summary", "mean_gamma_blind": 0.98,
                              "mean_gamma_aware": 0.99,
                              "mean_gamma_drift": 0.98,
                              "mean_gamma_abs_err": 0.01}],
        "fig_tiered": [{"config": "summary", "evict_ttft_attainment": 0.957,
                        "tiered_prefix_ttft_attainment": 0.996,
                        "prefix_hit_rate": 0.958}],
        "scale": [{"tier": "throughput", "mode": "vectorized",
                   "workers": 256, "sim_throughput_rps": 410.0,
                   "speedup_x": 4.1},
                  {"tier": "throughput", "mode": "vectorized",
                   "workers": 1024, "sim_throughput_rps": 1000.0,
                   "speedup_x": 13.8},
                  {"tier": "throughput", "mode": "scalar",
                   "workers": 1024, "sim_throughput_rps": 72.0},
                  {"tier": "engine", "mode": "vectorized",
                   "workers": 1024, "sim_throughput_rps": 155.0,
                   "speedup_x": 3.2},
                  {"tier": "engine", "mode": "scalar",
                   "workers": 1024, "sim_throughput_rps": 49.0},
                  {"tier": "real_exec", "mode": "seed",
                   "iters": 17, "step_ms": 375.8},
                  {"tier": "real_exec", "mode": "fast",
                   "iters": 17, "step_ms": 2.5, "speedup_x": 150.3}],
    }
    s = build_summary(results)
    assert s["schema_version"] == SUMMARY_SCHEMA_VERSION == 5
    assert s["slo_attainment"] == 0.97
    assert s["weighted_attainment"] == 0.95
    assert s["hetero_per_worker_attainment"] == 0.76
    assert s["interference_aware_attainment"] == 0.99
    assert s["interference_blind_attainment"] == 0.98
    assert s["interference_gamma_abs_err"] == 0.01
    assert s["tiered_evict_ttft_attainment"] == 0.957
    assert s["tiered_prefix_ttft_attainment"] == 0.996
    assert s["tiered_prefix_hit_rate"] == 0.958
    # throughput tier: largest-scale vectorized row wins
    assert s["sim_throughput_rps"] == 1000.0
    assert s["sim_throughput_workers"] == 1024
    assert s["sim_throughput_speedup"] == 13.8
    # engine tier: same rule, its own keys
    assert s["sim_engine_rps"] == 155.0
    assert s["sim_engine_workers"] == 1024
    assert s["sim_engine_speedup"] == 3.2
    # real-compute executor tier: the fast row's wall clock + speedup
    assert s["real_step_ms"] == 2.5
    assert s["real_exec_speedup"] == 150.3
    assert s["ttft_p90_s"] > 0 and s["tpot_p90_s"] > 0
    assert s["mean_step_s"] > 0 and s["n_requests"] > 0
