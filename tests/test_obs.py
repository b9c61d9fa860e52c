"""The program's own counters and spans (``repro.obs``): what the
scheduler, the engine and the executor record, and the spans with no
profiler running."""
import statistics
import sys

import pytest

from repro import obs
from repro.configs import get_config, get_smoke
from repro.core.request import Request, SLOSpec
from repro.sched.backend import CallableBackend, CostModelBackend
from repro.serving.costmodel import CostModel, WorkerSpec
from repro.serving.simulator import build_cluster
from repro.serving.trace import generate_trace

CFG = get_config("internlm-20b")
SPEC = WorkerSpec(tp=8)


@pytest.fixture
def recorder():
    obs.RECORDER.reset()
    yield obs.RECORDER
    obs.RECORDER.reset()


def _serve(backend, workers=2, rate=1.0, duration=40.0):
    sim, _ = build_cluster(CFG, "tropical", n_workers=workers,
                           worker_spec=SPEC, backend=backend)
    sim.add_trace(generate_trace(rate, duration, CostModel(CFG, SPEC),
                                 seed=3))
    sim.run(until=4000.0)
    return sim


def _err_pct(records):
    return statistics.median(100.0 * abs(r.measured - r.predicted)
                             / r.measured for r in records)


def test_cost_model_backend_predicts_itself_exactly(recorder):
    _serve(CostModelBackend())
    its = list(recorder.iterations)
    assert its
    assert all(r.predicted == r.measured for r in its)
    assert all(r.phases is None for r in its)      # no executor ran
    assert _err_pct(its) == 0.0
    assert {r.kind for r in its} == {"decode", "mixed"}


def test_backend_twice_the_model_reads_fifty_percent(recorder):
    _serve(CallableBackend(lambda w, plan: 2.0 * w.plan_duration(plan)))
    its = list(recorder.iterations)
    assert its
    assert _err_pct(its) == pytest.approx(50.0)


def test_recorded_wait_equals_worker_queue_times(recorder):
    sim = _serve(CostModelBackend())
    queue_times = {}
    for w in sim.workers.values():
        queue_times.update(w.queue_times)
    assert queue_times and set(recorder.requests) == set(queue_times)
    for rid, rec in recorder.requests.items():
        assert rec.wait == queue_times[rid]


def test_iteration_records_follow_the_driver_clock(recorder):
    _serve(CostModelBackend())
    by_wid = {}
    for r in recorder.iterations:
        by_wid.setdefault(r.wid, []).append(r)
    assert len(by_wid) == 2
    for rs in by_wid.values():
        # a worker composes its next plan only after its running one ends
        for a, b in zip(rs, rs[1:]):
            assert b.now >= a.now + a.measured


def test_match_picks_each_iterations_own_record():
    rec = obs.Recorder()

    class Plan:
        def __init__(self, mixed):
            self.prefill_parts = [object()] if mixed else []

    for wid, now, mixed in [(0, 0.0, True), (1, 0.5, False),
                            (0, 1.0, False), (0, 2.0, False)]:
        rec.iteration(Plan(mixed), wid, now, 0.9, 1.0)

    class It:
        def __init__(self, wid, start, kind):
            self.wid, self.start, self.kind = wid, start, kind

    got = rec.match([It(0, 0.1, "mixed"), It(1, 0.6, "decode"),
                     It(0, 1.2, "decode"), It(0, 2.0, "decode"),
                     It(2, 3.0, "decode"),            # no such worker
                     It(0, -1.0, "decode"),           # before any record
                     It(1, 0.7, "mixed")])            # kinds disagree
    assert [(r.wid, r.now) for r in got] == [(0, 0.0), (1, 0.5), (0, 1.0),
                                             (0, 2.0)]


def test_recorder_stays_bounded():
    rec = obs.Recorder()
    n_it, n_rq = rec.MAX_ITERATIONS, rec.MAX_REQUESTS

    class Plan:
        prefill_parts = ()

    plan = Plan()
    for i in range(n_it + 10):
        rec.iteration(plan, 0, float(i), 1.0, 1.0)
    for i in range(n_rq + 10):
        rec.admitted(i, float(i), float(i) + 0.5)
    assert len(rec.iterations) == n_it
    assert rec.iterations[0].now == 10.0             # the oldest went first
    assert len(rec.requests) == n_rq and min(rec.requests) == 10
    rec.admitted(n_rq, 0.0, 1.0)                      # re-admission overwrites
    assert len(rec.requests) == n_rq and rec.requests[n_rq].wait == 1.0
    rec.reset()
    assert not rec.iterations and not rec.requests


def test_executor_phases_ride_on_the_plan_they_were_measured_for():
    rec = obs.Recorder()

    class Plan:
        prefill_parts = ()

    a, b = Plan(), Plan()
    rec.executor_phases(a, obs.Phases(1.0, 2.0, 3.0, 4.0))
    rec.iteration(b, 0, 0.0, 10.0, 1.0)           # another plan: not a's
    rec.iteration(a, 0, 1.0, 10.0, 1.0)           # the slot was consumed
    rec.executor_phases(a, obs.Phases(1.0, 2.0, 3.0, 4.0))
    rec.iteration(a, 0, 2.0, 10.0, 1.0)
    assert [r.phases for r in rec.iterations][:2] == [None, None]
    assert rec.iterations[2].phases.host == 7.0


def test_spans_run_with_no_profiler_session(monkeypatch):
    import jax  # noqa: F401  (the span is a TraceAnnotation once loaded)
    with obs.span("sched.compose", wid=0):
        with obs.span("backend.sync"):
            pass
    monkeypatch.delitem(sys.modules, "jax")
    assert obs.span("sched.dispatch", rid=1) is obs.span("backend.prepare")
    with obs.span("sched.dispatch", rid=1):
        pass


def test_real_executor_records_its_phases(recorder):
    cfg = get_smoke("deepseek-7b")
    from repro.serving.executor import ClusterRealExecutors
    sim, _ = build_cluster(cfg, "tropical", n_workers=1,
                           worker_spec=WorkerSpec(tp=1))
    execs = ClusterRealExecutors(cfg, 1, max_slots=4, max_len=64)
    sim.sched.backend = execs.as_backend(clock="wall")
    slo = SLOSpec(ttft=30.0, tpot=5.0)
    sim.add_trace([Request(rid=i, arrival_time=0.05 * i, prompt_len=24,
                           output_len=4, slo=slo) for i in range(3)])
    m = sim.run(until=3000.0)
    assert m.n_finished == 3
    its = list(recorder.iterations)
    assert its and all(r.phases is not None for r in its)
    for r in its:
        p = r.phases
        assert min(p.prepare, p.dispatch, p.sync, p.readback) >= 0.0
        # run_plan's duration is its phases' sum: one timing site
        assert r.measured == pytest.approx(
            p.prepare + p.dispatch + p.sync + p.readback, rel=1e-9)
