"""The per-layer metrics read from the program's own counters
(``repro.obs``), on the CPU smoke cluster driven by the wall-clock
driver. The fake clock skips the driver's sleeps but runs with the host
clock, so the time spent inside the backend is real."""
import json
import math
import sys
import time
import types
from pathlib import Path

import pytest

from benchmarks.chip import driver, program, run, stats, traffic
from benchmarks.chip.tests.test_driver import FakeClock

HERE = Path(__file__).resolve().parent
METRICS = ("sched.queue_wait_p90_ms", "sched.predict_err_pct",
           "exec.host_ms_per_iter")
WINDOW_S = 2.0


class HostFakeClock(FakeClock):
    def __init__(self):
        super().__init__(tick=0.0)
        self.base = time.perf_counter()

    def __call__(self) -> float:
        return self.t + time.perf_counter() - self.base


@pytest.fixture(scope="module")
def served():
    """(the window's record, as ``run.Session.serve`` builds it; the
    driver's ``TimedBackend``)."""
    import jax
    import jax.numpy as jnp
    from repro import obs
    cfg = json.loads((HERE / "smoke" / "qwen2-smoke.json").read_text())
    tr = json.loads((HERE / "smoke" / "short.w1.json").read_text())
    sched, _ = program.serve(cfg, tr, 3, jax.devices()[:1], jnp.float32)
    planned = traffic.plan(tr, 512, seed=2**31 + 5, seconds=WINDOW_S)
    reqs = program.requests(planned, cfg["slo"])
    by_rid = {r.rid: r for r in reqs}
    obs.RECORDER.reset()
    clock = HostFakeClock()
    t0 = clock() + tr["warm_s"]
    backend = driver.TimedBackend(sched.backend, clock, t0)
    drv = driver.WallDriver(sched, backend, clock=clock, sleep=clock.sleep)
    cut = drv.run(reqs, t0, lambda now: all(
        r.finish_time is not None for r in reqs))
    window = [p for p in planned if p.phase == "window"]
    rec = types.SimpleNamespace(
        iterations=[it for it in backend.iterations
                    if 0.0 <= it.start < WINDOW_S],
        window_rids={p.rid for p in window}, cut=cut,
        outcomes=[stats.outcome(p.rid, p.due, p.prompt_len,
                                by_rid[p.rid].first_token_time,
                                by_rid[p.rid].finish_time,
                                by_rid[p.rid].streamed_tokens, cut,
                                cfg["slo"]) for p in window])
    yield rec, backend
    obs.RECORDER.reset()


@pytest.mark.parametrize("name", METRICS)
def test_readers_return_finite_values(served, name):
    rec, _ = served
    assert rec.iterations and rec.outcomes
    value = run.reader(name)(rec)
    assert value is not None and math.isfinite(value) and value >= 0.0


def test_one_record_per_window_iteration(served):
    from repro import obs
    rec, _ = served
    matched = obs.RECORDER.match(rec.iterations)
    assert len(matched) == len(rec.iterations)
    assert len({id(r) for r in matched}) == len(matched)
    for it, r in zip(rec.iterations, matched):
        assert r.wid == it.wid and r.kind == it.kind and r.now <= it.start


def test_phases_lie_within_the_drivers_time(served):
    from repro import obs
    rec, _ = served
    for it, r in zip(rec.iterations, obs.RECORDER.match(rec.iterations)):
        p = r.phases
        assert p is not None
        assert 0.0 < p.prepare + p.dispatch + p.sync + p.readback \
            <= it.seconds


@pytest.mark.parametrize("name", METRICS)
def test_readers_return_none_without_the_programs_counters(
        served, name, monkeypatch):
    rec, _ = served
    monkeypatch.setitem(sys.modules, "repro.obs", None)   # import fails
    monkeypatch.delattr(sys.modules["repro"], "obs", raising=False)
    assert run.reader(name)(rec) is None
