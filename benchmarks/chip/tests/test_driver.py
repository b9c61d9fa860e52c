"""The wall-clock driver on the CPU smoke config, with a fake clock."""
import json
from pathlib import Path

import pytest

from benchmarks.chip import driver, program, traffic

HERE = Path(__file__).resolve().parent


class FakeClock:
    """Advances by ``tick`` per reading and by the slept time."""

    def __init__(self, tick: float = 1e-3):
        self.t = 100.0
        self.tick = tick
        self.slept = 0.0

    def __call__(self) -> float:
        self.t += self.tick
        return self.t

    def sleep(self, dt: float) -> None:
        self.slept += dt
        self.t += dt


@pytest.fixture(scope="module")
def cluster():
    import jax
    import jax.numpy as jnp
    cfg = json.loads((HERE / "smoke" / "qwen2-smoke.json").read_text())
    tr = json.loads((HERE / "smoke" / "short.w1.json").read_text())
    sched, execs = program.serve(cfg, tr, 3, jax.devices()[:1], jnp.float32)
    return cfg, tr, sched, execs


def test_driver_serves_each_arrival_when_due(cluster):
    cfg, tr, sched, execs = cluster
    planned = traffic.plan(tr, 512, seed=4, seconds=2.0)
    reqs = program.requests(planned, cfg["slo"])
    clock = FakeClock()
    t0 = clock() + tr["warm_s"]
    backend = driver.TimedBackend(sched.backend, clock, t0)
    drv = driver.WallDriver(sched, backend, clock=clock, sleep=clock.sleep)
    seen = []
    cut = drv.run(reqs, t0, lambda now: all(
        r.finish_time is not None for r in reqs), seen.append)
    assert all(r.finish_time is not None for r in reqs)
    assert cut >= max(r.finish_time for r in reqs)
    # every arrival handed over at or just after its due time
    assert len(drv.lateness) == len(reqs)
    assert all(0.0 <= late < 0.05 for _, late in drv.lateness)
    assert clock.slept > 0.0                     # it waited for arrivals
    assert seen == sorted(seen)                  # one clock, never back
    for r in reqs:
        assert r.arrival_time <= r.first_token_time <= r.finish_time
    # the backend's record: every iteration timed, tokens accounted
    its = backend.iterations
    assert its and all(it.seconds > 0 for it in its)
    assert sum(it.tokens_out for it in its) == sum(r.output_len
                                                   for r in reqs)
    assert drv.handle_seconds >= backend.backend_seconds > 0


def test_deferred_events_run_in_time_order(cluster):
    _, _, sched, _ = cluster
    clock = FakeClock()
    drv = driver.WallDriver(sched, driver.TimedBackend(
        sched.backend.inner if isinstance(sched.backend, driver.TimedBackend)
        else sched.backend, clock, 0.0), clock=clock, sleep=clock.sleep)
    order = []
    sched.handle = lambda kind, now, payload: order.append((payload, now))
    t0 = clock()
    drv.defer("x", 0.3, "late")
    drv.defer("x", 0.1, "early")
    drv.run([], t0, lambda now: len(order) == 2)
    assert [p for p, _ in order] == ["early", "late"]
    assert order[0][1] >= 0.1 and order[1][1] >= 0.3
    del sched.handle
