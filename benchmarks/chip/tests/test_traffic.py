"""The open-loop generator: counts, shared sizes across seeds, clipping."""
import collections

from benchmarks.chip import traffic

MIX = {"body_median": 2048, "body_sigma": 1.1, "tail_median": 16384,
       "tail_sigma": 0.7, "tail_frac": 0.15, "min_input": 16,
       "max_input": 32768, "out_median": 256, "out_sigma": 0.7,
       "min_output": 2, "max_output": 2048}
T = {"mix": MIX, "arrivals": "poisson", "rate_rps": 2.0, "warm_s": 10,
     "drain_s": 20}


def _phase(plan, name):
    return [p for p in plan if p.phase == name]


def test_count_due_in_window_is_rate_times_window():
    plan = traffic.plan(T, 8192, seed=3, seconds=50)
    win = _phase(plan, "window")
    assert len(win) == 100
    assert all(0.0 <= p.due < 50.0 for p in win)
    assert len(_phase(plan, "warm")) == 20
    assert all(-10.0 <= p.due < 0.0 for p in _phase(plan, "warm"))
    assert len({p.rid for p in plan}) == len(plan)


def test_every_seed_gets_the_same_sizes_in_another_order():
    a = _phase(traffic.plan(T, 10**6, seed=1, seconds=50), "window")
    b = _phase(traffic.plan(T, 10**6, seed=2**31 + 9, seconds=50), "window")
    assert collections.Counter(p.prompt_len for p in a) == \
        collections.Counter(p.prompt_len for p in b)
    assert collections.Counter(p.output_len for p in a) == \
        collections.Counter(p.output_len for p in b)
    assert [p.prompt_len for p in a] != [p.prompt_len for p in b]
    gaps_a = sorted(round(y.due - x.due, 9) for x, y in zip(a, a[1:]))
    gaps_b = sorted(round(y.due - x.due, 9) for x, y in zip(b, b[1:]))
    assert len(gaps_a) == len(gaps_b)


def test_same_seed_same_plan():
    assert traffic.plan(T, 8192, 5, 50) == traffic.plan(T, 8192, 5, 50)


def test_clip_fits_one_slot():
    assert traffic.clip(20000, 300, 8192) == (7892, 300)
    assert traffic.clip(512, 3000, 4096) == (512, 3000)
    assert traffic.clip(512, 5000, 4096) == (512, 3584)
    for p in traffic.plan(T, 8192, 7, 50):
        assert p.prompt_len + p.output_len <= 8192


def test_prompt_tokens_are_the_executors_draw():
    import numpy as np
    want = np.random.default_rng(17).integers(0, 100, size=9)
    assert (traffic.prompt_tokens(17, 9, 100) == want).all()
