"""Trace to metrics: union of busy intervals and naming of idle gaps."""
import pytest

from benchmarks.chip import devtrace

MS = 1_000_000   # ns


def test_union_merges_and_clips():
    iv = [(5, 8, "a"), (0, 3, "b"), (2, 4, "c"), (9, 12, "d")]
    assert devtrace.union(iv, 1, 10) == [(1, 4), (5, 8), (9, 10)]


def test_gaps_are_the_complement():
    assert devtrace.gaps([(1, 4), (5, 8)], 0, 10) == [(0, 1), (4, 5), (8, 10)]


def test_gap_takes_the_innermost_span():
    spans = [(0, 100, "sched.handle.iter_done"),
             (10, 50, "backend.run_iteration.mixed")]
    gaps = [(20, 30), (60, 70), (200, 210)]
    assert devtrace.name_gaps(gaps, spans) == [
        "backend.run_iteration.mixed", "sched.handle.iter_done", "untraced"]


def test_self_time_leaves_out_nested_ops():
    iv = [(0, 100, "while"), (10, 40, "fusion"), (50, 60, "fusion"),
          (120, 130, "copy")]
    t = devtrace.self_times(iv)
    assert t == pytest.approx({"while": 60e-9, "fusion": 40e-9,
                               "copy": 10e-9})


def test_reduce_on_a_small_trace():
    ops = {"/device:TPU:0": [(0, 4 * MS, "fusion.1"), (2 * MS, 6 * MS,
                                                       "fusion.2"),
                             (8 * MS, 9 * MS, "fusion.1")]}
    spans = [(6 * MS, 8 * MS, "driver.wait_arrival"),
             (9 * MS, 10 * MS, "sched.handle.arrival")]
    r = devtrace.reduce(ops, spans, (0, 10 * MS), devices=1)
    assert r["window_s"] == pytest.approx(0.010)
    assert r["busy_s"] == pytest.approx(0.007)
    assert r["idle_share"] == pytest.approx(0.3)
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"driver.wait_arrival": 0.002, "sched.handle.arrival": 0.001})
    # self times: fusion.2 overlaps the first fusion.1 by 2 ms
    assert dict(r["device_ops"]) == pytest.approx(
        {"fusion.1": 0.003, "fusion.2": 0.004})


def test_chip_that_ran_nothing_counts_as_idle():
    ops = {"/device:TPU:0": [(0, 10 * MS, "f")]}
    r = devtrace.reduce(ops, [], (0, 10 * MS), devices=2)
    assert r["busy_s"] == pytest.approx(0.005)
    assert r["idle_share"] == pytest.approx(0.5)


def test_reduce_on_a_recorded_trace():
    """50 ms recorded on one v5e (qwen2-1.5b, one worker): the reduction
    agrees with a brute-force count on a 10 us grid."""
    import json
    from pathlib import Path
    rec = json.loads((Path(__file__).resolve().parent / "data"
                      / "trace_excerpt.json").read_text())
    lo, hi = rec["window"]
    ops = {d: [tuple(e) for e in iv] for d, iv in rec["ops"].items()}
    spans = [tuple(s) for s in rec["spans"]]
    assert ops and any(ops.values())
    r = devtrace.reduce(ops, spans, (lo, hi), devices=1)
    step = 10_000
    grid = range(int(lo), int(hi), step)
    (iv,) = ops.values()
    busy = sum(1 for t in grid if any(s <= t < e for s, e, _ in iv))
    assert r["busy_s"] == pytest.approx(busy * step * 1e-9, rel=0.02,
                                        abs=2e-5)
    assert r["busy_s"] + sum(t for _, t in r["idle_gaps"]) == \
        pytest.approx(r["window_s"])
    named = {n for n, _ in r["idle_gaps"]}
    assert named <= {n for *_, n in spans} | {"untraced"}
