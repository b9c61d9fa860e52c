"""The ``kernel.decode_attn_read_share`` reader on the CPU smoke cluster
driven by the wall-clock driver, in the style of ``test_obs_metrics``
(whose ``served`` fixture it shares)."""
import math
import sys
import types

import pytest

from benchmarks.chip import run
from benchmarks.chip.tests.test_obs_metrics import served  # noqa: F401

NAME = "kernel.decode_attn_read_share"


def test_reader_returns_a_finite_value(served):  # noqa: F811
    rec, _ = served
    value = run.reader(NAME)(rec)
    assert value is not None and math.isfinite(value) and value >= 0.0


def test_read_share_counts_the_decode_calls_fetches(served):  # noqa: F811
    """Live-block bytes the kernel is asked to read over the whole cache
    per decode call: above 0 (every window has decode calls), at most 1,
    and the records' own ratio."""
    from repro import obs
    rec, _ = served
    recs = [r for r in obs.RECORDER.match(rec.iterations)
            if r.kv_read_bytes is not None]
    assert recs
    share = run.reader(NAME)(rec)
    assert 0.0 < share <= 1.0
    assert share == pytest.approx(sum(r.kv_read_bytes for r in recs)
                                  / sum(r.kv_cache_bytes for r in recs))


def test_read_share_is_none_for_records_without_the_count(
        served, monkeypatch):  # noqa: F811
    """A program whose iteration records carry no KV byte count (before
    the decode-attention kernel) reads None, not 0."""
    from repro import obs
    rec, _ = served
    bare = [types.SimpleNamespace(wid=r.wid, now=r.now, kind=r.kind)
            for r in obs.RECORDER.match(rec.iterations)]
    monkeypatch.setattr(obs.RECORDER, "match", lambda its: bare)
    assert run.reader(NAME)(rec) is None


def test_reader_returns_none_without_the_programs_counters(
        served, monkeypatch):  # noqa: F811
    rec, _ = served
    monkeypatch.setitem(sys.modules, "repro.obs", None)   # import fails
    monkeypatch.delattr(sys.modules["repro"], "obs", raising=False)
    assert run.reader(NAME)(rec) is None
