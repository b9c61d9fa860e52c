"""End-to-end arithmetic over the requests due in the window.

End-of-window rule, one for every cell: the requests measured are those
due in ``[0, seconds)``. After the window closes the load keeps arriving
(the drain phase) until every one of them has finished, or until the
traffic's ``drain_s`` has passed; that moment is the cut. A request still
running at the cut is judged on what it streamed by then: TTFT to its
first token, and its token gap as ``(cut - first token)`` over the gaps
it has streamed (at least one), so a stalled stream misses. One with no
token at the cut misses both limits, with TTFT counted to the cut.
Nothing due in the window is dropped.

Percentiles are linear-interpolated, as the program's
``repro.core.metrics.percentile`` computes them.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np


def percentile(xs, p: float) -> float:
    if not len(xs):
        return math.nan
    return float(np.percentile(np.asarray(xs, dtype=np.float64), p))


@dataclasses.dataclass(frozen=True)
class Outcome:
    rid: int
    prompt_len: int
    ttft: float
    tpot: float | None     # mean gap between output tokens; None if < 2
    finished: bool
    ok: bool


def ttft_limit(slo: dict, prompt_len: int) -> float:
    return slo["ttft_base_s"] + slo["ttft_per_token_s"] * prompt_len


def outcome(rid: int, due: float, prompt_len: int, first: float | None,
            finish: float | None, tokens: int, cut: float,
            slo: dict) -> Outcome:
    """One request's latencies on the window's clock (seconds from its
    opening); ``tokens`` is how many output tokens it streamed."""
    finished = finish is not None
    ttft = (first if first is not None else cut) - due
    tpot = None
    if first is not None and not finished:
        tpot = (cut - first) / max(tokens - 1, 1)
    elif first is not None and tokens >= 2:
        tpot = (finish - first) / (tokens - 1)
    ok = first is not None and ttft <= ttft_limit(slo, prompt_len) \
        and (tpot is None or tpot <= slo["tpot_s"])
    return Outcome(rid, prompt_len, ttft, tpot, finished, ok)


def end_to_end(outcomes: list[Outcome], tokens_in_window: int,
               seconds: float) -> dict:
    """The cell's end-to-end numbers (seconds, shares, tokens/s)."""
    tpots = [o.tpot for o in outcomes if o.tpot is not None]
    return {
        "ttft_p90_s": percentile([o.ttft for o in outcomes], 90),
        "tpot_p90_s": percentile(tpots, 90),
        "slo_attainment": (sum(o.ok for o in outcomes) / len(outcomes)
                           if outcomes else math.nan),
        "output_tok_per_s": tokens_in_window / seconds,
    }
