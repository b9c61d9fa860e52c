"""The program's own tracing: spans on the profiler's clock, and counters
kept in memory.

* ``span(name, **ids)`` marks a phase of the scheduler or the executor.
  It is a ``jax.profiler.TraceAnnotation``, so under a profiler session
  the span lands in the profiler's own trace, on the same clock as the
  device's operations, and a device idle gap can be named by the phase
  the host was in. With no session it costs about a microsecond. Where
  JAX was never imported no profiler can be running: ``span`` then
  returns a shared ``nullcontext`` and imports nothing, so the simulator
  stays JAX-free.
* ``RECORDER`` is one process-wide, bounded ``Recorder``: a record per
  iteration (what the scheduler predicted and measured, the executor's
  phase times, the KV bytes of the live blocks its decode attention was
  asked to read) and a record per admitted request (its wait in the
  queue). The counters are always on; ``reset()`` empties them.

This module reads no clock: every time in it was stamped by its writer
(the driver's ``now``, the executor's phase stamps).
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import sys
from contextlib import nullcontext
from typing import Optional

_NULL = nullcontext()


def span(name: str, **ids):
    """A context manager that traces ``name`` (with ``ids`` as its
    arguments) while a profiler session runs."""
    jax = sys.modules.get("jax")
    if jax is None:
        return _NULL
    return jax.profiler.TraceAnnotation(name, **ids)


@dataclasses.dataclass(slots=True)
class Phases:
    """The executor's host time in one iteration, seconds (host clock):
    building the inputs, calling the jitted entries, waiting for the
    device, reading the tokens back."""
    prepare: float
    dispatch: float
    sync: float
    readback: float

    @property
    def host(self) -> float:
        """Time the host, not the device, held the iteration."""
        return self.prepare + self.dispatch + self.readback


@dataclasses.dataclass(slots=True)
class IterationRecord:
    wid: int
    now: float               # the driver's clock when the plan was composed
    kind: str                # "decode" (no prefill part) or "mixed"
    measured: float          # duration the backend returned, seconds
    predicted: float         # Worker.plan_duration: the cost model's price
    phases: Optional[Phases]  # None where no executor ran the plan
    # K and V bytes of the live blocks the decode call's attention was
    # asked to read (the kernel's block rule), and the bytes of the whole
    # stacked cache it reads from; None where no decode call ran on the
    # fast path
    kv_read_bytes: Optional[int] = None
    kv_cache_bytes: Optional[int] = None


@dataclasses.dataclass(slots=True)
class RequestRecord:
    rid: int
    arrival: float
    admitted: float          # driver's clock when its prefill started

    @property
    def wait(self) -> float:
        return self.admitted - self.arrival


class Recorder:
    """Bounded in-memory counters. An iteration's record has two writers:
    the executor hands in its phase times while it runs the plan, and the
    scheduler closes the record once the backend returns (both on the one
    thread that drives the scheduler, so one pending slot suffices)."""

    MAX_ITERATIONS = 1 << 16     # a benchmark run holds ~2k
    MAX_REQUESTS = 1 << 16

    def __init__(self):
        self.iterations: collections.deque[IterationRecord] = \
            collections.deque(maxlen=self.MAX_ITERATIONS)
        self.requests: dict[int, RequestRecord] = {}
        self._pending: Optional[tuple] = None

    def reset(self) -> None:
        self.iterations.clear()
        self.requests.clear()
        self._pending = None

    def executor_phases(self, plan, phases: Phases,
                        kv_read_bytes: Optional[int] = None,
                        kv_cache_bytes: Optional[int] = None) -> None:
        self._pending = (plan, phases, kv_read_bytes, kv_cache_bytes)

    def iteration(self, plan, wid: int, now: float, measured: float,
                  predicted: float) -> None:
        pending, self._pending = self._pending, None
        if pending is None or pending[0] is not plan:
            pending = (plan, None, None, None)
        kind = "mixed" if plan.prefill_parts else "decode"
        self.iterations.append(IterationRecord(
            wid, now, kind, measured, predicted, *pending[1:]))

    def admitted(self, rid: int, arrival: float, now: float) -> None:
        """A request's prefill started (a re-admission overwrites)."""
        if rid not in self.requests and \
                len(self.requests) >= self.MAX_REQUESTS:
            del self.requests[next(iter(self.requests))]
        self.requests[rid] = RequestRecord(rid, arrival, now)

    def match(self, iterations) -> list[IterationRecord]:
        """The records of ``iterations`` (each with ``wid``, ``kind`` and
        ``start``, a time on the driver's clock at which that worker's
        iteration was running): the latest record of the worker composed
        at or before ``start``, where its kind agrees. A worker composes
        its next plan only after its running one returns, so that record
        is the iteration's own."""
        by_wid: dict[int, list[IterationRecord]] = {}
        for r in self.iterations:
            by_wid.setdefault(r.wid, []).append(r)
        nows = {w: [r.now for r in rs] for w, rs in by_wid.items()}
        out = []
        for it in iterations:
            rs = by_wid.get(it.wid)
            i = bisect.bisect_right(nows[it.wid], it.start) - 1 if rs else -1
            if i >= 0 and rs[i].kind == it.kind:
                out.append(rs[i])
        return out


RECORDER = Recorder()
