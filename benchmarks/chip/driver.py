"""The wall-clock driver of the scheduler, and what it records.

The program's only driver, ``repro.serving.simulator.Simulator``, runs on an
event clock. This is the same loop on the host's clock: the scheduler is
bound through its public driver API (``bind(defer)``, ``handle(kind, now,
payload)``), every event is handled at ``now = clock() - t0``, and each
arrival is handed over when it is due. So a request's ``first_token_time``
and ``finish_time`` are wall-clock seconds from the window's opening, and
its TTFT counts host time, lateness and workers taking turns.

``TimedBackend`` wraps the program's backend. It times every
``run_iteration`` and ``on_migrate`` call on the host's clock (the former
ends in ``block_until_ready``) and records what each iteration computed,
so that the scheduler's own host time is the time in ``handle`` less the
time inside the backend.

With ``annotate`` on, each call is wrapped in a ``TraceAnnotation`` named
``driver.wait_arrival`` (or ``driver.wait_event``, for a deferred event
such as a migration's arrival), ``sched.handle.<kind>``,
``backend.run_iteration.{decode,mixed}`` or ``backend.on_migrate``, so
that the device trace's idle gaps can be named by what the host was doing.
"""
from __future__ import annotations

import contextlib
import dataclasses
import heapq
import itertools
import time
from typing import Callable, Optional


@dataclasses.dataclass
class Iteration:
    wid: int
    start: float             # seconds from t0
    seconds: float           # host clock around run_iteration
    kind: str                # "decode" (no prefill part) or "mixed"
    prefill: list            # (start position, tokens, completes prompt)
    decode_ctx: list         # context length of each decoding request
    tokens_out: int          # tokens this iteration emits


class CompileClock:
    """Counts JAX's backend compiles (persistent-cache reads included),
    copied from the program's ``chip_smoke.CompileClock``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration


def _span(name: str, on: bool):
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


class TimedBackend:
    """Wraps an ``ExecutionBackend``: same results, timed on the host."""

    def __init__(self, inner, clock: Callable[[], float], t0: float,
                 annotate: bool = False):
        self.inner = inner
        self.clock = clock
        self.t0 = t0
        self.annotate = annotate
        self.iterations: list[Iteration] = []
        self.backend_seconds = 0.0
        self.migrate_seconds = 0.0

    def run_iteration(self, worker, plan) -> float:
        prefill = [(int(r.prefilled_tokens), int(t),
                    int(r.prefilled_tokens) + int(t) >= r.prompt_len)
                   for r, t in plan.prefill_parts]
        ctx = [int(r.context_len) for r in plan.decode_reqs]
        kind = "mixed" if prefill else "decode"
        start = self.clock()
        with _span(f"backend.run_iteration.{kind}", self.annotate):
            dur = self.inner.run_iteration(worker, plan)
        took = self.clock() - start
        self.backend_seconds += took
        self.iterations.append(Iteration(
            worker.wid, start - self.t0, took, kind, prefill, ctx,
            len(ctx) + sum(1 for _, _, last in prefill if last)))
        return dur

    def on_finish(self, req) -> None:
        self.inner.on_finish(req)

    def on_migrate(self, req, src_wid: int, dst_wid: int) -> None:
        start = self.clock()
        with _span("backend.on_migrate", self.annotate):
            self.inner.on_migrate(req, src_wid, dst_wid)
        took = self.clock() - start
        self.backend_seconds += took
        self.migrate_seconds += took


class WallDriver:
    """Feeds a ``ClusterScheduler`` its arrivals and deferred events on the
    host's clock. ``clock`` and ``sleep`` are injectable for tests."""

    def __init__(self, sched, backend: TimedBackend,
                 clock: Callable[[], float] = time.perf_counter,
                 sleep: Callable[[float], None] = time.sleep,
                 annotate: bool = False):
        self.sched = sched
        self.backend = backend
        self.clock = clock
        self.sleep = sleep
        self.annotate = annotate
        self.heap: list[tuple] = []
        self.seq = itertools.count()
        self.lateness: list[tuple[float, float]] = []   # (due, late by)
        self.handle_seconds = 0.0     # inside sched.handle, backend included
        sched.backend = backend
        sched.bind(self.defer)

    def defer(self, kind: str, when: float, payload=None) -> None:
        heapq.heappush(self.heap, (when, next(self.seq), kind, payload))

    def _handle(self, kind: str, now: float, payload) -> None:
        start = self.clock()
        with _span(f"sched.handle.{kind}", self.annotate):
            self.sched.handle(kind, now, payload)
        self.handle_seconds += self.clock() - start

    def run(self, arrivals: list, t0: float,
            done: Callable[[float], bool],
            hook: Optional[Callable[[float], None]] = None) -> float:
        """Serve ``arrivals`` (``Request``s sorted by ``arrival_time``,
        seconds from ``t0``) until ``done(now)``; returns ``now``. ``hook``
        is called with ``now`` before each step (window bookkeeping)."""
        i = 0
        while True:
            now = self.clock() - t0
            if hook is not None:
                hook(now)
            if done(now):
                return now
            next_arrival = arrivals[i].arrival_time if i < len(arrivals) \
                else float("inf")
            next_event = self.heap[0][0] if self.heap else float("inf")
            due = min(next_arrival, next_event)
            if due > now:
                if due == float("inf"):
                    return now
                what = "arrival" if next_arrival <= next_event else "event"
                with _span(f"driver.wait_{what}", self.annotate):
                    self.sleep(min(due - now, 0.05))
                continue
            if next_arrival <= next_event:
                req = arrivals[i]
                i += 1
                self.lateness.append((req.arrival_time,
                                      now - req.arrival_time))
                self._handle("arrival", now, req)
            else:
                _, _, kind, payload = heapq.heappop(self.heap)
                self._handle(kind, now, payload)
