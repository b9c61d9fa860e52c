"""Reduction of a JAX profiler trace to busy time, idle gaps and top ops.

Device operations are the events of every device plane's ``XLA Ops``
line;
host spans are the harness's own ``TraceAnnotation`` events (names in
``SPAN_PREFIXES``) on any host thread. Both are read on the trace's one
clock, in nanoseconds, and clipped to the traced window, which the
harness marks with a ``bench.trace_window`` span.

* busy: the union of a device's operation intervals; averaged over the
  chips the cell uses.
* idle gaps: the complement of that union inside the window, each named
  by the innermost harness span around its midpoint ("untraced" where
  none is).
* top ops: by self time (an op's time less the ops nested in it, as a
  scan's body in its ``while``), named up to the HLO text's `` = ``.
"""
from __future__ import annotations

import collections
from pathlib import Path

WINDOW_SPAN = "bench.trace_window"
SPAN_PREFIXES = ("driver.", "sched.", "backend.")
OPS_LINE = "XLA Ops"


def load(trace_dir: str | Path):
    """(ops by device: {plane: [(start, end, name)]}, host spans
    [(start, end, name)], window (start, end)) from the newest
    ``*.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(str(files[-1]))
    ops: dict[str, list] = {}
    spans: list = []
    window = None
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.setdefault(plane.name, []).extend(
                        (e.start_ns, e.start_ns + e.duration_ns,
                         e.name.split(" = ")[0]) for e in line.events)
        else:
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_SPAN:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
                    elif e.name.startswith(SPAN_PREFIXES):
                        spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                      e.name))
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    return ops, spans, window


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged intervals clipped to ``[lo, hi]``."""
    out: list[list[float]] = []
    for s, e, *_ in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: list[tuple[float, float]], lo: float, hi: float):
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def name_gaps(gaps_, spans) -> list[str]:
    """Each gap's name: the innermost (latest-starting) host span around
    its midpoint, "untraced" where none is. One sweep over both lists."""
    order = sorted(range(len(gaps_)),
                   key=lambda i: gaps_[i][0] + gaps_[i][1])
    spans = sorted(spans)
    names = ["untraced"] * len(gaps_)
    active: list[tuple] = []
    j = 0
    for i in order:
        mid = 0.5 * (gaps_[i][0] + gaps_[i][1])
        while j < len(spans) and spans[j][0] <= mid:
            active.append(spans[j])
            j += 1
        active = [a for a in active if a[1] >= mid]
        if active:
            names[i] = max(active, key=lambda a: a[0])[2]
    return names


def self_times(intervals) -> dict[str, float]:
    """Seconds per op name, each op less the ops nested inside it."""
    out: dict[str, float] = collections.defaultdict(float)
    stack: list[list] = []
    for s, e, name in sorted(intervals, key=lambda v: (v[0], -v[1])):
        while stack and (stack[-1][1] <= s or stack[-1][1] < e):
            top = stack.pop()
            top[3] -= max(0, top[1] - s)      # a partial overlap
            out[top[2]] += top[3]
        if stack:
            stack[-1][3] -= e - s
        stack.append([s, e, name, e - s])
    for top in stack:
        out[top[2]] += top[3]
    return {k: v * 1e-9 for k, v in out.items()}


def reduce(ops: dict, spans: list, window, devices: int,
           top: int = 10) -> dict:
    """busy_s (mean over the ``devices`` chips the cell uses; one that ran
    nothing counts as idle), window_s, idle_share, and the top device ops
    and idle gaps, in seconds."""
    lo, hi = window
    window_s = (hi - lo) * 1e-9
    per_device = {d: union(iv, lo, hi) for d, iv in ops.items()}
    per_device = {d: b for d, b in per_device.items() if b}
    for i in range(devices - len(per_device)):
        per_device[f"idle:{i}"] = []
    busy_s = sum(sum(e - s for s, e in b) for b in per_device.values()) \
        * 1e-9 / len(per_device)
    op_time: collections.Counter = collections.Counter()
    for iv in ops.values():
        inside = [(max(s, lo), min(e, hi), n) for s, e, n in iv
                  if min(e, hi) > max(s, lo)]
        for name, t in self_times(inside).items():
            op_time[name] += t / len(per_device)
    gap_time: collections.Counter = collections.Counter()
    for b in per_device.values():
        g = gaps(b, lo, hi)
        for (s, e), name in zip(g, name_gaps(g, spans)):
            gap_time[name] += (e - s) * 1e-9 / len(per_device)
    return {"busy_s": busy_s, "window_s": window_s,
            "idle_share": 1.0 - busy_s / window_s,
            "device_ops": [[n, t] for n, t in op_time.most_common(top)],
            "idle_gaps": [[n, t] for n, t in gap_time.most_common(top)]}


def describe(trace_dir: str | Path, per_line: int = 3) -> list:
    """Planes, lines, event counts and a few events of a trace: what to
    look at before trusting ``load``."""
    from jax.profiler import ProfileData
    out = []
    for f in sorted(Path(trace_dir).rglob("*.xplane.pb")):
        data = ProfileData.from_file(str(f))
        for plane in data.planes:
            lines = []
            for line in plane.lines:
                evs = list(line.events)
                lines.append([line.name, len(evs), [
                    [e.name, e.start_ns, e.duration_ns]
                    for e in evs[:per_line]]])
            out.append([plane.name, lines[:40]])
    return out


def write_excerpt(path, ops: dict, spans: list, window,
                  span_ns: float = 50e6) -> None:
    """The first ``span_ns`` of a traced window, as JSON: a small
    recorded trace for the reduction's test."""
    import json
    lo = window[0]
    hi = lo + span_ns

    def keep(iv):
        return [[s - lo, e - lo, n] for s, e, n in iv if s < hi and e > lo]

    Path(path).parent.mkdir(parents=True, exist_ok=True)
    Path(path).write_text(json.dumps({
        "window": [0, span_ns],
        "ops": {d: keep(iv) for d, iv in ops.items()},
        "spans": keep(spans)}))
