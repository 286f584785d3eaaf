"""The program's host spans in a profiler trace: where a dispatch's host
time goes, and which span each device idle gap falls in.

The serving path writes ``jax.profiler.TraceAnnotation`` spans named
``serve.*``, ``ditto.*`` and ``diffusion.*`` (``repro.core.spans``). They
nest on the thread that serves a dispatch, on the same clock as the
device's ``XLA Ops`` line. This module reduces plain
:class:`bench.tracing.Event` records, so it is testable on a synthetic
trace:

- ``totals``: per span name, seconds clipped to the window and the count;
- ``self_times``: per span name, its clipped seconds less the part its
  child spans on the same thread cover;
- ``label_gaps``: each device idle gap labelled by the innermost span
  (``bench.*`` or program) open at its midpoint and by the next operation;
- ``split``: all of the above for one traced window, plus the share of the
  dispatch's time that names a child span and the share of idle time that
  falls inside a program span other than ``serve.dispatch``.

``python3 bench/host_split.py`` traces one dispatch of a cell on the chip
and prints :func:`split`.
"""
from __future__ import annotations

import bisect
import collections

from bench import tracing
from bench.tracing import Event

PROGRAM_PREFIXES = ("serve.", "ditto.", "diffusion.")
DISPATCH_SPAN = "serve.dispatch"


def is_host(e: Event) -> bool:
    return not e.plane.startswith(tracing.DEVICE_PREFIX)


def program_spans(events) -> list[Event]:
    return [e for e in events if is_host(e) and e.name.startswith(PROGRAM_PREFIXES)]


def _clipped(e: Event, lo: float, hi: float) -> float:
    return max(0.0, min(e.end_ns, hi) - max(e.start_ns, lo))


def totals(spans, window) -> dict[str, list]:
    """Span name -> [seconds inside the window, spans that overlap it]."""
    lo, hi = window
    out: dict[str, list] = {}
    for e in spans:
        ns = _clipped(e, lo, hi)
        if ns > 0:
            acc = out.setdefault(e.name, [0.0, 0])
            acc[0] += ns / 1e9
            acc[1] += 1
    return out


def self_times(spans, window) -> dict[str, float]:
    """Span name -> seconds inside the window not covered by a child span.
    Spans of one host thread nest, so each span's direct children are
    disjoint and its self time is its time less theirs."""
    lo, hi = window
    out: dict[str, float] = collections.defaultdict(float)
    by_line = collections.defaultdict(list)
    for e in spans:
        by_line[(e.plane, e.line)].append(e)
    for evs in by_line.values():
        evs.sort(key=lambda e: (e.start_ns, -e.dur_ns))
        stack: list[Event] = []
        for e in evs:
            while stack and e.start_ns >= stack[-1].end_ns:
                stack.pop()
            ns = _clipped(e, lo, hi)
            out[e.name] += ns / 1e9
            if stack:
                out[stack[-1].name] -= ns / 1e9
            stack.append(e)
    return dict(out)


def busy_intervals(events, window) -> dict[str, list[tuple[float, float]]]:
    """Per device plane, the union of its ``XLA Ops`` intervals in the window."""
    lo, hi = window
    per_dev = collections.defaultdict(list)
    for e in events:
        if not is_host(e) and e.line == tracing.OPS_LINE and e.end_ns > lo and e.start_ns < hi:
            per_dev[e.plane].append((e.start_ns, e.end_ns))
    return {dev: tracing.merge(tracing.clip(iv, lo, hi)) for dev, iv in per_dev.items()}


def idle_intervals(busy, window) -> list[tuple[float, float]]:
    lo, hi = window
    edges = [lo] + [x for pair in busy for x in pair] + [hi]
    return [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]


def overlap_ns(a, b) -> float:
    """Length of the intersection of two sorted disjoint interval lists."""
    i = j = 0
    out = 0.0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def label_gaps(events, window) -> list[tuple[str, float]]:
    """Idle gaps (all devices), longest first, each labelled
    ``"<innermost bench.* or program span> before <next op>"``."""
    spans = [e for e in events if is_host(e)
             and e.name.startswith((tracing.SPAN_PREFIX,) + PROGRAM_PREFIXES)
             and e.name != tracing.WINDOW_SPAN]
    spans.sort(key=lambda e: e.start_ns)
    starts_sp = [e.start_ns for e in spans]
    longest = max((e.dur_ns for e in spans), default=0.0)
    busy = busy_intervals(events, window)
    ops = collections.defaultdict(list)
    for e in events:
        if not is_host(e) and e.line == tracing.OPS_LINE:
            ops[e.plane].append(e)
    gaps = []
    for dev, iv in busy.items():
        evs = sorted(ops[dev], key=lambda e: e.start_ns)
        starts = [e.start_ns for e in evs]
        for s, e in idle_intervals(iv, window):
            mid = (s + e) / 2
            k = bisect.bisect_right(starts_sp, mid)
            first = bisect.bisect_left(starts_sp, mid - longest)
            open_ = [sp for sp in spans[first:k] if sp.end_ns > mid]
            host = min(open_, key=lambda sp: sp.dur_ns).name if open_ else "no span"
            i = bisect.bisect_left(starts, e)
            after = tracing.base_name(evs[i].name) if i < len(evs) else "window end"
            gaps.append((f"{host} before {after}", (e - s) / 1e9))
    gaps.sort(key=lambda g: -g[1])
    return gaps


def window_of(events) -> tuple[float, float]:
    """The ``bench.window`` span, else the extent of the ``serve.dispatch`` spans."""
    wins = [e for e in events if is_host(e) and e.name == tracing.WINDOW_SPAN]
    if wins:
        return (wins[0].start_ns, wins[0].end_ns)
    disp = [e for e in events if is_host(e) and e.name == DISPATCH_SPAN]
    if not disp:
        raise ValueError(f"trace holds neither {tracing.WINDOW_SPAN!r} nor {DISPATCH_SPAN!r}")
    return (min(e.start_ns for e in disp), max(e.end_ns for e in disp))


def split(events, window=None, top: int = 10) -> dict:
    """Where one traced window's host and device time went."""
    window = window_of(events) if window is None else window
    lo, hi = window
    prog = program_spans(events)
    busy = busy_intervals(events, window)
    idle = {dev: idle_intervals(iv, window) for dev, iv in busy.items()}
    idle_ns = sum(e - s for iv in idle.values() for s, e in iv)
    inner = tracing.merge((e.start_ns, e.end_ns) for e in prog if e.name != DISPATCH_SPAN)
    inner = tracing.clip(inner, lo, hi)
    idle_named = sum(overlap_ns(iv, inner) for iv in idle.values())
    tot = totals(prog, window)
    own = self_times(prog, window)
    dispatch_s = tot.get(DISPATCH_SPAN, [0.0, 0])[0]
    named_s = sum(v for k, v in own.items() if k != DISPATCH_SPAN)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(e - s for iv in busy.values() for s, e in iv) / 1e9 / max(len(busy), 1),
        "idle_s": idle_ns / 1e9 / max(len(busy), 1),
        "spans": tot,
        "self_s": dict(sorted(own.items(), key=lambda kv: -kv[1])),
        "dispatch_s": dispatch_s,
        "named_share": named_s / dispatch_s if dispatch_s else None,
        "idle_named_share": idle_named / idle_ns if idle_ns else None,
        "idle_gaps": label_gaps(events, window)[:top],
    }
