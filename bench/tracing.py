"""Profiler capture and the reduction from a trace to device metrics.

The benchmark's own host spans (``jax.profiler.TraceAnnotation`` named
``bench.*``) share the trace's clock with the device's operations, so one
file gives both. The reduction works on plain :class:`Event` records, which
keeps it testable on a synthetic trace:

- busy time is the union of the operation intervals on each device's
  ``XLA Ops`` line, clipped to the traced window and averaged over chips;
- per-kernel time sums an operation's durations by its name with the
  trailing ``.<n>`` of the HLO instruction removed (``int8_matmul.7`` ->
  ``int8_matmul``);
- each idle gap (window minus busy) is labelled by the innermost benchmark
  span open at its midpoint and by the first operation after it.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"

_SUFFIX = re.compile(r"(\.\d+)+$")


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def base_name(name: str) -> str:
    """HLO instruction name without its numeric suffix. A TPU trace names an
    op by its whole HLO line (``%int8_matmul.7 = s32[...] custom-call(...)``):
    only the name before `` = `` is kept."""
    return _SUFFIX.sub("", name.split(" = ", 1)[0].lstrip("%"))


def load_events(log_dir: str) -> list[Event]:
    """Every event of the newest ``.xplane.pb`` under ``log_dir``."""
    import jax  # only the reader needs JAX

    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = jax.profiler.ProfileData.from_file(files[-1])
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                out.append(Event(plane.name, line.name, ev.name, float(ev.start_ns),
                                 float(ev.duration_ns)))
    return out


def merge(intervals) -> list[tuple[float, float]]:
    """Union of ``(start, end)`` intervals as sorted disjoint intervals."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float  # mean over the devices that ran anything
    devices: int
    ops: dict[str, list]  # base op name -> [seconds, count] (all devices)
    modules: dict[str, list]  # module name -> [seconds, count]
    gaps: list[tuple[str, float]]  # labelled idle gaps, longest first

    @property
    def idle_frac(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_seconds(self, name: str) -> float:
        return self.ops.get(name, [0.0, 0])[0]

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(((k, v[0]) for k, v in self.ops.items()), key=lambda kv: -kv[1])
        return {"device_ops": [[k, v] for k, v in ops[:top]],
                "idle_gaps": [[k, v] for k, v in self.gaps[:top]]}


def summarize(events: list[Event], window: tuple[float, float] | None = None) -> TraceSummary:
    """Reduce a trace to device busy time, per-op and per-module totals, and
    labelled idle gaps inside ``window`` (ns; default: the ``bench.window``
    span)."""
    spans = [e for e in events if not e.plane.startswith(DEVICE_PREFIX)
             and e.name.startswith(SPAN_PREFIX)]
    if window is None:
        wins = [e for e in spans if e.name == WINDOW_SPAN]
        if not wins:
            raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
        window = (wins[0].start_ns, wins[0].end_ns)
    lo, hi = window
    ops_by_dev: dict[str, list[Event]] = collections.defaultdict(list)
    ops: dict[str, list] = {}
    modules: dict[str, list] = {}
    for e in events:
        if not e.plane.startswith(DEVICE_PREFIX) or e.end_ns <= lo or e.start_ns >= hi:
            continue
        if e.line == OPS_LINE:
            ops_by_dev[e.plane].append(e)
            acc = ops.setdefault(base_name(e.name), [0.0, 0])
            acc[0] += (min(e.end_ns, hi) - max(e.start_ns, lo)) / 1e9
            acc[1] += 1
        elif e.line == MODULES_LINE:
            acc = modules.setdefault(e.name, [0.0, 0])
            acc[0] += (min(e.end_ns, hi) - max(e.start_ns, lo)) / 1e9
            acc[1] += 1
    busy = {dev: merge(clip([(e.start_ns, e.end_ns) for e in evs], lo, hi))
            for dev, evs in ops_by_dev.items()}
    busy_s = (sum(sum(e - s for s, e in iv) for iv in busy.values()) / 1e9 / len(busy)
              if busy else 0.0)
    inside = [s for s in spans if s.name != WINDOW_SPAN and s.end_ns > lo and s.start_ns < hi]
    gaps = []
    for dev, iv in busy.items():
        starts = sorted(e.start_ns for e in ops_by_dev[dev])
        nexts = {round(e.start_ns): base_name(e.name) for e in ops_by_dev[dev]}
        edges = [lo] + [x for pair in iv for x in pair] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e <= s:
                continue
            gaps.append((_label(s, e, inside, starts, nexts), (e - s) / 1e9))
    gaps.sort(key=lambda g: -g[1])
    return TraceSummary(window_s=(hi - lo) / 1e9, busy_s=busy_s, devices=len(busy),
                        ops=ops, modules=modules, gaps=gaps)


def _label(s: float, e: float, spans: list[Event], starts: list[float], nexts: dict) -> str:
    mid = (s + e) / 2
    open_ = [sp for sp in spans if sp.start_ns <= mid < sp.end_ns]
    host = min(open_, key=lambda sp: sp.dur_ns).name if open_ else "no span"
    i = bisect.bisect_left(starts, e)
    after = nexts.get(round(starts[i])) if i < len(starts) else "window end"
    return f"{host} before {after}"
