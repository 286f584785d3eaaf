"""Reductions shared by more than one metric reader in ``bench/metrics/``.

Each reader file is ``read(run) -> float | None``; it returns ``None`` when
the run holds nothing to read (no trace, no kernel of that name, no
requests), and the harness then leaves the metric out of the line.
"""
from __future__ import annotations

import math

from bench import work


def latencies(run) -> list[float]:
    """Due-to-completion latency of every request of the window; a failed
    request counts as never completing."""
    return sorted(r.latency_s if r.error is None else math.inf for r in run.requests)


def kernel_roofline(run, kernel: str):
    """Least time of the kernel's calls in the traced window over their
    device time, in %. The calls come from the traced dispatches' modes and
    buckets; the number of compiled steps from the program's records where
    it keeps them, else from the kernel's event count in the trace."""
    if run.trace is None or not run.traced:
        return None
    seconds, events = run.trace.ops.get(kernel, (0.0, 0))
    if not seconds or any(d.modes is None for d in run.traced):
        return None
    ops = nbytes = 0.0
    for d in run.traced:
        calls = [c for b in d.buckets for c in work.step_calls(run.cell.config, b, d.modes)
                 if c.kernel == kernel]
        per_step = sum(c.count for c in calls)
        if not per_step:
            continue
        steps = d.compiled_steps
        if steps is None:
            steps = events / per_step / len(run.traced)
        nbytes += steps * sum(c.bytes() for c in calls)
        ops += d.diff_tile_ops if kernel == "ditto_diff_matmul" else steps * sum(
            c.ops() for c in calls)
    least = max(ops / run.peaks["int8_ops_s"], nbytes / run.peaks["hbm_bytes_s"])
    return 100.0 * least / seconds
