"""Traces and warmed-executable misses of the runner cache across the window."""


def read(run):
    keys = ("traces", "aot_misses")
    return float(sum(run.stats_after[k] - run.stats_before[k] for k in keys))
