"""Blocking device-to-host reads the program made per denoising step, eager
and compiled steps together, over the window's dispatches: the change in
ServeScheduler.stats()'s host_reads over the change in its step counts.
None where the program does not count them."""

KEYS = ("host_reads", "eager_steps", "compiled_steps")


def read(run):
    if not all(k in run.stats_before and k in run.stats_after for k in KEYS):
        return None
    d = {k: run.stats_after[k] - run.stats_before[k] for k in KEYS}
    steps = d["eager_steps"] + d["compiled_steps"]
    return d["host_reads"] / steps if steps else None
