"""Share of the window's compiled denoising steps that ran inside a segment
loop (the remaining steps of a plan segment as one on-device loop): the
change in ServeScheduler.stats()'s looped_steps over the change in its
compiled_steps. None where the program does not count looped steps, or
no compiled step ran in the window."""

KEYS = ("looped_steps", "compiled_steps")


def read(run):
    if not all(k in run.stats_before and k in run.stats_after for k in KEYS):
        return None
    d = {k: run.stats_after[k] - run.stats_before[k] for k in KEYS}
    return d["looped_steps"] / d["compiled_steps"] if d["compiled_steps"] else None
