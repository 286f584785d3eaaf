"""Share of the text stream's diff tiles that were zero over the window:
the change in ServeScheduler.stats()'s tiles_txt (zero, low, full), zero
over all. None where the program does not count them or ran no text-stream
layer in diff mode."""


def read(run):
    if "tiles_txt" not in run.stats_before or "tiles_txt" not in run.stats_after:
        return None
    d = [a - b for a, b in zip(run.stats_after["tiles_txt"], run.stats_before["tiles_txt"])]
    return 100.0 * d[0] / sum(d) if sum(d) else None
