"""Device time per call of the program's jitted step: the module whose name
holds ``step`` with the most device time in the traced window."""


def read(run):
    if run.trace is None:
        return None
    steps = [(s, n) for name, (s, n) in run.trace.modules.items() if "step" in name and n]
    if not steps:
        return None
    s, n = max(steps)
    return 1e3 * s / n
