"""Share of the traced window with no operation on the device."""


def read(run):
    return run.trace.idle_frac if run.trace is not None else None
