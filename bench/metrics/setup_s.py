"""Process start until the window's first request can be sent."""


def read(run):
    return run.setup_s
