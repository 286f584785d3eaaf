"""Median wall time of one ServeSession.serve call of the window."""
import statistics


def read(run):
    walls = [d.t1 - d.t0 for d in run.dispatches]
    return statistics.median(walls) if walls else None
