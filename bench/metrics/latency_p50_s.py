"""Median due-to-completion latency of the window's requests."""
import statistics

from bench import readers


def read(run):
    lat = readers.latencies(run)
    return statistics.median(lat) if lat else None
