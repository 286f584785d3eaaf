"""Nearest-rank 95th percentile latency; the maximum below 20 requests."""
import math

from bench import readers


def read(run):
    lat = readers.latencies(run)
    if not lat:
        return None
    return lat[-1] if len(lat) < 20 else lat[math.ceil(0.95 * len(lat)) - 1]
