"""Peak device memory in use after the window (memory_stats), in GB."""


def read(run):
    peak = run.device.get("memory_peak_bytes")
    return peak / 1e9 if peak is not None else None
