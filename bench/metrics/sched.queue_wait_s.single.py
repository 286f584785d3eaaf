"""Queue wait of the window's requests, dispatch_t - submit_t on the
scheduler's clock: the change in ServeScheduler.stats()'s queue_wait_s
over the change in tickets_dispatched (a mean; the harness drops the
tickets before the readers run). None where the program does not stamp it."""

KEYS = ("queue_wait_s", "tickets_dispatched")


def read(run):
    if not all(k in run.stats_before and k in run.stats_after for k in KEYS):
        return None
    n = run.stats_after["tickets_dispatched"] - run.stats_before["tickets_dispatched"]
    wait = run.stats_after["queue_wait_s"] - run.stats_before["queue_wait_s"]
    return wait / n if n else None
