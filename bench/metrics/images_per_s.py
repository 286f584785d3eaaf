"""Rows completed over (last completion - window start)."""


def read(run):
    rows = sum(r.rows for r in run.served)
    return rows / (run.last_done - run.t0) if rows else None
