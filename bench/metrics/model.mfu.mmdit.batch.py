"""MMDiT multiply-adds (``bench/work_mmdit.py``) of every denoising step of
the traced dispatches (eager ones included), as operations, over (traced
window x the int8 peak), in %."""
from bench import work_mmdit


def read(run):
    if run.trace is None or not run.traced:
        return None
    images = sum(d.rows for d in run.traced)
    ops = 2.0 * work_mmdit.mmdit_macs(run.cell.config) * images * run.plan.steps
    return 100.0 * ops / (run.trace.window_s * run.peaks["int8_ops_s"])
