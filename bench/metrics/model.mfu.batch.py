"""Dense model FLOPs of every denoising step of the traced dispatches (eager
ones included) over (traced window x the int8 peak), in %."""
from bench import work


def read(run):
    if run.trace is None or not run.traced:
        return None
    images = sum(d.rows for d in run.traced)
    flops = 2.0 * work.dit_macs(run.cell.config) * images * run.plan.steps
    return 100.0 * flops / (run.trace.window_s * run.peaks["int8_ops_s"])
