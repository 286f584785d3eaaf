"""Trace one dispatch of a cell on the chip and print where its time went.

    python3 bench/host_split.py --workload xl2-256.batch --seed 7

Builds the cell as ``bench/run.py`` does (weights from the seed, the
program's ``ServeScheduler``, the same warm-up), then serves one dispatch
of the cell's largest bucket untraced, one under the profiler, and one
untraced again, each timed on the host clock. The traced one is reduced by
:func:`bench.spans.split`: seconds and self time per program span, the
share of the dispatch that a child span names, the share of device idle
time inside a program span other than ``serve.dispatch``, and the longest
idle gaps with their labels. The scheduler's counters over the traced
dispatch give host reads per denoising step.

Prints one JSON line. Exits 3 without a TPU, as ``bench/run.py`` does.
"""
import time

PROCESS_T0 = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from bench import harness, spans, tracing  # noqa: E402

TRACE_DIR = os.path.join(ROOT, ".bench_trace", "host_split")
COUNTERS = ("host_reads", "eager_steps", "compiled_steps", "queue_wait_s",
            "tickets_dispatched")


def serve_one(scheduler, traffic, rows: int) -> float:
    """Serve one dispatch of ``rows`` rows; its wall time on the host clock."""
    from bench.traffic import DISPATCH_TIMEOUT_S

    reqs = traffic.first_requests(rows)
    t0 = time.monotonic()
    tickets = [scheduler.submit(r.x, r.labels) for r in reqs]
    for t in tickets:
        t.result(timeout=DISPATCH_TIMEOUT_S)
    return time.monotonic() - t0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    try:
        device = harness.device_info(cell.chips)
    except harness.NoDevice as e:
        harness.log(str(e))
        return 3
    harness.configure_jax()

    import jax

    from bench.traffic import Traffic, seed_key
    from repro.core import diffusion
    from repro.core.ditto.plan import DittoPlan
    from repro.serve import ServeScheduler

    config, mix = cell.config, cell.mix
    weights = cell.family.init_weights(config, seed_key(args.seed, 0))
    params, model_cfg = cell.family.program_model(config, weights)
    plan = DittoPlan(steps=mix["steps"], sampler=mix["sampler"],
                     max_batch=config["max_batch"], **mix.get("plan", {}))
    traffic = Traffic(mix, max_batch=config["max_batch"],
                      latent_shape=cell.family.latent_shape(config),
                      n_classes=config["num_classes"], seed=args.seed)
    scheduler = ServeScheduler(params, model_cfg, diffusion.cosine_schedule(1000), plan,
                               async_mode=True)
    rows = max(traffic.buckets())
    try:
        scheduler.warmup(buckets=traffic.buckets())
        traffic.warm(scheduler)
        setup_s = time.monotonic() - PROCESS_T0
        harness.log(f"set-up {setup_s:.1f} s", device)
        untraced = [serve_one(scheduler, traffic, rows)]
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        before = scheduler.stats()
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
        with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
            traced = serve_one(scheduler, traffic, rows)
        jax.profiler.stop_trace()
        after = scheduler.stats()
        untraced.append(serve_one(scheduler, traffic, rows))
    finally:
        scheduler.close(drain=False)
    delta = {k: after[k] - before[k] for k in COUNTERS if k in after}
    steps = delta.get("eager_steps", 0) + delta.get("compiled_steps", 0)
    out = {"workload": args.workload, "seed": args.seed, "device": device,
           "rows": rows, "setup_s": setup_s, "untraced_s": untraced, "traced_s": traced,
           "counters": delta,
           "reads_per_step": delta["host_reads"] / steps if steps else None,  # None: uncounted
           **spans.split(tracing.load_events(TRACE_DIR))}
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
