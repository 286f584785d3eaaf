"""End-to-end serving driver: batched image-generation requests through the
persistent Ditto serving runtime (the paper's deployment scenario —
inference acceleration).

A request queue of (n_images, class) jobs is dynamically batched and fed
to a :class:`repro.serve.ServeSession` configured by ONE
:class:`repro.serve.DittoPlan` (the CLI flags below just fill plan
fields); each batch runs the quantized DDIM loop with Defo execution-flow
optimization: steps 1-2 run the eager calibration engine, then the
per-layer modes are frozen and the remaining steps run through the
jit-compiled Pallas path (act layers -> int8_matmul, diff layers ->
diff_encode + ditto_diff_matmul with on-device tile skipping). The
session pads ragged batches to power-of-two batch buckets and reuses ONE
compiled runner per (mode signature, plan.cache_sig(), bucket) across
the whole queue — only the first batch of a bucket pays XLA
trace + compile. Per request we report: wall time, simulated
Ditto-hardware time, simulated ITC time (the baseline an operator would
compare against), and the runner-cache hit/trace stats. Fault tolerance:
the serving loop checkpoints its request log atomically and can resume
mid-queue.

With ``--deadline-ms`` (and/or ``--warmup``) the same queue instead goes
through the async SLO-aware front-end (:class:`repro.serve.ServeScheduler`
with ``async_mode=True``): every request is submitted individually with a
latency budget, a background dispatch thread coalesces them into bucket
batches — full buckets dispatch immediately, a request whose budget nears
fires a partial-bucket dispatch — and ``--warmup`` AOT-compiles the
bucket ladder up front so no request pays trace+compile. Samples are
bit-identical to the synchronous path.

    PYTHONPATH=src python examples/serve_diffusion.py [--requests 6] [--batch 4] [--eager]
    PYTHONPATH=src python examples/serve_diffusion.py --low-bits 4   # packed-int4 low tiles
    PYTHONPATH=src python examples/serve_diffusion.py --fused        # single-pass fused kernel
    PYTHONPATH=src python examples/serve_diffusion.py --int4-from 8  # int8 early, int4+fused late
    PYTHONPATH=src python examples/serve_diffusion.py --deadline-ms 2000 --warmup  # async SLO mode
    PYTHONPATH=src python examples/serve_diffusion.py --chaos 7       # seeded fault schedule
    PYTHONPATH=src python examples/serve_diffusion.py --mesh 8        # 8-shard CPU mesh

``--mesh N`` puts the same scheduler on a :class:`repro.serve.ServeMesh`
of N single-device shards (forcing N host CPU devices before jax
initializes): each shard runs its own dispatch queue and session, new
request groups route to the least-loaded shard, and an idle shard steals
due work from a busy sibling's queue. Samples stay bit-identical to
single-device serving — a shard's identity is its data-parallel width
and axis name (part of ``plan.cache_sig()``), never its concrete
devices, so all shards share one runner cache and one trace set. CI runs
this as the mesh smoke.

``--chaos SEED`` serves the queue under a seeded fault schedule
(:func:`repro.serve.chaos_schedule` over the ``session.serve`` and
``denoise.step`` sites) with the recovery stack armed: a retry/fallback
ladder on the dispatch path and the numerical re-anchor watchdog on the
denoise path. Every request must still resolve — CI runs this as the
chaos smoke.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# --mesh N serves over N host devices, and jax locks the device count at
# first init — so the flag must reach XLA_FLAGS before ANY jax import
# (repro.serve.mesh.force_host_device_count does the same for libraries;
# an example script peeks its own argv)
if "--mesh" in sys.argv[1:]:
    _n = int(sys.argv[sys.argv.index("--mesh") + 1])
    if _n > 1 and "host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={_n}").strip()

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.compile_cache import use_compile_cache
from repro.core import diffusion
from repro.data.synthetic import DataCfg, batch_for
from repro.launch import steps as steps_mod
from repro.serve import (DittoPlan, PlanSchedule, ServeMesh, ServeScheduler,
                         ServeSession, chaos_schedule, inject)
from repro.sim import harness


def build_model(train_steps=200):
    arch = dataclasses.replace(
        configs.get("dit-xl2").smoke(), n_layers=3, d_model=64, input_size=16, n_classes=8
    )
    dcfg = steps_mod.make_dit_model(arch)
    opt = steps_mod.make_optimizer(arch, base_lr=2e-3, total=train_steps)
    state = steps_mod.init_state(arch, jax.random.PRNGKey(0), opt)
    train = jax.jit(steps_mod.make_train_step(arch, opt))
    dc = DataCfg(seed=0, batch=16, seq_len=1)
    for step in range(train_steps):
        state, _ = train(state, batch_for(arch, dc, step))
    return arch, dcfg, state["params"]


def serve_async(args, arch, dcfg, params, sched, plan, done, queue):
    """Async SLO path: one submission per request, background dispatch."""
    import contextlib
    import time

    injector = None
    if args.chaos is not None:
        # session.serve errors exercise the retry/fallback ladder (a
        # 3-retry budget always out-lasts 3 one-shot faults); denoise.step
        # poisons/drift exercise the re-anchor watchdog
        injector = chaos_schedule(args.chaos, n_faults=3,
                                  sites=("session.serve", "denoise.step"),
                                  max_at=6)
        print(f"[serve] chaos seed {args.chaos}: "
              + ", ".join(f"{f.kind}@{f.site}[{f.at}]"
                          for f in injector.faults))
    mesh = ServeMesh(args.mesh, dp=1) if args.mesh else None
    if mesh is not None:
        print(f"[serve] mesh: {mesh.n_shards} shard(s) over "
              f"{mesh.n_devices} device(s), dp={mesh.dp}, "
              f"steal={'on' if mesh.steal else 'off'}")
    s = ServeScheduler(params, dcfg, sched, plan, async_mode=True,
                       dispatch_interval_ms=25.0, mesh=mesh)
    if args.warmup:
        w = s.warmup()
        print(f"[serve] warmup: {w['aot_compiled']} executable(s) AOT-compiled "
              f"({w['traces']} trace(s)) in {w['wall_s']:.1f}s")
    t0 = time.monotonic()
    tickets = []
    with (inject(injector) if injector is not None
          else contextlib.nullcontext()):
        with s:
            for rid, cls in queue:
                key = jax.random.fold_in(jax.random.PRNGKey(42), rid)
                x = jax.random.normal(
                    key, (1, arch.input_size, arch.input_size, arch.in_channels))
                tickets.append(
                    (rid, cls, s.submit(x, jnp.array([cls]),
                                        deadline_ms=args.deadline_ms)))
            for _, _, t in tickets:
                t.result(timeout=600.0)
            st = s.stats()
    wall = time.monotonic() - t0
    if injector is not None:
        print(f"[serve] chaos: {len(injector.fired)}/{len(injector.faults)} "
              f"fault(s) fired, {st['retries']} retry(ies), "
              f"{st['fallback_dispatches']} fallback dispatch(es), "
              f"{st['watchdog_events']} watchdog re-anchor(s), "
              f"{st['failed']} failed ticket(s)")
    for rid, cls, t in tickets:
        lat = t.done_t - t.submit_t
        done[rid] = {"class": cls, "wall_s": lat}
        print(f"[serve] request {rid}: latency {lat * 1e3:.0f}ms")
    tmp = args.log + ".tmp"
    with open(tmp, "w") as f:
        json.dump(done, f)
    os.replace(tmp, args.log)
    print(f"[serve] served {len(tickets)} request(s) in {wall:.1f}s: "
          f"{st['dispatches']} dispatch(es) {st['triggers']}, "
          f"{st['pad_rows']} pad row(s), "
          f"{st['deadline_misses']} deadline miss(es)")
    print(f"[serve] runner cache: {st['runners']} compiled runner(s), "
          f"{st['traces']} trace(s), {st['hits']} hit(s), "
          f"{st['aot_hits']} AOT hit(s)")
    if mesh is not None:
        m = st["mesh"]
        print(f"[serve] mesh: shard dispatches {m['shard_dispatches']}, "
              f"{m['steals']} steal(s) ({m['stolen_rows']} row(s))")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--log", default="/tmp/ditto_serve_log.json")
    ap.add_argument("--eager", action="store_true",
                    help="run every step on the eager engine (no compiled path)")
    ap.add_argument("--low-bits", type=int, default=8, choices=(4, 8),
                    help="4 = execute class-1 diff tiles through the packed-int4 "
                         "kernel branch (bit-identical samples, separate runner "
                         "cache key)")
    ap.add_argument("--fused", action="store_true",
                    help="run diff layers through the single-pass fused kernel "
                         "(scalar-prefetch DMA skipping, y_prev epilogue) — "
                         "bit-identical samples, separate runner cache key")
    ap.add_argument("--int4-from", type=int, default=None, metavar="STEP",
                    help="serve a PlanSchedule instead of one constant plan: "
                         "steps [0, STEP) run the base lowering, steps "
                         "[STEP, --steps) run low_bits=4 + fused (bit-identical "
                         "samples; exactly one extra trace for the late segment)")
    ap.add_argument("--deadline-ms", type=float, default=None, metavar="MS",
                    help="serve through the async SLO-aware ServeScheduler: "
                         "each request carries this latency budget; partial "
                         "buckets dispatch when a budget nears instead of "
                         "waiting for a full bucket")
    ap.add_argument("--warmup", action="store_true",
                    help="AOT-compile the whole bucket ladder before serving "
                         "(implies the async scheduler) so the first request "
                         "of each bucket skips trace AND compile")
    ap.add_argument("--mesh", type=int, default=None, metavar="N",
                    help="serve through a ServeMesh of N single-device CPU "
                         "shards (implies the async scheduler): per-shard "
                         "dispatch queues with cross-shard work stealing; "
                         "forces N host devices via XLA_FLAGS when needed")
    ap.add_argument("--chaos", type=int, default=None, metavar="SEED",
                    help="serve under a seeded fault schedule (implies the "
                         "async scheduler) with the retry/fallback ladder "
                         "and the re-anchor watchdog armed; every request "
                         "must still resolve")
    args = ap.parse_args(argv)
    use_compile_cache()
    if args.int4_from is not None and not 0 < args.int4_from < args.steps:
        ap.error(f"--int4-from must be inside (0, {args.steps})")
    if args.chaos is not None and args.int4_from is not None:
        ap.error("--chaos arms a constant recovery plan; drop --int4-from")
    if args.mesh is not None and args.mesh < 1:
        ap.error("--mesh needs at least 1 device")

    arch, dcfg, params = build_model()
    sched = diffusion.cosine_schedule(1000)

    # request queue: (request_id, class label) — resume from a prior log
    done = {}
    if os.path.exists(args.log):
        done = {int(k): v for k, v in json.load(open(args.log)).items()}
        print(f"[serve] resuming: {len(done)} requests already served")
    queue = [(i, i % arch.n_classes) for i in range(args.requests) if i not in done]

    # ONE DittoPlan is the whole serving configuration: sampling loop,
    # kernel lowering and serve behavior (the plan is also the runner-cache
    # trace identity — see repro.serve.cache.RunnerKey)
    # bucket ladders are power-of-two (bucket_for/DittoPlan validate this
    # now), so round a ragged --batch up to the next bucket
    max_batch = 1 << (max(args.batch, 1) - 1).bit_length()
    plan = DittoPlan(steps=args.steps, compiled=not args.eager,
                     low_bits=args.low_bits, fused=args.fused,
                     max_batch=max_batch)
    if args.int4_from is not None:
        # a schedule is a plan per phase: the denoise loop partitions by
        # segment, each distinct segment sig compiles one trace
        plan = PlanSchedule(plan, [
            (0, args.int4_from, {}),
            (args.int4_from, args.steps, dict(low_bits=4, fused=True)),
        ])
    if args.chaos is not None:
        # recovery stack: dispatch ladder (fused -> unfused -> int8) plus
        # the numerical watchdog with the saturation re-anchor armed; none
        # of these fields is trace identity (DittoPlan.cache_sig), so the
        # runner cache behaves exactly as in the fault-free run
        plan = plan.replace(max_retries=3, retry_backoff_ms=25.0,
                            fallbacks=(dict(fused=False),
                                       dict(fused=False, low_bits=8)),
                            watchdog=True, reanchor_full_frac=0.97)
    if (args.deadline_ms is not None or args.warmup or args.chaos is not None
            or args.mesh is not None):
        return serve_async(args, arch, dcfg, params, sched, plan, done, queue)
    sess = ServeSession(params, dcfg, sched, plan)
    while queue:
        batch_reqs, queue = queue[: args.batch], queue[args.batch :]
        rids = [r for r, _ in batch_reqs]
        labels = jnp.array([c for _, c in batch_reqs])
        key = jax.random.fold_in(jax.random.PRNGKey(42), rids[0])
        x = jax.random.normal(key, (len(rids), arch.input_size, arch.input_size, arch.in_channels))

        result = sess.serve(x, labels)
        records, eng = result.records, result.chunks[0].engine
        wall = result.wall_s
        res = harness.run_designs(records, t_mult=64, d_mult=18,
                                  designs=("itc", "ditto", "ditto+"))
        s = eng.summary()
        n_compiled = sum(1 for r in records if r.get("compiled"))
        modes = dict(s["modes"])
        # records are collected at BUCKET scale (padded rows are replicas),
        # so per-request sim cost divides by the bucket, not the true batch
        bucket = result.chunks[0].bucket  # None = eager (unbucketed) chunk
        dispatch_b = bucket or result.chunks[0].batch
        for i, rid in enumerate(rids):
            done[rid] = {
                "class": int(labels[i]),
                "wall_s": wall / len(rids),
                "compiled_records": n_compiled,
                "bucket": bucket,
                "cached_runner": result.traces_delta == 0,
                "modes": modes,
                "sim_ditto_ms": res["ditto"]["time_s"] * 1e3 / dispatch_b,
                "sim_itc_ms": res["itc"]["time_s"] * 1e3 / dispatch_b,
                "speedup": res["itc"]["time_s"] / res["ditto"]["time_s"],
                "bops_ratio": s["bops"] / s["bops_act"],
            }
        # checkpoint the served log atomically: a crash mid-write must not
        # corrupt the resume file
        tmp = args.log + ".tmp"
        with open(tmp, "w") as f:
            json.dump(done, f)
        os.replace(tmp, args.log)
        cache_note = "eager (no compiled runner)" if bucket is None else \
            "cached runner" if result.traces_delta == 0 else \
            f"{result.traces_delta} new trace(s)"
        print(f"[serve] batch {rids} (bucket {result.chunks[0].bucket}, {cache_note}): "
              f"wall {wall:.1f}s  "
              f"sim ditto {res['ditto']['time_s']*1e3:.2f}ms vs itc {res['itc']['time_s']*1e3:.2f}ms "
              f"(speedup {res['itc']['time_s']/res['ditto']['time_s']:.2f}x)")
    n = len(done)
    sp = np.mean([d["speedup"] for d in done.values()])
    st = sess.stats()
    print(f"[serve] served {n} requests; mean simulated speedup vs ITC: {sp:.2f}x")
    print(f"[serve] runner cache: {st['runners']} compiled runner(s), {st['traces']} trace(s), "
          f"{st['hits']} hit(s) across {st['batches']} batches")


if __name__ == "__main__":
    main()
