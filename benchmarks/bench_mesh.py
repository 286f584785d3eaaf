"""Mesh serving benchmark: a ragged request stream on 1 vs 8 CPU-mesh devices.

The same ragged stream (mostly batch-3 under max_batch=4) is served twice
on the dit* model:

  solo : one single-device ``ServeSession``, one ``serve()`` per request —
         every request pays its own eager-calibration prefix and pads its
         own remainder chunks;
  mesh : the same submissions through a mesh-aware ``ServeScheduler`` on
         ``ServeMesh(8, dp=1)`` (8 single-device shards, async dispatch,
         cross-shard stealing on) — queued rows coalesce into full buckets
         across request boundaries and dispatch over the per-shard lanes.

Both regimes are warmed untimed first (solo serves the ladder once; mesh
runs ``warmup()``, which AOT-compiles shard 0 and primes every sibling
shard's placement-keyed executables) and each then runs one untimed
shakeout round of the exact stream, so ``wall_ratio`` = solo wall / mesh
wall compares steady-state serving; dispatch/steal counts are
timed-round deltas.
On this box the ratio is earned by dispatch coalescing (fewer
eager-calibration prefixes, fuller buckets — the same mechanism
bench_scheduler measures); shard-level concurrency adds on top only on
a multi-core host, since XLA CPU serving is compute-bound and the
shards share the cores. Recorded alongside:
per-shard dispatch counts, steal events, trace count, and the per-sample
bit-identity witness vs the solo regime (``bitidentical`` — gated exactly
by tools/check_bench.py).

The 8 host devices require ``--xla_force_host_platform_device_count=8``
BEFORE jax initializes, so the measurement runs in a child interpreter
pinned to the CPU backend; this module just launches it and records the
rows, which are CPU-mesh counts (``bench_mesh/platform`` = cpu), never chip
numbers. A child started after the parent has initialised a JAX backend
could find the chip held, so :func:`run` refuses then; ``benchmarks/run.py``
runs this bench first for that reason.

    PYTHONPATH=src python benchmarks/bench_mesh.py
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import common

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

N_DEVICES = 8
_CHILD = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
import json, sys, time
sys.path.insert(0, "benchmarks")
sys.path.insert(0, "src")
import numpy as np
import common
from repro.serve import DittoPlan, ServeMesh, ServeScheduler, ServeSession

STEPS = 8
MAX_BATCH = 4
# ragged on purpose: batch-1/2/3 requests each pay a whole dispatch
# (and pad up to a power-of-two bucket) when served independently; the
# scheduler packs them into full bucket-4 dispatches across requests
SIZES = [3, 1, 2, 1, 3, 1, 2, 1, 3, 1, 1, 2] * 2

bm = common.MODELS["dit*"]
dcfg, params = common.train_or_load(bm)
sched = common.schedule_for(bm)
plan = DittoPlan(steps=STEPS, sampler=bm.sampler, collect_stats=False,
                 max_batch=MAX_BATCH)
requests = [common.sample_inputs(bm, batch=b, seed=300 + i)
            for i, b in enumerate(SIZES)]

# ---- solo: one single-device serve() per request -----------------------
# both regimes get an untimed warm + one untimed shakeout round of the
# exact stream, so the timed round measures steady-state serving (no
# first-touch XLA compiles, no first-dispatch residuals)
sess = ServeSession(params, dcfg, sched, plan)
for b in (4, 2, 1):
    sess.serve(*common.sample_inputs(bm, batch=b, seed=900 + b))
[sess.serve(x, labels) for x, labels in requests]  # shakeout
t0 = time.monotonic()
solo = [sess.serve(x, labels) for x, labels in requests]
solo_s = time.monotonic() - t0

# ---- mesh: 8 shards, async dispatch, stealing on -----------------------
mesh = ServeMesh(8, dp=1, steal=True)
s = ServeScheduler(params, dcfg, sched, plan, mesh=mesh, async_mode=True,
                   dispatch_interval_ms=5.0)
warm = s.warmup()  # every shard: stolen dispatches hit warm executables
shake = [s.submit(x, labels) for x, labels in requests]  # shakeout
s.flush()
[t.result() for t in shake]
st0 = s.stats()
t0 = time.monotonic()
tickets = [s.submit(x, labels) for x, labels in requests]
s.flush()
mesh_s = time.monotonic() - t0
st = s.stats()
# timed-round deltas (stats are cumulative across the shakeout round)
d_dispatches = st["dispatches"] - st0["dispatches"]
d_shard = [a - b for a, b in zip(st["mesh"]["shard_dispatches"],
                                 st0["mesh"]["shard_dispatches"])]
d_steals = st["mesh"]["steals"] - st0["mesh"]["steals"]
d_stolen = st["mesh"]["stolen_rows"] - st0["mesh"]["stolen_rows"]

# per-sample bit-identity: every ticket's rows == its solo serve() rows
bit = all(np.array_equal(np.asarray(t.result()), np.asarray(r.sample))
          for t, r in zip(tickets, solo))
s.close()

print("MESH_ROWS_JSON:" + json.dumps({
    "requests": len(SIZES),
    "request_rows": sum(SIZES),
    "solo_total_s": round(solo_s, 2),
    "mesh_total_s": round(mesh_s, 2),
    "wall_ratio": round(solo_s / mesh_s, 2),
    "solo_dispatches": sum(len(r.chunks) for r in solo),
    "mesh_dispatches": d_dispatches,
    "shard_dispatches": d_shard,
    "steals": d_steals,
    "stolen_rows": d_stolen,
    "mesh_traces": st["traces"],
    "warm_aot": warm["aot_compiled"],
    "bitidentical": bool(bit),
    "shards": st["mesh"]["n_shards"],
}))
"""


def run():
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        raise RuntimeError(
            "bench_mesh starts a CPU child process; run it before anything in "
            "this process initialises a JAX backend")
    out = subprocess.run([sys.executable, "-c", _CHILD], capture_output=True,
                         text=True, cwd=ROOT, timeout=1200)
    payload = next((line.split(":", 1)[1] for line in out.stdout.splitlines()
                    if line.startswith("MESH_ROWS_JSON:")), None)
    if payload is None:
        raise RuntimeError(
            f"bench_mesh child produced no result:\n"
            f"{out.stdout[-2000:]}\n{out.stderr[-4000:]}")
    d = json.loads(payload)
    n = d["requests"]
    rows = [
        ("bench_mesh/platform", 0, "cpu"),
        ("bench_mesh/devices", 0, N_DEVICES),
        ("bench_mesh/shards", 0, d["shards"]),
        ("bench_mesh/requests", 0, n),
        ("bench_mesh/request_rows", 0, d["request_rows"]),
        ("bench_mesh/solo_total_s", round(d["solo_total_s"] * 1e6 / n, 1),
         d["solo_total_s"]),
        ("bench_mesh/mesh_total_s", round(d["mesh_total_s"] * 1e6 / n, 1),
         d["mesh_total_s"]),
        ("bench_mesh/wall_ratio", 0, d["wall_ratio"]),
        ("bench_mesh/solo_dispatches", 0, d["solo_dispatches"]),
        ("bench_mesh/mesh_dispatches", 0, d["mesh_dispatches"]),
        ("bench_mesh/shard_dispatches", 0, d["shard_dispatches"]),
        ("bench_mesh/steal_events", 0, d["steals"]),
        ("bench_mesh/stolen_rows", 0, d["stolen_rows"]),
        ("bench_mesh/mesh_traces", 0, d["mesh_traces"]),
        ("bench_mesh/warm_aot_executables", 0, d["warm_aot"]),
        ("bench_mesh/bitidentical", 0, d["bitidentical"]),
    ]
    common.record_perf("bench_mesh", rows)
    return rows


if __name__ == "__main__":
    common.emit(run())
