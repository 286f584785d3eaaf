"""Sharded multi-device serving: mesh identity, routing, stealing, and the
8-device bit-identity / trace-sharing / fault-isolation contracts.

Two layers, matching how a mesh is testable on this box:

* in-process tests (any device count): plan/ServeMesh validation, the
  mesh signature's place in ``cache_sig()`` and the scheduler group key,
  and the routing + work-stealing policy driven deterministically through
  ``poll(shard=...)`` over duck-typed per-shard sessions.
* subprocess tests: a REAL 8-device CPU mesh forced with
  ``--xla_force_host_platform_device_count=8`` (the tests/test_pipeline.py
  idiom — the flag must precede jax initialization, so each gets its own
  interpreter), proving per-sample bit-identity against solo serving,
  shard trace-sharing vs unsharded isolation, warmup-once-per-mesh-sig,
  cross-shard stealing under a skewed arrival stream, and one-shard fault
  recovery via the PR 9 ladder without poisoning siblings.
"""
import subprocess
import sys
import types
from pathlib import Path

import jax.numpy as jnp
import pytest

from repro.core.ditto import DittoPlan
from repro.core.ditto.plan import MESH_SIG_FIELDS, PlanSchedule
from repro.serve import ServeMesh, ServeScheduler, bucket_for
from repro.serve.mesh import MESH_POLICY_FIELDS
from repro.serve.session import ChunkResult, ServeResult

REPO = Path(__file__).resolve().parent.parent

PLAN = DittoPlan(steps=3, policy="diff", max_batch=4, collect_stats=False)


# -------------------------------------------------------- plan mesh fields
def test_plan_mesh_validation():
    assert DittoPlan().mesh_sig() is None
    p = DittoPlan(mesh_devices=4, mesh_axis="dp")
    assert p.mesh_sig() == (4, "dp")
    with pytest.raises(ValueError, match="mesh_devices"):
        DittoPlan(mesh_devices=3)
    with pytest.raises(ValueError, match="mesh_devices"):
        DittoPlan(mesh_devices=0)
    with pytest.raises(ValueError, match="mesh_axis"):
        DittoPlan(mesh_devices=2, mesh_axis="not an identifier")


def test_sharded_plan_refused_under_native_lowering():
    """dp > 1 under native lowering: GSPMD cannot partition a Mosaic
    kernel, so accepting such a plan raises a typed error instead of
    gathering the batch onto every chip."""
    from repro.core.ditto.plan import require_native_lowering
    from repro.kernels import ShardedLoweringUnsupported
    from repro.serve import ServeSession

    native = DittoPlan(interpret=False, mesh_devices=4)
    with pytest.raises(ShardedLoweringUnsupported, match="mesh_devices=4"):
        require_native_lowering(native)
    with pytest.raises(ShardedLoweringUnsupported):
        require_native_lowering(PlanSchedule(native, [(0, native.steps, {})]))
    with pytest.raises(ShardedLoweringUnsupported):  # refused before params are placed
        ServeSession(None, None, None, native)
    # one device per dispatch, or the interpreter, partitions fine
    require_native_lowering(native.replace(mesh_devices=1))
    require_native_lowering(native.replace(interpret=True))
    require_native_lowering(DittoPlan(interpret=False))
    assert ServeMesh(1).plan_for(DittoPlan(interpret=False)).mesh_sig() == (1, "data")


def test_mesh_sig_is_trace_identity():
    base = DittoPlan(collect_stats=False)
    meshed = base.replace(mesh_devices=2)
    assert base.cache_sig() != meshed.cache_sig()
    # the sig's mesh slot is exactly mesh_sig() — RunnerKey.mesh reads it
    assert base.cache_sig()[5] is None
    assert meshed.cache_sig()[5] == (2, "data")
    # distinct widths and axes are distinct identities
    assert meshed.cache_sig() != base.replace(mesh_devices=4).cache_sig()
    assert (meshed.cache_sig()
            != base.replace(mesh_devices=2, mesh_axis="x").cache_sig())
    # a schedule's segments inherit the base's mesh sig
    sched = PlanSchedule(meshed.replace(steps=12),
                         [(0, 6, {}), (6, 12, dict(low_bits=4))])
    assert sched.mesh_sig() == (2, "data")
    for _, _, seg in sched.segment_plans():
        assert seg.cache_sig()[5] == (2, "data")


def test_mesh_field_tuples_disjoint():
    """The static partition the lint rule enforces, restated as data: sig
    fields and scheduler-policy fields never overlap."""
    assert set(MESH_SIG_FIELDS) == {"mesh_devices", "mesh_axis"}
    assert not set(MESH_SIG_FIELDS) & set(MESH_POLICY_FIELDS)
    # policy knobs live on ServeMesh, not the plan: stamping a plan must
    # not smuggle them into plan fields
    stamped = ServeMesh(1).plan_for(DittoPlan())
    for name in MESH_POLICY_FIELDS:
        assert not hasattr(stamped, name)


# ------------------------------------------------------------- ServeMesh
def test_serve_mesh_validation():
    with pytest.raises(ValueError, match="power of two"):
        ServeMesh(3, dp=3)
    with pytest.raises(ValueError, match="multiple"):
        ServeMesh(3, dp=2)
    with pytest.raises(ValueError, match="identifier"):
        ServeMesh(1, axis="bad axis")
    with pytest.raises(ValueError, match="steal_min_rows"):
        ServeMesh(1, steal_min_rows=0)
    with pytest.raises(ValueError, match="xla_force_host_platform"):
        ServeMesh(4096)  # more devices than any host exposes


def test_serve_mesh_identity_and_stamping():
    m = ServeMesh(1, dp=1, axis="data")
    assert m.n_shards == 1
    assert m.signature() == (1, "data")
    stamped = m.plan_for(PLAN)
    assert stamped.mesh_sig() == (1, "data")
    assert stamped.cache_sig() != PLAN.cache_sig()
    sched = PlanSchedule(PLAN.replace(steps=12), [(0, 12, {})])
    assert m.plan_for(sched).mesh_sig() == (1, "data")
    # concrete submesh: right devices, right axis
    mesh = m.shard_mesh(0)
    assert mesh.axis_names == ("data",) and mesh.devices.size == 1
    with pytest.raises(ValueError, match="shard"):
        m.shard_mesh(1)


def test_group_key_separates_mesh_plans():
    plain = PLAN.normalized()
    stamped = ServeMesh(1).plan_for(PLAN).normalized()
    assert (ServeScheduler._group_key(plain)
            != ServeScheduler._group_key(stamped))


# ---------------------------------------- routing + stealing (white box)
class _ShardSession:
    """Duck-typed per-shard session (x -> 2x): records which shard served
    each batch, and carries the counter attributes mesh-mode stats() sums."""

    def __init__(self, plan, shard):
        import threading

        self.plan = plan
        self.shard = shard
        self.calls = []
        self.batches_served = 0
        self.requests_served = 0
        self.watchdog_events = 0
        self._stats_lock = threading.Lock()

    def serve(self, x, labels, plan=None):
        plan = self.plan if plan is None else plan
        self.calls.append((x.shape[0], plan))
        self.batches_served += 1
        b = x.shape[0]
        sample = x * 2.0
        return ServeResult(sample=sample, chunks=[ChunkResult(
            sample=sample, records=[], engine=None, batch=b,
            bucket=bucket_for(b, max_batch=plan.max_batch),
            wall_s=0.0, traces_delta=0)])

    def stats(self):
        return {}


def _mesh_fake_scheduler(n_shards=2, steal=True, steal_min_rows=1, **kw):
    """A scheduler rewired onto fake per-shard sessions: the full mesh
    routing/steal policy, no devices, fully deterministic via poll()."""
    sessions = [_ShardSession(PLAN, k) for k in range(n_shards)]
    s = ServeScheduler.from_session(sessions[0], **kw)
    s.mesh = types.SimpleNamespace(
        n_devices=n_shards, dp=1, axis="data", steal=steal,
        steal_min_rows=steal_min_rows, n_shards=n_shards,
        plan_for=lambda p: p)
    s._sessions = sessions
    s._n_shards = n_shards
    s._shard_dispatches = [0] * n_shards
    s._shard_rows = [0] * n_shards
    s._shard_inflight = [0] * n_shards
    return s, sessions


def _req(b, seed=0):
    x = jnp.arange(b * 4, dtype=jnp.float32).reshape(b, 4) + 100 * seed
    return x, None


def test_new_groups_route_least_loaded():
    s, _ = _mesh_fake_scheduler(n_shards=2, eager=False)
    s.submit(*_req(2), plan=PLAN)
    s.submit(*_req(2), plan=PLAN.replace(steps=5))
    shards = sorted(g.shard for g in s._groups.values())
    assert shards == [0, 1]  # spread, not piled on shard 0
    s.close(drain=False)


def test_steal_only_from_busy_owner():
    # a deadline-due partial bucket (sync eager submit would dispatch a
    # full one immediately): due work the policy wants served NOW
    s, sessions = _mesh_fake_scheduler(n_shards=2)
    s.submit(*_req(3), deadline_ms=1.0)  # group owned by shard 0
    # owner idle: sibling must NOT steal — the owner takes its own work
    assert s.poll(shard=1) == 0
    # owner mid-dispatch: the same due rows are stolen and served on the
    # thief's OWN session
    s._shard_inflight[0] = 1
    assert s.poll(shard=1) == 3
    s._shard_inflight[0] = 0
    st = s.stats()
    assert st["triggers"]["steal"] == 1
    assert st["mesh"]["steals"] == 1 and st["mesh"]["stolen_rows"] == 3
    assert st["mesh"]["shard_dispatches"] == [0, 1]
    assert sessions[1].calls and not sessions[0].calls
    s.close(drain=False)


def test_steal_respects_gates():
    # steal=False: never steals even from a busy owner
    s, _ = _mesh_fake_scheduler(n_shards=2, steal=False)
    s.submit(*_req(3), deadline_ms=1.0)
    s._shard_inflight[0] = 1
    assert s.poll(shard=1) == 0
    s._shard_inflight[0] = 0
    s.close(drain=False)
    # steal_min_rows above the queue depth: too little queued to steal
    s, _ = _mesh_fake_scheduler(n_shards=2, steal_min_rows=8)
    s.submit(*_req(3), deadline_ms=1.0)
    s._shard_inflight[0] = 1
    assert s.poll(shard=1) == 0
    s._shard_inflight[0] = 0
    # the owner itself still serves its due work normally
    assert s.poll(shard=0) == 3
    assert s.stats()["triggers"]["deadline"] == 1
    s.close(drain=False)


def test_mesh_stats_shape():
    s, _ = _mesh_fake_scheduler(n_shards=2)
    s.submit(*_req(4))  # full bucket: sync eager submit dispatches on shard 0
    st = s.stats()
    assert st["triggers"]["full"] == 1
    assert st["mesh"]["n_shards"] == 2 and st["mesh"]["dp"] == 1
    assert st["mesh"]["shard_dispatches"] == [1, 0]
    assert st["mesh"]["shard_rows"] == [4, 0]
    assert st["batches"] == 1  # summed across per-shard sessions
    s.close(drain=False)


# ------------------------------------------------- 8-device subprocesses
_CHILD_PREAMBLE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import sys
sys.path.insert(0, "src")
import jax, jax.numpy as jnp, numpy as np
from repro.core import diffusion
from repro.core.ditto import DittoPlan
from repro.nn import dit as dit_mod
from repro.serve import (CompiledRunnerCache, Fault, FaultInjector,
                         ServeMesh, ServeScheduler, ServeSession, inject)

CFG = dit_mod.DiTCfg(d_model=64, n_layers=2, n_heads=2, patch=2,
                     in_channels=4, input_size=8, n_classes=4)
PLAN = DittoPlan(steps=3, policy="diff", max_batch=4, collect_stats=False)
params = dit_mod.init(jax.random.PRNGKey(0), CFG)
sched = diffusion.cosine_schedule(100)

def req(b, seed):
    x = jax.random.normal(jax.random.PRNGKey(100 + seed),
                          (b, CFG.input_size, CFG.input_size, CFG.in_channels))
    return x, (jnp.arange(b) + seed) % CFG.n_classes

solo = ServeSession(params, CFG, sched, PLAN)
def solo_ref(b, seed):
    x, lab = req(b, seed)
    return np.asarray(solo.serve(x, lab).sample)
"""


def _run_child(body, timeout=540):
    out = subprocess.run([sys.executable, "-c", _CHILD_PREAMBLE + body],
                         capture_output=True, text=True, cwd=str(REPO),
                         timeout=timeout)
    assert "MESH_OK" in out.stdout, (out.stdout[-2000:], out.stderr[-4000:])


def test_mesh_bit_identity_and_trace_sharing_subprocess():
    """8 devices: dp=8 whole-mesh serving and dp=1 shard serving are both
    bit-identical per sample to solo serving; all shards of one mesh share
    one trace set in one cache; an unsharded plan lands on separate keys
    (never a collision); warmup AOT-compiles once per mesh signature."""
    _run_child("""
assert len(jax.devices()) == 8, jax.devices()

# dp=8: one shard spanning the whole mesh, batch axis split 8 ways
m8 = ServeMesh(8, dp=8)
s8 = ServeScheduler(params, CFG, sched, PLAN.replace(max_batch=8), mesh=m8)
reqs = [(8, 1), (8, 2)]
tickets = [s8.submit(*req(b, seed)) for b, seed in reqs]
s8.flush()
for t, (b, seed) in zip(tickets, reqs):
    assert (np.asarray(t.result()) == solo_ref(b, seed)).all(), "dp8 not bit-identical"
s8.close()

# dp=1: 8 single-device shards sharing ONE cache + ONE trace set
cache = CompiledRunnerCache()
m1 = ServeMesh(8, dp=1)
s1 = ServeScheduler(params, CFG, sched, PLAN, cache=cache, mesh=m1)
w1 = s1.warmup()
assert w1["aot_compiled"] > 0
w2 = s1.warmup()
assert w2["aot_compiled"] == 0 and w2["traces"] == 0, (w1, w2)  # once per mesh sig
keys_warm = set(cache.trace_counts)
assert all(k.mesh == (1, "data") for k in keys_warm)

reqs = [(3, 3), (4, 4), (2, 5), (4, 6)]
tickets = [s1.submit(*req(b, seed)) for b, seed in reqs]
s1.flush()
for t, (b, seed) in zip(tickets, reqs):
    assert (np.asarray(t.result()) == solo_ref(b, seed)).all(), "dp1 not bit-identical"
st = s1.stats()
assert sum(st["mesh"]["shard_dispatches"]) == st["dispatches"]
# serving on ANY shard minted no key beyond the warmed (sig, bucket) set
assert set(cache.trace_counts) == keys_warm, (keys_warm, set(cache.trace_counts))
s1.close()

# an unsharded session on the SAME cache: new keys, zero collisions
un = ServeSession(params, CFG, sched, PLAN, cache=cache)
x, lab = req(4, 7)
assert (np.asarray(un.serve(x, lab).sample) == solo_ref(4, 7)).all()
new_keys = set(cache.trace_counts) - keys_warm
assert new_keys and all(k.mesh is None for k in new_keys)
print("MESH_OK")
""")


def test_mesh_work_stealing_skewed_stream_subprocess():
    """Async 8-shard mesh under a skewed arrival stream (every request in
    one behavioral group -> one owner shard): siblings steal the owner's
    due buckets while it is mid-dispatch, and every stolen row is still
    bit-identical to solo serving."""
    _run_child("""
m = ServeMesh(8, dp=1, steal=True)
s = ServeScheduler(params, CFG, sched, PLAN, mesh=m, async_mode=True,
                   dispatch_interval_ms=5.0)
reqs = [(4, seed) for seed in range(12)]  # 12 full buckets, one group
tickets = [s.submit(*req(b, seed)) for b, seed in reqs]
s.flush()
for t, (b, seed) in zip(tickets, reqs):
    assert (np.asarray(t.result()) == solo_ref(b, seed)).all(), "stolen rows differ"
st = s.stats()
assert st["completed"] == len(reqs) and st["failed"] == 0
assert st["mesh"]["steals"] >= 1, st["mesh"]  # siblings picked up due work
# the lone group is owned by shard 0, so every row a sibling served was
# by definition stolen
owner = next(iter(s._groups.values())).shard if s._groups else 0
non_owner = sum(r for k, r in enumerate(st["mesh"]["shard_rows"]) if k != owner)
assert st["mesh"]["stolen_rows"] == non_owner, st["mesh"]
s.close()
print("MESH_OK")
""")


def test_mesh_fault_on_one_shard_recovers_via_ladder_subprocess():
    """A fault injected into one shard's dispatch walks that dispatch's
    degradation ladder (PR 9) and recovers bit-identically — siblings'
    dispatches are untouched and the scheduler never dies."""
    _run_child("""
mk = lambda steps: PLAN.replace(steps=steps, max_retries=1,
                                fallbacks=(dict(low_bits=4),))
plans = [mk(3), mk(4), mk(5)]  # 3 behavioral groups -> 3 distinct shards
m = ServeMesh(8, dp=1, steal=False)  # pin each group to its owner shard
s = ServeScheduler(params, CFG, sched, PLAN, mesh=m)
# sync eager submits dispatch in submission order; arrival 1 = the SECOND
# group's dispatch (its own shard): error once, then ladder-recover
with inject(FaultInjector([Fault("session.serve", 1, "error")])) as inj:
    tickets = [s.submit(*req(4, seed), plan=p) for seed, p in enumerate(plans)]
    s.flush()
assert len(inj.fired) == 1
for seed, (t, p) in enumerate(zip(tickets, plans)):
    x, lab = req(4, seed)
    want = np.asarray(solo.serve(x, lab, plan=p).sample)
    assert (np.asarray(t.result()) == want).all(), "recovery not bit-identical"
st = s.stats()
assert st["completed"] == 3 and st["failed"] == 0 and not st["died"]
assert st["retries"] == 1 and st["fallback_dispatches"] == 1
# exactly the faulted shard's dispatch walked the ladder; siblings served
# their group plan untouched
assert tickets[1].served_with.low_bits == 4
assert tickets[0].served_with.low_bits != 4
assert tickets[2].served_with.low_bits != 4
assert sorted(st["mesh"]["shard_dispatches"], reverse=True)[:3] == [1, 1, 1]
s.close()
print("MESH_OK")
""")
