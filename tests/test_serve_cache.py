"""Persistent compiled serving runtime: runner cache + batch buckets.

Contracts under test (docs/architecture.md §serving):

  * CompiledRunnerCache traces each runner ONCE per (mode signature,
    plan.cache_sig(), bucket): N same-bucket batches -> exactly one XLA
    trace, asserted via the cache's trace counter (a trace-time side
    effect, not a wall-clock heuristic).
  * Batch-bucket padding is bit-exact: padding replicates real rows, and
    activation calibration is per sample, so the bucketed sample sliced
    to the true batch equals the unbucketed compiled sample bit-for-bit —
    for ragged batch sizes off the bucket grid.
  * ServeSession chunks oversized requests and reports cache stats.
  * The deprecated splatted-kwarg call style maps onto the SAME RunnerKey
    as the plan style, so migrating callers share traces with
    un-migrated ones (no trace duplication during migration).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import diffusion
from repro.core.ditto import DittoPlan
from repro.nn import dit as dit_mod
from repro.serve import CompiledRunnerCache, ServeSession, bucket_for, pad_batch
from repro.sim import harness

CFG = dit_mod.DiTCfg(d_model=64, n_layers=2, n_heads=2, patch=2, in_channels=4,
                     input_size=8, n_classes=4)


@pytest.fixture(scope="module")
def setup():
    key = jax.random.PRNGKey(0)
    params = dit_mod.init(key, CFG)
    sched = diffusion.cosine_schedule(100)
    return params, sched


def _request(b, seed=1):
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (b, CFG.input_size, CFG.input_size, CFG.in_channels))
    labels = jnp.arange(b) % CFG.n_classes
    return x, labels


# --------------------------------------------------------------- bucketing
def test_bucket_for_rounds_to_pow2():
    assert [bucket_for(n, max_batch=16) for n in (1, 2, 3, 4, 5, 9, 16)] == \
        [1, 2, 4, 4, 8, 16, 16]
    with pytest.raises(ValueError):
        bucket_for(0)
    with pytest.raises(ValueError):
        bucket_for(17, max_batch=16)


def test_bucket_for_rejects_non_pow2_max_batch():
    """Satellite regression: min(b, max_batch) used to return the
    NON-CANONICAL bucket 6 for (5, max_batch=6), silently fragmenting the
    runner cache past log2(max_batch)+1 entries. Now both directions are
    enforced: every returned bucket is a power of two, and a non-pow2 cap
    is rejected outright."""
    with pytest.raises(ValueError):
        bucket_for(5, max_batch=6)
    with pytest.raises(ValueError):
        bucket_for(1, max_batch=12)
    # canonical ladder only — never a bucket between pow2 points
    for n in range(1, 17):
        b = bucket_for(n, max_batch=16)
        assert b & (b - 1) == 0 and b >= n


def test_pad_batch_replicates_rows():
    x, labels = _request(3)
    xp, lp = pad_batch(x, labels, 8)
    assert xp.shape[0] == 8 and lp.shape[0] == 8
    np.testing.assert_array_equal(np.asarray(xp[:3]), np.asarray(x))
    # cyclic replication: padded rows are exact copies of real rows, so no
    # per-sample calibration scale can change
    for i in range(3, 8):
        np.testing.assert_array_equal(np.asarray(xp[i]), np.asarray(x[i % 3]))
        assert int(lp[i]) == int(labels[i % 3])
    assert float(jnp.max(jnp.abs(xp))) == float(jnp.max(jnp.abs(x)))
    xp2, lp2 = pad_batch(x, None, 4)
    assert lp2 is None and xp2.shape[0] == 4
    with pytest.raises(ValueError):
        pad_batch(x, labels, 2)


# ------------------------------------------------------------ trace counts
@pytest.mark.slow
def test_same_bucket_batches_trace_once(setup):
    """N=4 batches across 2 buckets -> exactly 2 traces (one per bucket);
    later same-bucket batches are pure cache hits."""
    params, sched = setup
    cache = CompiledRunnerCache()
    plan = DittoPlan(steps=3, policy="diff", max_batch=4, collect_stats=False)
    sess = ServeSession(params, CFG, sched, plan, cache=cache)
    sizes = [4, 3, 4, 2]  # buckets 4, 4, 4, 2
    results = [sess.serve(*_request(b, seed=10 + i)) for i, b in enumerate(sizes)]
    for b, r in zip(sizes, results):
        assert r.sample.shape[0] == b
        assert not bool(jnp.isnan(r.sample).any())
    assert len(cache) == 2, cache.stats()
    assert cache.n_traces == 2, cache.stats()
    assert all(c == 1 for c in cache.trace_counts.values()), cache.trace_counts
    # first batch of each bucket misses, the other two hit
    assert cache.misses == 2 and cache.hits == 2, cache.stats()
    assert results[1].traces_delta == 0 and results[2].traces_delta == 0
    # cached runner output == a fresh uncached run of the same request
    x, labels = _request(4, seed=12)
    _, fresh, _ = harness.serve_records(params, CFG, sched, x, labels, plan)
    np.testing.assert_array_equal(np.asarray(results[2].sample), np.asarray(fresh))


# ------------------------------------------------------------ bit-identity
@pytest.mark.slow
@pytest.mark.parametrize("b", [1, 3])
def test_bucket_padding_bitidentical(setup, b):
    """Ragged batch served at bucket 4 == the unbucketed compiled path,
    bit-for-bit in the fp32 sample."""
    params, sched = setup
    x, labels = _request(b, seed=33)
    plan = DittoPlan(steps=4, policy="defo")
    _, plain, _ = harness.serve_records(params, CFG, sched, x, labels, plan)
    _, bucketed, eng = harness.serve_records(params, CFG, sched, x, labels, plan,
                                             bucket=4)
    assert bucketed.shape[0] == b
    np.testing.assert_array_equal(np.asarray(bucketed), np.asarray(plain))
    # records are collected at bucket scale
    assert all(r["t"] % 4 == 0 for r in eng.records if not r["attention"])


# ----------------------------------------------------------- serve edges
@pytest.mark.slow
def test_batch_one_request_no_padding(setup):
    """batch=1 lands in bucket 1: NO replication padding anywhere, and the
    session result equals the direct unbucketed compiled run bit-for-bit."""
    params, sched = setup
    plan = DittoPlan(steps=3, policy="diff", max_batch=4, collect_stats=False)
    sess = ServeSession(params, CFG, sched, plan)
    x, labels = _request(1, seed=21)
    res = sess.serve(x, labels)
    assert res.sample.shape[0] == 1
    assert [c.bucket for c in res.chunks] == [1] and res.chunks[0].batch == 1
    assert res.pad_rows == 0
    _, plain, _ = harness.serve_records(params, CFG, sched, x, labels, plan)
    np.testing.assert_array_equal(np.asarray(res.sample), np.asarray(plain))


@pytest.mark.slow
def test_exact_bucket_size_request(setup):
    """A request already ON the bucket grid (b == bucket_for(b)) pads
    nothing — pad_batch returns the batch unchanged — and serves exactly."""
    params, sched = setup
    b = 4
    assert bucket_for(b, max_batch=4) == b
    x, labels = _request(b, seed=22)
    xp, lp = pad_batch(x, labels, b)
    assert xp is x and lp is labels  # identity, not a copy
    plan = DittoPlan(steps=3, policy="diff", max_batch=4, collect_stats=False)
    sess = ServeSession(params, CFG, sched, plan)
    res = sess.serve(x, labels)
    assert res.sample.shape[0] == b
    assert [c.bucket for c in res.chunks] == [b]
    _, plain, _ = harness.serve_records(params, CFG, sched, x, labels, plan)
    np.testing.assert_array_equal(np.asarray(res.sample), np.asarray(plain))


def test_cache_key_misses_when_only_low_bits_differs():
    """int8 and int4 runners lower different kernel bodies: a shared cache
    must key them apart even when every other component agrees."""
    cache = CompiledRunnerCache()
    modes = {"l1": "diff"}
    p8 = DittoPlan(steps=4, low_bits=8)
    p4 = DittoPlan(steps=4, low_bits=4)
    f8 = cache.step_for(CFG, modes, p8, bucket=4)
    f4 = cache.step_for(CFG, modes, p4, bucket=4)
    assert f8 is not f4
    assert len(cache) == 2 and cache.misses == 2 and cache.hits == 0
    k8 = cache.key_for(CFG, modes, p8, bucket=4)
    k4 = cache.key_for(CFG, modes, p4, bucket=4)
    assert k8 != k4 and k8.low_bits == 8 and k4.low_bits == 4
    assert k8 == cache.key_for(CFG, modes, DittoPlan(steps=4), bucket=4)  # 8 is the default
    # and a repeat int4 request is a pure hit
    assert cache.step_for(CFG, modes, p4, bucket=4) is f4
    assert cache.hits == 1


def test_dit_and_mmdit_configs_of_equal_sizes_never_share_a_key():
    """The runner key's config signature names the config class: a DiT and
    an MMDiT with the same sizes trace different forwards."""
    from repro.nn import mmdit

    shared = dict(d_model=64, n_layers=2, n_heads=2, patch=2, in_channels=4, input_size=8)
    dit, mm = dit_mod.DiTCfg(**shared), mmdit.MMDiTCfg(**shared)
    cache = CompiledRunnerCache()
    modes = {"final.out": "act"}
    plan = DittoPlan(steps=4)
    assert cache.key_for(dit, modes, plan, bucket=1) != cache.key_for(mm, modes, plan, bucket=1)
    assert cache.step_for(dit, modes, plan, bucket=1) is not cache.step_for(mm, modes, plan,
                                                                            bucket=1)


def test_plan_only_loop_fields_share_a_key():
    """sampler/policy/compiled/max_batch shape the loop AROUND the step,
    not the step itself — plans differing only there must share a trace."""
    cache = CompiledRunnerCache()
    modes = {"l1": "diff"}
    base = DittoPlan(steps=4)
    for other in (base.replace(sampler="plms"), base.replace(policy="diff"),
                  base.replace(compiled=False), base.replace(max_batch=2)):
        assert cache.key_for(CFG, modes, base, bucket=4) == \
            cache.key_for(CFG, modes, other, bucket=4), other


def test_legacy_kwargs_hit_the_same_runner_key():
    """Migration safety: the deprecated splatted-kwarg style and the plan
    style land on the SAME RunnerKey (and therefore the same cached
    runner) — old and new callers never duplicate traces."""
    from repro.core.ditto import plan as plan_mod

    plan_mod.reset_deprecation_warnings()  # warn-once: make this site fresh
    cache = CompiledRunnerCache()
    modes = {"l1": "diff", "l2": "act"}
    with pytest.warns(DeprecationWarning):
        k_old = cache.key_for(CFG, modes, low_bits=4, block=64, collect_stats=False,
                              extra=(6, 8))
    k_new = cache.key_for(
        CFG, modes, DittoPlan(steps=6, low_bits=4, block=64, collect_stats=False),
        bucket=8)
    assert k_old == k_new
    # the cached STEP is shared too, not just the key
    f_old = cache.step_for(CFG, modes, low_bits=4, block=64, collect_stats=False,
                           extra=(6, 8))
    f_new = cache.step_for(
        CFG, modes, DittoPlan(steps=6, low_bits=4, block=64, collect_stats=False),
        bucket=8)
    assert f_old is f_new
    assert cache.stats() == {"runners": 1, "traces": 0, "hits": 1, "misses": 1,
                             "aot_hits": 0, "aot_misses": 0}


@pytest.mark.slow
def test_int4_serve_bitidentical(setup):
    """ServeSession(low_bits=4) == ServeSession(low_bits=8) bit-for-bit in
    the fp32 sample (the class-1 pack/unpack round-trip is exact)."""
    params, sched = setup
    x, labels = _request(3, seed=44)
    out = {}
    for lb in (8, 4):
        plan = DittoPlan(steps=4, policy="diff", max_batch=4, collect_stats=False,
                         low_bits=lb)
        sess = ServeSession(params, CFG, sched, plan)
        out[lb] = sess.serve(x, labels).sample
    np.testing.assert_array_equal(np.asarray(out[4]), np.asarray(out[8]))


# ----------------------------------------------------- cache bookkeeping
def test_cache_key_hit_miss_bookkeeping():
    """Key construction and hit/miss accounting without paying any XLA
    trace (the jitted step is never called): same (cfg, modes, plan,
    bucket) -> one entry + a hit; different bucket/low_bits/modes ->
    distinct entries; different steps shares the entry (steps is not a
    trace identity — the same step just runs more times)."""
    cache = CompiledRunnerCache()
    modes = {"l1": "diff", "l2": "act"}
    plan = DittoPlan(steps=4)
    f1 = cache.step_for(CFG, modes, plan, bucket=8)
    f2 = cache.step_for(CFG, dict(reversed(list(modes.items()))), plan, bucket=8)
    assert f1 is f2  # mode signature is order-insensitive
    assert cache.stats() == {"runners": 1, "traces": 0, "hits": 1, "misses": 1,
                             "aot_hits": 0, "aot_misses": 0}
    f3 = cache.step_for(CFG, modes, plan.replace(steps=8), bucket=8)
    assert f3 is f1  # steps is loop-level: same trace, a cache HIT
    assert cache.stats() == {"runners": 1, "traces": 0, "hits": 2, "misses": 1,
                             "aot_hits": 0, "aot_misses": 0}
    cache.step_for(CFG, modes, plan, bucket=4)  # different bucket
    cache.step_for(CFG, modes, plan.replace(low_bits=4), bucket=8)  # different lowering
    cache.step_for(CFG, {"l1": "act", "l2": "act"}, plan, bucket=8)  # different modes
    assert len(cache) == 4 and cache.misses == 4
    k1 = cache.key_for(CFG, modes, plan, bucket=8)
    k2 = cache.key_for(CFG, modes, plan, bucket=4)
    assert k1 != k2 and k1.mode_sig == k2.mode_sig and k1.plan_sig == k2.plan_sig
    cache.clear()
    assert cache.stats() == {"runners": 0, "traces": 0, "hits": 0, "misses": 0,
                             "aot_hits": 0, "aot_misses": 0}


# ---------------------------------------------------------------- session
@pytest.mark.slow
def test_session_chunks_oversized_requests(setup):
    params, sched = setup
    plan = DittoPlan(steps=3, policy="act", max_batch=2, collect_stats=False)
    sess = ServeSession(params, CFG, sched, plan)
    x, labels = _request(5, seed=5)
    res = sess.serve(x, labels)
    assert res.sample.shape[0] == 5
    assert [c.batch for c in res.chunks] == [2, 2, 1]
    assert [c.bucket for c in res.chunks] == [2, 2, 1]
    st = sess.stats()
    assert st["batches"] == 1 and st["requests"] == 5
    # chunk 2 reuses chunk 1's bucket-2 runner
    assert st["runners"] == 2 and st["traces"] == 2


@pytest.mark.slow
def test_eager_chunks_report_bucket_none(setup):
    """compiled=False chunks run unbucketed: ChunkResult.bucket is None
    (not the raw batch size masquerading as a bucket) and no pad rows or
    trace deltas are claimed."""
    params, sched = setup
    plan = DittoPlan(steps=3, policy="act", compiled=False, max_batch=4,
                     collect_stats=False)
    sess = ServeSession(params, CFG, sched, plan)
    x, labels = _request(3, seed=7)
    res = sess.serve(x, labels)
    assert res.sample.shape[0] == 3
    assert [c.bucket for c in res.chunks] == [None]
    assert res.pad_rows == 0 and res.traces_delta == 0
    assert len(sess.cache) == 0  # eager serving never touches the runner cache
