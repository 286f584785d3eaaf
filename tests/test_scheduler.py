"""ServeScheduler: continuous batching across request submissions.

Contracts under test (docs/architecture.md §scheduler):

  * coalescing: N ragged submissions dispatch as full power-of-two
    buckets with FEWER pad rows than N independent serve() calls (the
    3+3+2 stream of the motivating example dispatches as 4+4 with zero
    padding);
  * bit-identity: every ticket's rows equal a per-request serve() of the
    same request — the per-sample calibration invariant
    (quant.sample_scale) makes batch composition invisible;
  * per-request plan overrides share one runner cache but never share a
    trace when their plans lower differently;
  * requests split across dispatches reassemble in row order;
  * eager dispatch fires exactly when a plan group fills a bucket;
  * grouping is behavioral (cache_sig()-based): sig-equal plans and
    PlanSchedules constructed separately coalesce, behaviorally
    different schedules never batch together.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import diffusion
from repro.core.ditto import DittoPlan, PlanSchedule, quant
from repro.nn import dit as dit_mod
from repro.serve import CompiledRunnerCache, ServeScheduler, ServeSession

CFG = dit_mod.DiTCfg(d_model=64, n_layers=2, n_heads=2, patch=2, in_channels=4,
                     input_size=8, n_classes=4)
PLAN = DittoPlan(steps=3, policy="diff", max_batch=4, collect_stats=False)


@pytest.fixture(scope="module")
def setup():
    key = jax.random.PRNGKey(0)
    params = dit_mod.init(key, CFG)
    sched = diffusion.cosine_schedule(100)
    return params, sched


def _request(b, seed):
    key = jax.random.PRNGKey(100 + seed)
    x = jax.random.normal(key, (b, CFG.input_size, CFG.input_size, CFG.in_channels))
    labels = (jnp.arange(b) + seed) % CFG.n_classes
    return x, labels


# ------------------------------------------------------------- unit level
def test_sample_scale_is_per_sample():
    """The enabling invariant, in isolation: each row group's scale is a
    function of its own elements only, so concatenating requests changes
    no scale."""
    key = jax.random.PRNGKey(3)
    a = jax.random.normal(key, (6, 16))  # 3 samples x 2 rows
    b = jax.random.normal(jax.random.fold_in(key, 1), (4, 16)) * 50.0  # huge outlier
    sa = quant.sample_scale(a, 3)
    sab = quant.sample_scale(jnp.concatenate([a, b]), 5)
    np.testing.assert_array_equal(np.asarray(sa), np.asarray(sab[:6]))
    # within a sample the scale is constant; across samples it varies
    assert float(sa[0, 0]) == float(sa[1, 0])
    with pytest.raises(ValueError):
        quant.sample_scale(a, 4)  # 6 rows don't group into 4 samples


def test_pending_queue_accounting(setup):
    params, sched = setup
    s = ServeScheduler(params, CFG, sched, PLAN, eager=False)
    t1 = s.submit(*_request(3, 0))
    t2 = s.submit(*_request(2, 1))
    st = s.stats()
    assert st["submitted"] == 2 and st["submitted_rows"] == 5
    assert st["queued_rows"] == 5 and st["dispatches"] == 0
    assert not t1.done and not t2.done
    assert s.naive_pad_rows() == (4 - 3) + 0  # bucket_for(3)=4, bucket_for(2)=2


# ------------------------------------------------------------- coalescing
@pytest.mark.slow
def test_coalescing_reduces_pad_rows_bitidentically(setup):
    """The ISSUE's motivating stream: 3+3+2 dispatches as two FULL
    bucket-4 batches (0 pad rows) instead of 4+4+2 (2 pad rows), and every
    request's rows are bit-identical to its own independent serve()."""
    params, sched = setup
    sizes = [3, 3, 2]
    reqs = [_request(b, i) for i, b in enumerate(sizes)]
    sess = ServeSession(params, CFG, sched, PLAN)  # per-request baseline
    refs = [sess.serve(x, l).sample for x, l in reqs]

    s = ServeScheduler(params, CFG, sched, PLAN)
    tickets = [s.submit(x, l) for x, l in reqs]
    s.flush()
    assert all(t.done for t in tickets)
    st = s.stats()
    assert st["dispatches"] == 2 and st["dispatched_rows"] == 8
    assert s.pad_rows == 0 and s.naive_pad_rows() == 2
    assert s.pad_rows < s.naive_pad_rows()
    for t, ref, b in zip(tickets, refs, sizes):
        got = t.result()
        assert got.shape[0] == b
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@pytest.mark.slow
def test_request_split_across_dispatches(setup):
    """A 6-row request under max_batch=4 spans two dispatch batches (4+2
    with a following 2-row request coalesced into the tail); its ticket
    reassembles the rows in order, bit-identical to a lone serve()."""
    params, sched = setup
    x6, l6 = _request(6, 7)
    x2, l2 = _request(2, 8)
    sess = ServeSession(params, CFG, sched, PLAN)
    ref6 = sess.serve(x6, l6).sample
    ref2 = sess.serve(x2, l2).sample

    # retain=True: ticket/dispatch introspection below needs the opt-in
    # record keeping (tickets retire to counters by default)
    s = ServeScheduler(params, CFG, sched, PLAN, retain=True)
    t6 = s.submit(x6, l6)  # eager: dispatches rows 0..3 immediately
    assert s.stats()["dispatches"] == 1 and not t6.done
    t2 = s.submit(x2, l2)  # 2 leftover + 2 new = full bucket 4
    assert s.stats()["dispatches"] == 2
    assert t6.done and t2.done and s.pad_rows == 0
    np.testing.assert_array_equal(np.asarray(t6.result()), np.asarray(ref6))
    np.testing.assert_array_equal(np.asarray(t2.result()), np.asarray(ref2))
    assert len(t6.results) == 2  # served by two dispatches


@pytest.mark.slow
def test_mixed_plans_never_share_a_trace(setup):
    """Per-request plan overrides: int8 and int4 submissions coexist in
    one scheduler and one cache, group separately, and compile separate
    runners (the plan is the trace identity) — while same-plan
    submissions still coalesce."""
    params, sched = setup
    cache = CompiledRunnerCache()
    p8 = PLAN
    p4 = PLAN.replace(low_bits=4)
    s = ServeScheduler(params, CFG, sched, p8, cache=cache)
    t8a = s.submit(*_request(2, 20))
    t4 = s.submit(*_request(2, 21), plan=p4)
    t8b = s.submit(*_request(2, 22))  # coalesces with t8a into bucket 4
    assert s.stats()["plan_groups"] == 2
    assert s.stats()["dispatches"] == 1  # the p8 group filled its bucket
    s.flush()
    assert all(t.done for t in (t8a, t4, t8b))
    keys = list(cache.trace_counts)
    assert len(cache) == 2, cache.stats()
    assert {k.low_bits for k in keys} == {4, 8}
    # results match per-request serves under the matching plan
    sess = ServeSession(params, CFG, sched, p8, cache=CompiledRunnerCache())
    for t, (b, seed), plan in ((t8a, (2, 20), p8), (t4, (2, 21), p4), (t8b, (2, 22), p8)):
        ref = sess.serve(*_request(b, seed), plan=plan).sample
        np.testing.assert_array_equal(np.asarray(t.result()), np.asarray(ref))


@pytest.mark.slow
def test_result_triggers_flush(setup):
    """Ticket.result() on a queued request flushes the scheduler instead
    of deadlocking; the ragged tail is the only padded dispatch."""
    params, sched = setup
    s = ServeScheduler(params, CFG, sched, PLAN)
    t = s.submit(*_request(3, 30))
    assert not t.done and s.stats()["dispatches"] == 0
    out = t.result()  # implicit flush
    assert t.done and out.shape[0] == 3
    assert s.stats()["dispatches"] == 1 and s.pad_rows == 1  # 3 -> bucket 4


def test_submit_rejects_empty_request(setup):
    params, sched = setup
    s = ServeScheduler(params, CFG, sched, PLAN)
    with pytest.raises(ValueError):
        s.submit(jnp.zeros((0, 8, 8, 4)), None)


# ------------------------------------------------------ schedule coalescing
# The grouping key is behavioral (loop fields + normalized per-segment
# cache_sig()s), not plan-object equality: sig-equal plans/schedules
# constructed separately must coalesce, behaviorally different ones must
# never batch together.
SCHED_A = PlanSchedule(PLAN, [(0, 2, {}), (2, 3, dict(low_bits=4))])


def test_equal_schedules_coalesce_into_one_group(setup):
    """Two DIFFERENT schedule objects that normalize identically (one
    spells the int8 prefix as two segments) land in one bucket group."""
    params, sched = setup
    other = PlanSchedule(PLAN, [(0, 1, {}), (1, 2, {}), (2, 3, dict(low_bits=4))])
    assert other is not SCHED_A and other != SCHED_A  # raw objects differ ...
    s = ServeScheduler(params, CFG, sched, PLAN, eager=False)
    s.submit(*_request(2, 40), plan=SCHED_A)
    s.submit(*_request(2, 41), plan=other)
    assert s.stats()["plan_groups"] == 1  # ... but the group key coalesces


def test_constant_schedule_coalesces_with_bare_plan(setup):
    """Satellite-5 regression: grouping by the raw normalized plan object
    would split a constant schedule from its equivalent bare plan (they
    are different types); the cache_sig()-based key coalesces them."""
    params, sched = setup
    const = PlanSchedule(PLAN, [(0, 1, {}), (1, 3, {})])
    s = ServeScheduler(params, CFG, sched, PLAN, eager=False)
    s.submit(*_request(2, 42))  # session default: the bare plan
    s.submit(*_request(2, 43), plan=const)
    assert s.stats()["plan_groups"] == 1


def test_sig_equal_duck_typed_plan_coalesces(setup):
    """Same regression from the other side: a duck-typed plan subclass is
    never equal to a DittoPlan (dataclass eq checks the class), but when
    its loop fields and cache_sig() agree it must share the group."""

    @dataclasses.dataclass(frozen=True)
    class TaggedPlan(DittoPlan):
        tag: str = "client-a"  # not a sig field: behaviorally identical

    params, sched = setup
    tagged = TaggedPlan(**dataclasses.asdict(PLAN))
    assert tagged != PLAN and tagged.cache_sig() == PLAN.cache_sig()
    s = ServeScheduler(params, CFG, sched, PLAN, eager=False)
    s.submit(*_request(2, 44))
    s.submit(*_request(2, 45), plan=tagged)
    assert s.stats()["plan_groups"] == 1


def test_behaviorally_distinct_schedules_split_groups(setup):
    """Schedules differing in any step's lowering (same sigs, different
    boundary) never batch together."""
    params, sched = setup
    later = PlanSchedule(PLAN, [(0, 1, {}), (1, 3, dict(low_bits=4))])
    s = ServeScheduler(params, CFG, sched, PLAN, eager=False)
    s.submit(*_request(2, 46), plan=SCHED_A)
    s.submit(*_request(2, 47), plan=later)
    assert s.stats()["plan_groups"] == 2


@pytest.mark.slow
def test_mixed_schedules_share_cache_but_not_traces(setup):
    """An int8→int4 schedule and a plain int8 plan coexist in one
    scheduler/cache: two groups, one segment loop per (segment sig, loop
    length). The schedule's int8 segment runs one compiled step after the
    eager one, the bare plan two, so the two int8 loops are keyed apart
    by their length."""
    params, sched = setup
    cache = CompiledRunnerCache()
    s = ServeScheduler(params, CFG, sched, PLAN, cache=cache)
    ta = s.submit(*_request(2, 50), plan=SCHED_A)
    t8 = s.submit(*_request(2, 51))
    assert s.stats()["plan_groups"] == 2
    s.flush()
    assert ta.done and t8.done
    keys = list(cache.trace_counts)
    assert {(k.low_bits, k.loop[1]) for k in keys} == {(8, 1), (4, 1), (8, 2)}
    assert len(cache) == 3
    # both tickets bit-identical to solo serves under the matching plan
    sess = ServeSession(params, CFG, sched, PLAN, cache=CompiledRunnerCache())
    for t, seed, plan in ((ta, 50, SCHED_A), (t8, 51, PLAN)):
        ref = sess.serve(*_request(2, seed), plan=plan).sample
        np.testing.assert_array_equal(np.asarray(t.result()), np.asarray(ref))


@pytest.mark.slow
def test_ticket_row_slicing_bit_identical_under_schedules(setup):
    """Ragged requests coalesced under one schedule: every ticket's rows
    (including a request split across dispatches) equal its own solo
    serve under the same schedule."""
    params, sched = setup
    sizes = [3, 3, 2]
    reqs = [_request(b, 60 + i) for i, b in enumerate(sizes)]
    sess = ServeSession(params, CFG, sched, SCHED_A)
    refs = [sess.serve(x, l).sample for x, l in reqs]

    s = ServeScheduler(params, CFG, sched, SCHED_A)
    tickets = [s.submit(x, l) for x, l in reqs]
    s.flush()
    assert s.stats()["dispatches"] == 2 and s.pad_rows == 0
    for t, ref, b in zip(tickets, refs, sizes):
        got = t.result()
        assert got.shape[0] == b
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
