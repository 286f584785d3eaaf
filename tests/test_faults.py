"""Chaos suite: fault injection, the degradation ladder, and the
numerical re-anchor watchdog (docs/architecture.md §fault model).

Contracts under test:

  * injection is deterministic and one-shot: a seeded chaos schedule
    replays identically; each (site, arrival) fires at most once and is
    recorded in ``.fired``;
  * recovery: a failed dispatch retries down the validated fallback
    ladder with bounded backoff; kernel-family recoveries are
    bit-identical to the fault-free sample; exhausting the ladder raises
    a typed :class:`DispatchFailed`;
  * liveness: EVERY ticket terminates (sample or typed error) under any
    seeded fault schedule — a batch-assembly fault fails only the
    covered tickets (the dispatch thread survives), a policy fault kills
    the thread but every ``result()``/``submit()`` gets a typed
    :class:`SchedulerDied`, and ``close(drain=True)`` never deadlocks;
  * watchdog: a non-finite compiled step rolls back and re-runs as a
    full-bit-width re-anchor step; tile-class saturation schedules a
    re-anchor for the next step; all kernel-family plans share ONE
    audited canonical re-anchor trace.

Fast tests run against a fake session; the fake calls
``faults.fire("session.serve")`` itself because the real probe lives in
:meth:`ServeSession.serve`. Real-stack recovery tests are marked slow.
"""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import diffusion
from repro.core.ditto import DittoPlan
from repro.nn import dit as dit_mod
from repro.serve import (CompiledRunnerCache, DispatchFailed, Fault,
                         FaultInjector, InjectedFault, RequestShed,
                         SchedulerDied, ServeScheduler, ServeSession,
                         bucket_for, chaos_schedule, faults, inject)
from repro.serve.session import ChunkResult, ServeResult

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


# ----------------------------------------------------------- fake plumbing
class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, s):
        self.t += s


class _FakeSession:
    """Duck-typed ServeSession (x -> 2x) that FIRES the session.serve
    probe itself — the real probe is inside ServeSession.serve, so a
    fake must reproduce it for session-site faults to land."""

    def __init__(self, plan):
        self.plan = plan
        self.calls = []  # (rows, plan) per successful serve

    def serve(self, x, labels, plan=None):
        fault = faults.fire("session.serve")
        if fault is not None:
            faults.perform(fault)
        plan = self.plan if plan is None else plan
        self.calls.append((x.shape[0], plan))
        b = x.shape[0]
        sample = x * 2.0
        return ServeResult(sample=sample, chunks=[ChunkResult(
            sample=sample, records=[], engine=None, batch=b,
            bucket=bucket_for(b, max_batch=plan.max_batch),
            wall_s=0.0, traces_delta=0)])

    def stats(self):
        return {}


def _fake_scheduler(**kw):
    fake = _FakeSession(kw.pop("plan", PLAN))
    return ServeScheduler.from_session(fake, **kw)


# -------------------------------------------------------- injector basics
def test_fault_validation():
    with pytest.raises(ValueError, match="unknown fault site"):
        Fault("nope.site", 0, "error")
    with pytest.raises(ValueError, match="does not support kind"):
        Fault("scheduler.take", 0, "stall")
    with pytest.raises(ValueError, match="arrival index"):
        Fault("session.serve", -1, "error")
    with pytest.raises(ValueError, match="positive value"):
        Fault("scheduler.dispatch", 0, "stall")
    with pytest.raises(ValueError, match="duplicate fault"):
        FaultInjector([Fault("session.serve", 0, "error"),
                       Fault("session.serve", 0, "resource_exhausted")])
    with pytest.raises(TypeError):
        FaultInjector(["not a fault"])


def test_injector_one_shot_and_recorded():
    inj = FaultInjector([Fault("session.serve", 1, "error")])
    assert inj.check("session.serve") is None          # arrival 0
    f = inj.check("session.serve")                     # arrival 1: fires
    assert f is not None and f.kind == "error"
    assert inj.check("session.serve") is None          # one-shot
    assert inj.fired == [f] and inj.arrivals("session.serve") == 3


def test_chaos_schedule_deterministic():
    a, b = chaos_schedule(7, 5), chaos_schedule(7, 5)
    assert a.faults == b.faults and len(a.faults) == 5
    assert chaos_schedule(8, 5).faults != a.faults
    for f in a.faults:
        assert f.kind in faults.SITE_KINDS[f.site]


def test_inject_exclusive_and_scoped():
    inj = FaultInjector([Fault("session.serve", 0, "error")])
    assert faults.fire("session.serve") is None  # nothing installed
    with inject(inj):
        with pytest.raises(RuntimeError, match="already installed"):
            with inject(FaultInjector([])):
                pass
        assert faults.fire("session.serve") is inj.faults[0]
    assert faults.fire("session.serve") is None  # uninstalled on exit


# ------------------------------------------------- plan recovery contract
def test_plan_recovery_validation():
    with pytest.raises(ValueError, match="max_retries"):
        DittoPlan(max_retries=-1)
    with pytest.raises(ValueError, match="retry_backoff_ms"):
        DittoPlan(retry_backoff_ms=-1.0)
    with pytest.raises(ValueError):
        DittoPlan(fallbacks=(dict(steps=5),))  # not a FALLBACK_FIELDS key
    with pytest.raises(ValueError, match="watchdog"):
        DittoPlan(reanchor_full_frac=0.9, collect_stats=True)
    with pytest.raises(ValueError, match="collect_stats"):
        DittoPlan(reanchor_full_frac=0.9, watchdog=True, collect_stats=False)
    with pytest.raises(ValueError):
        DittoPlan(reanchor_full_frac=1.5, watchdog=True, collect_stats=True)


def test_recovery_knobs_not_trace_identity():
    base = DittoPlan(collect_stats=False)
    decked = base.replace(max_retries=3, retry_backoff_ms=10.0, watchdog=True,
                          fallbacks=(dict(fused=False), dict(compiled=False)))
    assert decked.cache_sig() == base.cache_sig()
    rungs = decked.fallback_plans()
    assert [r.fused for r in rungs] == [False, False]
    assert rungs[1].compiled is False
    # rungs never recurse: their own ladders are empty
    assert all(r.max_retries == 0 and r.fallbacks == () for r in rungs)


# ----------------------------------------------------- retries and ladder
def test_retry_recovers_without_fallback():
    s = _fake_scheduler(plan=PLAN.replace(max_retries=1))
    with inject(FaultInjector([Fault("session.serve", 0, "error")])):
        t = s.submit(*_request(4, 1))  # full bucket: dispatches in submit
    out = t.result()
    assert out.shape[0] == 4
    st = s.stats()
    assert st["retries"] == 1 and st["fallback_dispatches"] == 0
    assert st["completed"] == 1 and st["failed"] == 0
    assert t.served_with.cache_sig() == PLAN.cache_sig()
    s.close()


def test_ladder_falls_back_on_retry():
    plan = PLAN.replace(fused=True, max_retries=2,
                        fallbacks=(dict(fused=False),))
    s = _fake_scheduler(plan=plan)
    with inject(FaultInjector([Fault("session.serve", 0,
                                     "resource_exhausted")])):
        t = s.submit(*_request(4, 2))
    t.result()
    st = s.stats()
    assert st["retries"] == 1 and st["fallback_dispatches"] == 1
    assert t.served_with.fused is False  # recovered on the rung
    # the rung the fake actually served with is the validated fallback
    assert s.session.calls[-1][1].cache_sig() == plan.fallback_plans()[0].cache_sig()
    s.close()


@pytest.mark.parametrize("plan,kernel", [
    (PLAN.replace(interpret=False, fused=True, max_retries=2,
                  fallbacks=(dict(fused=False),)), "ditto_fused_matmul"),
    (PLAN.replace(interpret=False, max_retries=1,
                  fallbacks=(dict(low_bits=4),)), "int4 branch"),
], ids=["fused_plan", "int4_rung"])
def test_native_refusal_is_never_recovered_by_the_ladder(plan, kernel):
    """A lowering the backend lacks — on the plan or on any ladder rung —
    is refused when the plan is accepted, with the typed error on the
    caller's stack; nothing is served, retried or counted as a recovery."""
    from repro.kernels import NativeLoweringUnsupported

    s = _fake_scheduler()
    with pytest.raises(NativeLoweringUnsupported, match=kernel):
        s.submit(*_request(4, 2), plan=plan)
    st = s.stats()
    assert s.session.calls == [] and st["submitted"] == 0
    assert st["retries"] == 0 and st["fallback_dispatches"] == 0
    with pytest.raises(NativeLoweringUnsupported, match=kernel):
        ServeSession(None, CFG, None, plan)
    s.close()


def test_ladder_exhaustion_is_typed():
    plan = PLAN.replace(max_retries=2, fallbacks=(dict(fused=False),))
    s = _fake_scheduler(plan=plan)
    schedule = [Fault("session.serve", i, "error") for i in range(3)]
    with inject(FaultInjector(schedule)) as inj:
        with pytest.raises(DispatchFailed) as ei:
            s.submit(*_request(4, 3))
        assert ei.value.attempts == 3
        assert isinstance(ei.value.__cause__, InjectedFault)
        assert len(inj.fired) == 3
    st = s.stats()
    assert st["failed"] == 1 and st["retries"] == 2
    s.close()


def test_single_attempt_raises_original_error():
    """No retry budget: the original fault surfaces, never DispatchFailed."""
    s = _fake_scheduler()
    with inject(FaultInjector([Fault("session.serve", 0, "error")])):
        with pytest.raises(InjectedFault, match="session.serve"):
            s.submit(*_request(4, 4))
    s.close()


def test_backoff_is_bounded():
    """Exponential backoff between retries stays under BACKOFF_CAP_MS."""
    from repro.serve.scheduler import BACKOFF_CAP_MS
    plan = PLAN.replace(max_retries=3, retry_backoff_ms=1.0)
    s = _fake_scheduler(plan=plan)
    t0 = time.monotonic()
    with inject(FaultInjector([Fault("session.serve", i, "error")
                               for i in range(3)])):
        t = s.submit(*_request(4, 5))
    t.result()
    wall = time.monotonic() - t0
    assert wall < 3 * BACKOFF_CAP_MS / 1e3  # 1+2+4 ms of backoff, not caps
    assert s.stats()["retries"] == 3
    s.close()


# ------------------------------------------------ thread-death and repair
def test_take_fault_fails_covered_tickets_thread_survives():
    s = _fake_scheduler(async_mode=True, dispatch_interval_ms=5.0)
    with inject(FaultInjector([Fault("scheduler.take", 0, "error")])):
        t1 = s.submit(*_request(4, 6))
        with pytest.raises(InjectedFault):
            t1.result(timeout=30.0)
    # the queue is repaired and the thread alive: next request serves
    t2 = s.submit(*_request(4, 7))
    assert t2.result(timeout=30.0).shape[0] == 4
    st = s.stats()
    assert st["failed"] == 1 and st["completed"] == 1 and not st["died"]
    s.close()


def test_policy_fault_is_typed_scheduler_death():
    s = _fake_scheduler(async_mode=True, dispatch_interval_ms=5.0)
    with inject(FaultInjector([Fault("scheduler.policy", 0, "error")])):
        # the policy may fire on a wakeup before OR after this submit
        # lands; either way the failure must be a typed SchedulerDied
        with pytest.raises(SchedulerDied):
            s.submit(*_request(4, 8)).result(timeout=30.0)
        with pytest.raises(SchedulerDied):
            s.submit(*_request(2, 9))
    st = s.stats()
    assert st["died"] and st["live_tickets"] == 0
    s.close()  # a dead scheduler still closes without hanging


def test_close_surfaces_stalled_dispatch():
    s = _fake_scheduler(async_mode=True, dispatch_interval_ms=5.0)
    with inject(FaultInjector([Fault("scheduler.dispatch", 0, "stall",
                                     value=1.0)])):
        s.submit(*_request(4, 10))
        deadline = time.monotonic() + 5.0
        while not s.stats()["inflight"] and time.monotonic() < deadline:
            time.sleep(0.005)
        with pytest.raises(RuntimeError, match="failed to join"):
            s.close(drain=False, join_timeout_s=0.05)


def test_shed_expired_is_typed():
    clk = _FakeClock()
    s = _fake_scheduler(eager=False, shed_expired=True, clock=clk)
    t = s.submit(*_request(2, 11), deadline_ms=50.0)
    clk.advance(0.2)  # budget long gone, nothing dispatched
    s.poll()
    with pytest.raises(RequestShed, match="shed"):
        t.result()
    st = s.stats()
    assert st["shed"] == 1 and st["failed"] == 1 and st["live_tickets"] == 0
    s.close()


def test_shed_never_hits_dispatched_rows():
    """A request with rows already in flight is served, not half-shed."""
    clk = _FakeClock()
    s = _fake_scheduler(eager=False, shed_expired=True, clock=clk,
                        plan=PLAN.replace(max_batch=2))
    t = s.submit(*_request(3, 12), deadline_ms=50.0)  # splits 2 + 1
    s.poll()  # nothing due yet (not full, budget not near)
    clk.advance(0.049)
    s.poll()  # deadline trigger: first 2 rows dispatch
    clk.advance(0.2)  # now expired, but 2 rows are already served
    s.poll()
    assert t.result().shape[0] == 3
    assert s.stats()["shed"] == 0
    s.close()


# ----------------------------------------------------------- chaos matrix
@pytest.mark.parametrize("seed", range(6))
def test_chaos_every_ticket_terminates(seed):
    """Seeded multi-fault schedules over the scheduler/session sites:
    every ticket terminates with a sample or a typed error within a
    bound, and close(drain=True) returns. (denoise.step is exercised by
    the slow real-stack tests — the fake has no denoise loop.)"""
    sites = ("session.serve", "scheduler.policy", "scheduler.take",
             "scheduler.dispatch")
    inj = chaos_schedule(seed, 4, sites=sites, max_at=4)
    plan = PLAN.replace(max_retries=2, retry_backoff_ms=1.0,
                        fallbacks=(dict(fused=False),))
    s = _fake_scheduler(plan=plan, async_mode=True, dispatch_interval_ms=5.0)
    outcomes = []
    with inject(inj):
        tickets = []
        for i, b in enumerate([3, 4, 2, 4, 1]):
            try:
                tickets.append(s.submit(*_request(b, 20 + i)))
            except (SchedulerDied, RuntimeError) as e:
                outcomes.append(e)
        for t in tickets:
            try:
                outcomes.append(t.result(timeout=60.0))
            except (InjectedFault, DispatchFailed, SchedulerDied) as e:
                outcomes.append(e)
    assert len(outcomes) == 5  # nothing hung, nothing vanished
    try:
        s.close(drain=True, join_timeout_s=30.0)
    except SchedulerDied:
        pass  # a policy fault may have killed the thread; close still returns
    st = s.stats()
    assert st["live_tickets"] == 0 and st["queued_rows"] == 0
    # every ticket that was actually created resolved one way or the other
    assert st["completed"] + st["failed"] == len(tickets)


# ------------------------------------------------- real-stack (slow) tests
@pytest.mark.slow
def test_ladder_recovery_bit_identical(setup):
    """The acceptance property: a dispatch recovered on a kernel-family
    fallback rung returns bit-identical rows to the fault-free serve."""
    params, sched = setup
    plan = PLAN.replace(fused=True, low_bits=4, max_retries=1,
                        fallbacks=(dict(fused=False),))
    cache = CompiledRunnerCache()
    x, labels = _request(4, 30)

    ref_s = ServeScheduler(params, CFG, sched, plan, cache=cache)
    ref = ref_s.submit(x, labels).result()
    ref_s.close()

    s = ServeScheduler(params, CFG, sched, plan, cache=cache)
    with inject(FaultInjector([Fault("session.serve", 0, "error")])) as inj:
        t = s.submit(x, labels)
        out = t.result()
    s.close()
    assert len(inj.fired) == 1 and t.served_with.fused is False
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.slow
def test_poison_triggers_nonfinite_reanchor(setup):
    """A poisoned compiled step re-runs as a full-bit-width re-anchor:
    the sample comes back finite and the event is visible in stats."""
    params, sched = setup
    plan = PLAN.replace(watchdog=True)
    sess = ServeSession(params, CFG, sched, plan)
    x, labels = _request(4, 31)
    with inject(FaultInjector([Fault("denoise.step", 0, "poison_nan")])) as inj:
        out = sess.serve(x, labels).sample
    assert len(inj.fired) == 1
    assert bool(jnp.isfinite(out).all())
    assert sess.stats()["watchdog_events"] >= 1


@pytest.mark.slow
def test_drift_triggers_saturation_reanchor(setup):
    """Drift saturates the tile-class histograms; the next step runs as
    a scheduled re-anchor (paper's initial-step semantics mid-sample)."""
    params, sched = setup
    plan = PLAN.replace(steps=4, collect_stats=True, watchdog=True,
                        reanchor_full_frac=0.9)
    sess = ServeSession(params, CFG, sched, plan)
    x, labels = _request(4, 32)
    with inject(FaultInjector([Fault("denoise.step", 0, "drift",
                                     value=64.0)])) as inj:
        out = sess.serve(x, labels).sample
    assert len(inj.fired) == 1
    assert bool(jnp.isfinite(out).all())
    assert sess.stats()["watchdog_events"] >= 1


@pytest.mark.slow
def test_reanchor_shares_canonical_trace(setup):
    """Every kernel-family serving plan re-anchors through ONE canonical
    trace (unfused, default bits, all-act modes) — recovery never mints
    a surprise trace."""
    params, sched = setup
    cache = CompiledRunnerCache()
    x, labels = _request(4, 33)
    p_fused = PLAN.replace(fused=True, watchdog=True)
    p_int4 = PLAN.replace(low_bits=4, watchdog=True)
    sess = ServeSession(params, CFG, sched, p_fused, cache=cache)
    with inject(FaultInjector([Fault("denoise.step", 0, "poison_inf")])):
        sess.serve(x, labels)
    n_after_first = cache.n_traces  # fused step + canonical re-anchor
    sess2 = ServeSession(params, CFG, sched, p_int4, cache=cache)
    sess2.serve(x, labels)  # fault-free: compiles the int4 step only
    n_warm = cache.n_traces
    with inject(FaultInjector([Fault("denoise.step", 0, "poison_inf")])):
        out = sess2.serve(x, labels).sample
    assert bool(jnp.isfinite(out).all())
    assert sess2.stats()["watchdog_events"] >= 1
    # the second plan's re-anchor reused the already-compiled canonical
    # trace: no new trace appeared
    assert cache.n_traces == n_warm
    assert n_warm == n_after_first + 1
