"""ServeScheduler: continuous batching across request submissions.

``ServeSession.serve`` batches WITHIN one call: each call chunks to
``max_batch`` and pads its own remainder chunk up to a power-of-two
bucket. A stream of small requests therefore wastes pad rows on every
call — batch-3 requests each pad to bucket 4, throwing away a quarter of
every dispatch. The scheduler closes that gap by coalescing ACROSS
submissions:

  * ``submit(x, labels, plan=None, deadline_ms=...) -> Ticket`` queues a
    request (with an optional per-request :class:`DittoPlan` override and
    an optional latency budget) and returns immediately. Whenever a plan
    group's queue holds at least ``max_batch`` rows, a full bucket is
    dispatched eagerly — requests never wait behind an arbitrary flush to
    make forward progress.
  * ``flush()`` dispatches everything still queued (the ragged tail pays
    the only padding in the stream) and resolves all tickets.
  * ``Ticket.result()`` returns this request's rows of the sample —
    blocking until a dispatch covers them.

Requests are grouped by behavior, not object identity: the grouping key
is the loop-level fields plus the normalized ``(start, stop,
cache_sig())`` segment partition (+ label presence), so sig-equal plans
or :class:`PlanSchedule`\\ s constructed separately — including a constant
schedule and its equivalent bare plan, or duck-typed plans whose extra
fields don't reach the sig — coalesce into ONE bucket group, while
submissions that differ in sampling loop or in the kernel lowering of
ANY step never batch together. ``deadline_ms`` deliberately stays OUT of
the key (and out of ``cache_sig()`` — gated by the trace audit): it
changes WHEN a request dispatches, never what it computes, so requests
with different budgets still coalesce. Per-request overrides (one client
on ``fused``, another on an int8→int4 schedule) therefore coexist in one
scheduler sharing one runner cache — and can never share a trace, since
the plan is the trace identity (``RunnerKey`` embeds
``plan.cache_sig()``).

Dispatches may split a request across two batches or pack several
requests into one; both are invisible in the results because activation
calibration is PER SAMPLE (``quant.sample_scale``): no element of a
sample's quantized trajectory depends on which other samples share its
batch, so the coalesced rows are bit-identical to a per-request
``serve()`` (property-tested in tests/test_scheduler.py and
tests/test_async_serving.py).

Async SLO-aware mode
--------------------

``async_mode=True`` starts a background dispatch thread and turns the
flush policy time-based: a group dispatches when it holds a full bucket
OR when the oldest queued request's latency budget (``deadline_ms``,
from the submit call or the plan) is within one ``dispatch_interval`` of
expiring — a deliberate partial-bucket dispatch that trades pad rows for
the SLO. ``Ticket.result()`` then blocks on a completion event instead
of synchronously flushing the world. The policy lives in
``_next_job_locked`` (deadline-due first, then full buckets, then
demanded/drained tails); ``poll()`` runs the same policy one step on the
calling thread, which with an injected ``clock`` makes the time-based
behavior deterministic under test — the background thread itself always
waits on real time.

Fault tolerance
---------------

Dispatch failures walk the plan's degradation ladder (``max_retries``
re-dispatches with bounded backoff down ``fallbacks`` rungs — see
``_serve_and_deliver``); batch-assembly failures are transactional
(``_take_locked``); a dead dispatch thread fails every pending and
future call with a typed :class:`SchedulerDied` instead of hanging
(``_on_died``); and ``shed_expired=True`` rejects already-expired queued
requests with :class:`RequestShed`. Every path is driven determinist-
ically by ``repro.serve.faults`` probes and covered by the chaos suite
(tests/test_faults.py). See docs/architecture.md § fault model.

Completed tickets RETIRE: the scheduler keeps aggregate counters, not
the tickets' device arrays (each resolved Ticket holds exactly its own
sample until the client drops it). ``retain=True`` restores the full
``self.tickets`` / ``self.dispatches`` / ``Ticket.results`` record
keeping for benches and tests that introspect dispatch composition —
with the documented cost that every ServeResult (engines, records,
padded samples) stays live for the scheduler's lifetime.

Mesh mode
---------

``mesh`` (a :class:`repro.serve.mesh.ServeMesh`) puts the scheduler on a
device mesh: one :class:`ServeSession` per shard (all sharing ONE runner
cache — shard submeshes are sig-equal, so they share every trace), every
submitted plan stamped with the mesh signature (``mesh_devices`` /
``mesh_axis`` enter ``cache_sig()``, so mesh groups can never coalesce
with unsharded ones), and per-shard dispatch: each group is routed to
the least-loaded shard at creation, and in async mode each shard runs
its own dispatch thread over its own queues. When a shard's queue runs
hot — due work (a full bucket, a nearing deadline, a demanded or drained
tail) the owner is too busy to take — an idle sibling STEALS it: the
thief runs the same dispatch policy over sibling queues (gated by
``mesh.steal`` / ``mesh.steal_min_rows``) and serves the batch on its
own shard, bit-identically (per-sample calibration makes the serving
device invisible in the rows). Deadline, shedding, and ladder-recovery
semantics are per dispatch and therefore preserved per shard; a fault
injected on one shard walks that dispatch's ladder without touching
siblings. See docs/architecture.md § mesh.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time
from collections import deque
from typing import Any, Callable

import jax
import jax.numpy as jnp

from ..core import diffusion
from ..core.ditto import DittoEngine, make_denoise_fn
from ..core.ditto.plan import (UNSET, DittoPlan, PlanSchedule, is_unset,
                               require_native_lowering, segment_view)
from . import faults
from .bucketing import bucket_for
from .cache import CompiledRunnerCache
from .session import ServeResult, ServeSession

#: Per-retry exponential backoff is capped here so a deep ladder cannot
#: sleep a dispatch past any plausible SLO.
BACKOFF_CAP_MS = 2000.0


class SchedulerDied(RuntimeError):
    """The background dispatch thread died; the scheduler cannot serve.

    Every pending ``Ticket.result()`` raises this (the original thread
    exception is the ``__cause__``), as does any later ``submit()``."""


class DispatchFailed(RuntimeError):
    """A dispatch failed after exhausting its retry/fallback ladder."""

    def __init__(self, attempts: int, cause: BaseException):
        super().__init__(
            f"dispatch failed after {attempts} attempt(s): {cause!r}")
        self.attempts = attempts
        self.__cause__ = cause


class RequestShed(RuntimeError):
    """Deadline-aware load shedding rejected this request: its latency
    budget expired before any dispatch covered it (``shed_expired=True``).
    A typed rejection the client can retry — not a silent SLO blowout."""


class _TakeFailed(RuntimeError):
    """Internal: batch assembly failed; covered tickets are already
    failed and the queue repaired — the dispatch loop just moves on."""


class Ticket:
    """Handle for one submitted request; resolves to its own sample rows."""

    def __init__(self, scheduler: "ServeScheduler", index: int, batch: int,
                 plan: DittoPlan | PlanSchedule, deadline_ms: float | None,
                 submit_t: float):
        self._scheduler = scheduler
        self.index = index  # submission order, scheduler-wide
        self.batch = batch  # rows in this request
        self.plan = plan  # normalized plan/schedule this request runs under
        self.deadline_ms = deadline_ms  # latency budget; None = no SLO
        self.submit_t = submit_t  # scheduler-clock time of submit()
        self.done_t: float | None = None  # scheduler-clock time of completion
        # absolute budget expiry on the scheduler clock; the dispatch policy
        # compares against this, never against wall time directly
        self._deadline_t = (None if deadline_ms is None
                            else submit_t + deadline_ms / 1e3)
        self.served_with = None  # plan of the successful dispatch (ladder rung)
        self._pieces: list[jax.Array] = []  # filled in row order by dispatches
        self._filled = 0
        self._sample: jax.Array | None = None
        self._error: BaseException | None = None
        self._event = threading.Event()
        self.results: list[ServeResult] = []  # populated only under retain=True

    @property
    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: float | None = None) -> jax.Array:
        """This request's sample at its TRUE batch size (rows in submission
        order). Blocks until served; in sync mode a still-queued request
        triggers ``flush()``, in async mode it marks the request demanded
        so the dispatch thread drains its group next."""
        if not self._event.is_set():
            self._scheduler._demand(self)
            if not self._event.wait(timeout):
                raise TimeoutError(
                    f"request {self.index} not served within {timeout}s")
        if self._error is not None:
            raise self._error
        return self._sample

    # ------------------------------------------------------------- internal
    # all mutation happens under the scheduler's condition lock
    def _deliver(self, dst: int, rows: jax.Array,
                 result: ServeResult | None) -> None:
        # dst = this piece's row offset within the request, captured at
        # take time: split pieces may be SERVED on different shard
        # threads and complete out of order, so append order is not row
        # order — _finish reassembles by offset
        self._pieces.append((dst, rows))
        self._filled += rows.shape[0]
        if result is not None:
            self.results.append(result)

    def _finish(self, now: float) -> None:
        pieces = [rows for _, rows in sorted(self._pieces,
                                             key=lambda p: p[0])]
        if len(pieces) > 1:
            # a request split across dispatches may have been served on
            # different shards (steal / bucket split); concatenate needs
            # the pieces co-located, so pull stragglers onto piece 0's
            # device — placement only, the row values are untouched
            devs: set[Any] = set()
            for p in pieces:
                devs.update(getattr(p, "devices", set)())
            if len(devs) > 1:
                dev = next(iter(pieces[0].devices()))
                pieces = [jax.device_put(p, dev) for p in pieces]
        self._sample = pieces[0] if len(pieces) == 1 else jnp.concatenate(
            pieces, axis=0)
        self._pieces = []  # drop the dispatch-sliced intermediates
        self.done_t = now
        self._event.set()

    def _fail(self, exc: BaseException, now: float) -> None:
        self._error = exc
        self._pieces = []
        self.done_t = now
        self._event.set()


@dataclasses.dataclass
class _Pending:
    ticket: Ticket
    x: jax.Array
    labels: jax.Array | None
    used: int = 0  # rows already dispatched

    @property
    def remaining(self) -> int:
        return self.x.shape[0] - self.used


class _Group:
    """FIFO queue of pending requests sharing one behavioral group key.
    ``plan`` is the first-seen normalized plan/schedule of the group —
    every member is behaviorally identical to it (same loop, same
    per-step sigs), so dispatching all members under it is exact.
    ``shard`` is the mesh shard whose queue owns the group (0 — the only
    session — in solo mode); a sibling shard may still steal its due
    work."""

    def __init__(self, plan: DittoPlan | PlanSchedule, shard: int = 0):
        self.plan = plan
        self.shard = shard
        self.pending: deque[_Pending] = deque()

    @property
    def queued_rows(self) -> int:
        return sum(p.remaining for p in self.pending)


def _naive_pad(batch: int, max_batch: int) -> int:
    """Pad rows ``batch`` would waste as an independent serve() call."""
    total, b = 0, batch
    while b > 0:
        c = min(b, max_batch)
        total += bucket_for(c, max_batch=max_batch) - c
        b -= c
    return total


def _bucket_ladder(max_batch: int) -> list[int]:
    out, b = [], 1
    while b <= max_batch:
        out.append(b)
        b *= 2
    return out


class ServeScheduler:
    """Continuous-batching front-end over one :class:`ServeSession`.

    ``plan`` is the default for submissions that don't carry their own;
    ``cache`` (shared runner cache) and the session are owned by the
    scheduler. ``eager=False`` disables the dispatch-on-full-bucket
    behavior, queueing everything until ``flush()`` (useful for tests and
    offline/batch workloads that want maximal packing decisions made at
    one point in time).

    ``async_mode=True`` starts the background dispatch thread (see module
    docstring): submissions return immediately, dispatch is driven by the
    full-bucket / deadline policy, ``Ticket.result()`` blocks on
    completion. ``dispatch_interval_ms`` is the policy's time granularity
    — a request's budget counts as "nearing" within one interval of
    expiry, and the acceptance bound for deadline tests is one interval.
    ``clock`` (a ``() -> float`` seconds callable) injects a fake clock
    for deterministic tests; it must be monotonic. ``collect_done=True``
    exposes completed tickets on the ``done`` queue (consumer's job to
    drain it). ``retain=True`` keeps full per-dispatch records — see the
    retirement note in the module docstring.
    """

    def __init__(self, params, cfg, sched, plan: DittoPlan | PlanSchedule | None = None, *,
                 cache: CompiledRunnerCache | None = None, mesh=None,
                 eager: bool = True, async_mode: bool = False,
                 dispatch_interval_ms: float = 10.0,
                 retain: bool = False, collect_done: bool = False,
                 shed_expired: bool = False,
                 clock: Callable[[], float] = time.monotonic):
        plan = plan if plan is not None else DittoPlan()
        sessions = None
        if mesh is not None:
            # one session per shard, one shared cache: shard submeshes are
            # sig-equal, so every trace is shared; each session commits the
            # params onto its own shard submesh once
            cache = cache if cache is not None else CompiledRunnerCache()
            plan = mesh.plan_for(plan)
            sessions = [ServeSession(params, cfg, sched, plan, cache=cache,
                                     mesh=mesh.shard_mesh(k))
                        for k in range(mesh.n_shards)]
            session = sessions[0]
        else:
            session = ServeSession(params, cfg, sched, plan, cache=cache)
        self._init_runtime(
            session, mesh=mesh, sessions=sessions,
            eager=eager, async_mode=async_mode,
            dispatch_interval_ms=dispatch_interval_ms, retain=retain,
            collect_done=collect_done, shed_expired=shed_expired, clock=clock)

    @classmethod
    def from_session(cls, session, *, eager: bool = True, async_mode: bool = False,
                     dispatch_interval_ms: float = 10.0, retain: bool = False,
                     collect_done: bool = False, shed_expired: bool = False,
                     clock: Callable[[], float] = time.monotonic) -> "ServeScheduler":
        """Wrap an existing session-like object (anything with ``.plan``,
        ``.serve(x, labels, plan=)`` and ``.stats()``) — the hook tests
        and benches use to drive the dispatch policy without a model."""
        s = cls.__new__(cls)
        s._init_runtime(session, eager=eager, async_mode=async_mode,
                        dispatch_interval_ms=dispatch_interval_ms,
                        retain=retain, collect_done=collect_done,
                        shed_expired=shed_expired, clock=clock)
        return s

    def _init_runtime(self, session, *, eager, async_mode, dispatch_interval_ms,
                      retain, collect_done, shed_expired, clock,
                      mesh=None, sessions=None):
        self.session = session
        self.mesh = mesh
        # per-shard sessions (mesh mode); solo mode serves everything on
        # self.session, which is also sessions[0] in mesh mode
        self._sessions = sessions if sessions is not None else [session]
        self._n_shards = mesh.n_shards if mesh is not None else 1
        self.eager = eager
        self.async_mode = async_mode
        self.retain = retain
        self.shed_expired = shed_expired  # reject expired queued requests
        self.dispatch_interval = dispatch_interval_ms / 1e3
        self._clock = clock
        self._cv = threading.Condition()  # guards everything below
        self._groups: dict[tuple, _Group] = {}
        self._live: dict[int, Ticket] = {}  # unresolved tickets only
        self._urgent: set[int] = set()  # ticket indices demanded via result()
        self._draining = False
        self._inflight = 0
        self._closed = False
        self._n_submitted = 0
        self._rows_submitted = 0
        self._n_dispatches = 0
        self._dispatched_rows = 0
        self._pad_rows = 0
        self._naive_pad_rows = 0
        self._completed = 0
        self._failed = 0
        self._deadline_misses = 0
        self._retries = 0
        self._fallbacks = 0
        self._shed = 0
        self._died: BaseException | None = None
        self._triggers = {"full": 0, "deadline": 0, "demand": 0, "drain": 0,
                          "steal": 0}
        # mesh accounting: dispatches/rows per serving shard, steal events
        self._shard_dispatches = [0] * self._n_shards
        self._shard_rows = [0] * self._n_shards
        self._shard_inflight = [0] * self._n_shards  # steal gate: owner busy?
        self._steals = 0
        self._stolen_rows = 0
        self._rr = 0  # round-robin tiebreak for group routing
        # retained record keeping — empty unless retain=True (retirement
        # keeps the live set bounded by the number of UNRESOLVED requests)
        self.tickets: list[Ticket] = []
        self.dispatches: list[ServeResult] = []
        self.done: queue.SimpleQueue | None = (
            queue.SimpleQueue() if collect_done else None)
        self._threads: list[threading.Thread] = []
        if async_mode:
            # one dispatch thread per shard (solo = one thread, shard 0);
            # each thread runs the same policy over its own shard's groups
            # and — in mesh mode — may steal due work from siblings
            for k in range(self._n_shards):
                name = ("ditto-serve-dispatch" if self._n_shards == 1
                        else f"ditto-serve-shard{k}")
                t = threading.Thread(target=self._dispatch_loop, args=(k,),
                                     name=name, daemon=True)
                self._threads.append(t)
                t.start()

    # ------------------------------------------------------------------ api
    @staticmethod
    def _group_key(plan: DittoPlan | PlanSchedule) -> tuple:
        """Behavioral coalescing key for a normalized plan or schedule:
        the loop-level fields plus the ``(start, stop, cache_sig())``
        segment partition. Built from ``cache_sig()`` rather than plan
        equality so sig-equal plans/schedules constructed separately — a
        constant schedule vs its bare plan, duck-typed plan subclasses —
        land in one group; anything that can change the served rows
        (different loop, different lowering at any step) cannot.
        ``deadline_ms`` is deliberately absent: urgency is per-request
        metadata, not behavior. The recovery policy (retries, ladder,
        watchdog) IS part of the key — it never changes a trace (gated by
        the trace audit), but a dispatch recovers all covered tickets
        under the group plan's policy, so requests with different ladders
        must not share a dispatch."""
        segments = tuple((start, stop, p.cache_sig())
                         for start, stop, p in segment_view(plan))
        recovery = (getattr(plan, "max_retries", 0),
                    getattr(plan, "retry_backoff_ms", 0.0),
                    tuple(getattr(plan, "fallbacks", ()) or ()),
                    bool(getattr(plan, "watchdog", False)),
                    getattr(plan, "reanchor_full_frac", None))
        return (plan.steps, plan.sampler, plan.policy, plan.compiled,
                plan.max_batch, segments, recovery)

    def submit(self, x: jax.Array, labels=None,
               plan: DittoPlan | PlanSchedule | None = None, *,
               deadline_ms: float | None = UNSET) -> Ticket:
        """Queue one request; returns its :class:`Ticket` immediately.

        ``plan`` (a DittoPlan or PlanSchedule) overrides the scheduler
        default for this request. ``deadline_ms`` overrides the plan's
        latency budget for this request (``None`` = no budget). Full
        ``max_batch`` buckets are dispatched as soon as they fill (unless
        ``eager=False``). A plan whose lowering — on any segment or ladder
        rung — the backend lacks raises here
        (``core.ditto.plan.require_native_lowering``) and is never queued."""
        if x.shape[0] < 1:
            raise ValueError("empty request")
        plan = plan if plan is not None else self.session.plan
        if self.mesh is not None:
            # every dispatched plan carries the mesh signature — an
            # unstamped override would land in a separate (unsharded)
            # trace-identity group and never share the warmed runners
            plan = self.mesh.plan_for(plan)
        # a lowering the chip lacks is refused here, on the caller's stack:
        # raised inside a dispatch it would look like a fault the
        # degradation ladder recovers from on another lowering
        require_native_lowering(plan)
        plan = plan.normalized()
        if is_unset(deadline_ms):
            deadline_ms = plan.deadline_ms
        elif deadline_ms is not None and not deadline_ms > 0:
            raise ValueError(f"deadline_ms must be > 0 (or None), got {deadline_ms}")
        now = self._clock()
        with self._cv:
            if self._closed:
                raise RuntimeError("scheduler is closed")
            if self._died is not None:
                raise SchedulerDied(
                    "scheduler dispatch thread has died; no further "
                    "requests can be served") from self._died
            key = (self._group_key(plan), labels is not None)
            group = self._groups.get(key)
            if group is None:
                group = self._groups[key] = _Group(plan,
                                                   shard=self._route_locked())
            ticket = Ticket(self, self._n_submitted, x.shape[0], plan,
                            deadline_ms, now)
            self._n_submitted += 1
            self._rows_submitted += ticket.batch
            self._naive_pad_rows += _naive_pad(ticket.batch, plan.max_batch)
            self._live[ticket.index] = ticket
            if self.retain:
                self.tickets.append(ticket)
            group.pending.append(_Pending(ticket, x, labels))
            if self.async_mode:
                self._cv.notify_all()  # wake the dispatch thread
            elif self.eager:
                while group.queued_rows >= plan.max_batch:
                    self._dispatch_locked(group, plan.max_batch, "full")
        return ticket

    def flush(self) -> list[Ticket]:
        """Dispatch every queued row (full buckets first; the ragged tail
        is the only padded dispatch) and return the tickets resolved by
        this call. In async mode this blocks until the dispatch thread
        has drained every group and nothing is in flight."""
        with self._cv:
            snapshot = list(self._live.values())
            if self.async_mode:
                self._draining = True
                self._cv.notify_all()
                while (not self._closed and self._died is None and (
                        self._inflight
                        or any(g.queued_rows for g in self._groups.values()))):
                    self._cv.wait()
                self._draining = False
            else:
                for group in self._groups.values():
                    while group.queued_rows:
                        self._dispatch_locked(
                            group, min(group.queued_rows, group.plan.max_batch),
                            "drain")
            return [t for t in snapshot if t.done]

    def poll(self, shard: int | None = None) -> int:
        """Run at most one due dispatch on the calling thread and return
        the rows it dispatched (0 = nothing due). Same policy as the
        background threads (``_next_job_locked``) — the deterministic
        counterpart for fake-clock tests and thread-free embeddings.
        ``shard`` polls as that shard's dispatch thread would: its own
        queues first, then the cross-shard steal scan; ``None`` (default)
        scans every group with no stealing."""
        with self._cv:
            job = self._next_job_locked(shard)
            if job is None:
                return 0
            group, rows, trigger = job
            try:
                batch = self._take_locked(group, rows)
            except _TakeFailed:
                return rows  # covered tickets failed; the queue is repaired
            serve_shard = shard if shard is not None else group.shard
            self._inflight += 1
            self._shard_inflight[serve_shard] += 1
        try:
            self._serve_and_deliver(group, batch, trigger, shard=serve_shard)
        finally:
            with self._cv:
                self._inflight -= 1
                self._shard_inflight[serve_shard] -= 1
                self._cv.notify_all()
        return rows

    def close(self, *, drain: bool = True, join_timeout_s: float = 5.0) -> None:
        """Stop the dispatch thread; ``drain=True`` (default) flushes the
        queues first so no ticket is left unresolved. A dispatch thread
        that fails to join within ``join_timeout_s`` raises (the
        scheduler still counts as closed) — a wedged thread holding the
        device is an error the caller must see, not a silent leak."""
        if self._closed:
            return
        if drain:
            self.flush()
        with self._cv:
            self._closed = True
            self._cv.notify_all()
        threads, self._threads = self._threads, []
        for thread in threads:
            thread.join(timeout=join_timeout_s)
            if thread.is_alive():
                raise RuntimeError(
                    f"dispatch thread {thread.name} failed to join within "
                    f"{join_timeout_s}s (stalled dispatch?); the scheduler "
                    f"is closed but the thread may still hold the device")

    def __enter__(self) -> "ServeScheduler":
        return self

    def __exit__(self, *exc) -> None:
        # a failing with-body shouldn't hang on a drain of queued work
        self.close(drain=exc[0] is None)

    # --------------------------------------------------------------- warmup
    def warmup(self, *, plans=None, buckets=None, labels: bool = True,
               probe_seed: int = 0) -> dict:
        """AOT-compile the bucket ladder before the first request.

        Runs a cheap eager calibration probe per distinct (policy, steps)
        — batch-1, deterministic seeded noise, the 2-forward prefix before
        the Defo decision, which is sampler-independent (both samplers'
        first update is the same DDIM step) — to obtain the frozen
        per-layer modes, then lowers + compiles one executable per (plan
        segment sig, bucket) through
        :meth:`CompiledRunnerCache.warmup`. First requests then skip both
        the XLA trace and the XLA compile. Caveat: a request whose Defo
        decision differs from the probe's lands on a different RunnerKey
        and pays a cold compile (``aot_misses`` in ``stats()`` counts
        fingerprint mismatches on warmed keys).

        ``plans`` defaults to the session plan; ``buckets`` to each
        plan's full power-of-two ladder; ``labels`` must match whether
        requests pass class labels (it is part of the traced signature).

        In mesh mode the ladder is additionally PRIMED on every sibling
        shard: executables are placement-specific (``jax.jit`` compiles
        per argument sharding), so shard 0's abstract AOT copy cannot
        serve a dispatch placed on shard k. Siblings run one real
        batch-``b`` dispatch per ladder bucket through their own session
        — populating jit's placement-keyed executable cache with ZERO new
        traces (the jaxpr is shared; ``traces`` stays once per mesh
        signature) — so a first request landing on (or stolen by) any
        shard skips the cold compile. Priming dispatches count toward
        session stats, never scheduler dispatch counters; the count is
        returned as ``primed``.
        """
        t0 = time.monotonic()
        plans = [p.normalized() for p in
                 (plans if plans is not None else [self.session.plan])]
        by_probe: dict[tuple, list] = {}
        for p in plans:
            by_probe.setdefault((p.policy, p.steps), []).append(p)
        out = {"aot_compiled": 0, "traces": 0, "primed": 0}
        cfg = self.session.cfg
        for group_plans in by_probe.values():
            modes = self._probe_modes(group_plans[0], labels=labels,
                                      probe_seed=probe_seed)
            for p in group_plans:
                ladder = (_bucket_ladder(p.max_batch) if buckets is None
                          else buckets)
                r = self.session.cache.warmup(self.session.cfg, modes, [p],
                                              ladder, labels=labels,
                                              params=self.session.params)
                out["aot_compiled"] += r["aot_compiled"]
                out["traces"] += r["traces"]
                for sess in self._sessions[1:]:
                    for b in ladder:
                        x = jax.random.normal(
                            jax.random.PRNGKey(probe_seed),
                            (b, cfg.input_size, cfg.input_size,
                             cfg.in_channels), jnp.float32)
                        lab = (jnp.zeros((b,), jnp.int32) if labels
                               else None)
                        sess.serve(x, lab, plan=p)
                        out["primed"] += 1
        out["wall_s"] = time.monotonic() - t0
        return out

    def _probe_modes(self, plan, *, labels: bool, probe_seed: int) -> dict:
        """Frozen per-layer modes from an eager calibration prefix: run
        batch-1 seeded-noise forwards until the engine is ready for the
        compiled pass (scales calibrated; Defo decided after step 2)."""
        cfg = self.session.cfg
        eng = DittoEngine(policy=plan.policy, collect_oracle=False)
        fn = make_denoise_fn(self.session.params, cfg, eng)
        x = jax.random.normal(
            jax.random.PRNGKey(probe_seed),
            (1, cfg.input_size, cfg.input_size, cfg.in_channels), jnp.float32)
        lab = jnp.zeros((1,), jnp.int32) if labels else None
        ts = diffusion.ddim_timesteps(self.session.sched.T, plan.steps)
        eng.begin_sample()
        for i in range(len(ts)):
            if eng.ready_for_compiled():
                break
            t = int(ts[i])
            t_prev = int(ts[i + 1]) if i + 1 < len(ts) else -1
            t_vec = jnp.full((1,), t, jnp.int32)
            eps = fn(x, t_vec, lab)
            x = diffusion.ddim_step(self.session.sched, x, eps, t, t_prev)
        return eng.compiled_modes()

    # ------------------------------------------------------------ internals
    def _demand(self, ticket: Ticket) -> None:
        """A client is blocked in ``result()`` on a still-queued ticket."""
        if not self.async_mode:
            self.flush()
            return
        with self._cv:
            if ticket.index in self._live:
                self._urgent.add(ticket.index)
                self._cv.notify_all()

    def _route_locked(self) -> int:
        """Shard for a newly created group: least total queued rows across
        its current groups, round-robin tiebreak (an idle mesh spreads
        fresh groups across shards instead of piling them on shard 0)."""
        if self._n_shards == 1:
            return 0
        load = [0] * self._n_shards
        for g in self._groups.values():
            load[g.shard] += g.queued_rows
        order = [(self._rr + k) % self._n_shards
                 for k in range(self._n_shards)]
        shard = min(order, key=lambda k: load[k])
        self._rr = (shard + 1) % self._n_shards
        return shard

    def _next_job_locked(self, shard: int | None = None
                         ) -> tuple[_Group, int, str] | None:
        """The dispatch policy: pick the next (group, rows, trigger) to
        serve, or None if nothing is due. Deadline-due partials preempt
        full buckets — a full bucket is never urgent (it loses no budget
        by dispatching one policy round later), an expiring request is.
        With ``shed_expired=True``, requests whose budget already expired
        un-dispatched are rejected (typed :class:`RequestShed`) before
        the deadline scan — serving them late helps nobody and steals
        device time from requests that can still make their SLO.

        ``shard`` scopes the scan to that shard's own groups (the per-
        shard dispatch threads); ``None`` scans everything (solo mode,
        ``poll()`` default, sync ``flush()``). A shard with no due work
        of its own STEALS: it runs the same scan over sibling groups
        whose owner shard is currently mid-dispatch — work that is due
        but whose owner is too busy to take — never force-dispatching a
        partial bucket an idle owner was still coalescing."""
        f = faults.fire("scheduler.policy")
        if f is not None:
            faults.perform(f)
        now = self._clock()
        if self.shed_expired:
            self._shed_locked(now)
        groups = (list(self._groups.values()) if shard is None else
                  [g for g in self._groups.values() if g.shard == shard])
        job = self._policy_scan_locked(groups, now)
        if job is not None or shard is None:
            return job
        if self.mesh is not None and self.mesh.steal:
            victims = [g for g in self._groups.values()
                       if g.shard != shard
                       and self._shard_inflight[g.shard]
                       and g.queued_rows >= self.mesh.steal_min_rows]
            job = self._policy_scan_locked(victims, now)
            if job is not None:
                group, rows, _ = job
                return group, rows, "steal"
        return None

    def _policy_scan_locked(self, groups, now: float
                            ) -> tuple[_Group, int, str] | None:
        """One pass of the deadline -> full -> demand -> drain policy over
        ``groups`` (a shard's own queues, or — for a steal — a sibling's)."""
        for group in groups:
            if any(p.ticket._deadline_t is not None
                   and p.ticket._deadline_t - now <= self.dispatch_interval
                   for p in group.pending):
                q = group.queued_rows
                return group, min(q, group.plan.max_batch), "deadline"
        if self.eager or self._draining:
            for group in groups:
                if group.queued_rows >= group.plan.max_batch:
                    return group, group.plan.max_batch, "full"
        if self._urgent:
            for group in groups:
                if any(p.ticket.index in self._urgent for p in group.pending):
                    q = group.queued_rows
                    return group, min(q, group.plan.max_batch), "demand"
        if self._draining:
            for group in groups:
                q = group.queued_rows
                if q:
                    return group, min(q, group.plan.max_batch), "drain"
        return None

    def _next_wakeup_locked(self) -> float | None:
        """Seconds (real-clock semantics) until the earliest queued budget
        becomes due, or None to sleep until notified."""
        now = self._clock()
        waits = [p.ticket._deadline_t - self.dispatch_interval - now
                 for g in self._groups.values() for p in g.pending
                 if p.ticket._deadline_t is not None]
        if not waits:
            return None
        return max(min(waits), 1e-4)  # floor avoids a zero-length spin

    def _shed_locked(self, now: float) -> None:
        """Reject every queued request whose budget has already expired
        (none of its rows dispatched yet — a split request in flight is
        served, not half-shed)."""
        any_shed = False
        for group in self._groups.values():
            for p in [p for p in group.pending
                      if p.used == 0 and p.ticket._deadline_t is not None
                      and now > p.ticket._deadline_t]:
                group.pending.remove(p)
                self._shed += 1
                self._failed += 1
                p.ticket._fail(RequestShed(
                    f"request {p.ticket.index} shed: deadline_ms="
                    f"{p.ticket.deadline_ms} expired before dispatch"), now)
                self._retire_locked(p.ticket)
                any_shed = True
        if any_shed:
            self._cv.notify_all()

    def _dispatch_loop(self, shard: int = 0) -> None:
        # Any escape from the loop body — a policy/take bug, an injected
        # scheduler fault, OOM during concatenate — lands in _on_died so a
        # dead thread fails fast instead of stranding result() callers.
        # One thread per shard shares this body; a death on ANY shard
        # fails the whole scheduler (recovery from serve faults is the
        # per-dispatch ladder in _serve_and_deliver, not thread death).
        try:
            self._dispatch_loop_inner(shard)
        except BaseException as exc:  # noqa: BLE001 — death must be typed
            self._on_died(exc)

    def _dispatch_loop_inner(self, shard: int) -> None:
        while True:
            with self._cv:
                while True:
                    if self._closed:
                        return
                    job = self._next_job_locked(shard)
                    if job is not None:
                        break
                    self._cv.wait(self._next_wakeup_locked())
                group, rows, trigger = job
                try:
                    batch = self._take_locked(group, rows)
                except _TakeFailed:
                    continue  # tickets failed, queue repaired — move on
                self._inflight += 1
                self._shard_inflight[shard] += 1
            try:
                fault = faults.fire("scheduler.dispatch")
                if fault is not None:
                    faults.perform(fault)
                self._serve_and_deliver(group, batch, trigger, shard=shard)
            finally:
                with self._cv:
                    self._inflight -= 1
                    self._shard_inflight[shard] -= 1
                    self._cv.notify_all()

    def _on_died(self, exc: BaseException) -> None:
        """The dispatch thread is dead: fail every live ticket with a
        typed :class:`SchedulerDied` (original exception chained) and
        clear the queues so ``flush()`` waiters wake instead of hanging."""
        now = self._clock()
        with self._cv:
            self._died = exc
            err = SchedulerDied(
                f"dispatch thread died: {exc!r}; all pending requests "
                f"failed")
            err.__cause__ = exc
            for ticket in list(self._live.values()):
                self._failed += 1
                ticket._fail(err, now)
                self._retire_locked(ticket)
            self._groups.clear()
            self._cv.notify_all()

    def _take_locked(self, group: _Group, rows: int):
        """Pop exactly ``rows`` queued rows of ``group`` (FIFO, splitting a
        request across dispatches when needed).

        Assembly is transactional: rows are planned with pure index math
        first, and only after slicing/concatenation succeed are the
        pendings consumed. On failure (this used to be the silent-hang
        site — an exception here killed the dispatch thread with the
        tickets still queued) the covered tickets fail with the error,
        leave the queue, and :class:`_TakeFailed` tells the caller to
        continue."""
        plan_items: list[tuple[_Pending, int]] = []
        take, i = rows, 0
        while take:
            p = group.pending[i]
            c = min(p.remaining, take)
            plan_items.append((p, c))
            take -= c
            i += 1
        try:
            fault = faults.fire("scheduler.take")
            if fault is not None:
                faults.perform(fault)
            xs, ls = [], []
            for p, c in plan_items:
                xs.append(p.x[p.used:p.used + c])
                if p.labels is not None:
                    ls.append(p.labels[p.used:p.used + c])
            x = xs[0] if len(xs) == 1 else jnp.concatenate(xs, axis=0)
            labels = None if not ls else (ls[0] if len(ls) == 1
                                          else jnp.concatenate(ls, axis=0))
        except BaseException as exc:
            now = self._clock()
            for p, _ in plan_items:
                self._failed += 1
                p.ticket._fail(exc, now)
                self._retire_locked(p.ticket)
                group.pending.remove(p)
            self._cv.notify_all()
            raise _TakeFailed(str(exc)) from exc
        segments = []
        for p, c in plan_items:
            segments.append((p.ticket, p.used, c))
            p.used += c
        while group.pending and not group.pending[0].remaining:
            group.pending.popleft()
        return x, labels, segments

    def _serve_and_deliver(self, group: _Group, batch, trigger: str,
                           shard: int | None = None) -> ServeResult | None:
        """Serve one taken batch (OUTSIDE the lock — the policy keeps
        accepting submissions while the device runs) and deliver each
        covered ticket its slice. ``shard`` is the SERVING shard — the
        thief's own on a stolen job, the group's otherwise (per-sample
        calibration makes the serving devices invisible in the rows).

        A failed serve walks the plan's degradation ladder: up to
        ``max_retries`` re-dispatches with bounded exponential backoff,
        each retry running the next ``fallback_plans()`` rung (the last
        rung repeats once the ladder is shorter than the retry budget).
        Kernel-family rungs (fused→unfused→int8→eager) are bit-identical
        by the exact-integer-math contract, so a recovered ticket's rows
        match the fault-free ones bit for bit. Exhausting the ladder
        fails the covered tickets with :class:`DispatchFailed` (single
        no-retry attempts keep raising the original error)."""
        x, labels, segments = batch
        shard = group.shard if shard is None else shard
        session = self._sessions[shard] if shard < len(self._sessions) else self.session
        plan = group.plan
        ladder = (plan,) + tuple(plan.fallback_plans()
                                 if hasattr(plan, "fallback_plans") else ())
        attempts = 1 + getattr(plan, "max_retries", 0)
        backoff_ms = getattr(plan, "retry_backoff_ms", 0.0)
        result = None
        used_plan = plan
        last_exc: BaseException | None = None
        ran = 0
        for attempt in range(attempts):
            used_plan = ladder[min(attempt, len(ladder) - 1)]
            if attempt:
                with self._cv:
                    self._retries += 1
                    if used_plan is not plan:
                        self._fallbacks += 1
                if backoff_ms:
                    time.sleep(
                        min(backoff_ms * 2 ** (attempt - 1), BACKOFF_CAP_MS)
                        / 1e3)
            ran = attempt + 1
            try:
                result = session.serve(x, labels, plan=used_plan)
                break
            except Exception as exc:
                last_exc = exc
            except BaseException as exc:
                last_exc = exc  # never retry KeyboardInterrupt/SystemExit
                break
        if result is None:
            exc = (last_exc if ran <= 1
                   else DispatchFailed(ran, last_exc))
            now = self._clock()
            with self._cv:
                self._failed += len(segments)
                for ticket, _, _ in segments:
                    ticket._fail(exc, now)
                    self._retire_locked(ticket)
                self._cv.notify_all()
            if not self.async_mode:
                raise exc  # sync callers get the error on their own stack
            return None
        now = self._clock()
        with self._cv:
            self._n_dispatches += 1
            self._dispatched_rows += x.shape[0]
            self._pad_rows += result.pad_rows
            self._triggers[trigger] += 1
            self._shard_dispatches[shard] += 1
            self._shard_rows[shard] += x.shape[0]
            if trigger == "steal":
                self._steals += 1
                self._stolen_rows += x.shape[0]
            if self.retain:
                self.dispatches.append(result)
            off = 0
            for ticket, dst, c in segments:
                ticket.served_with = used_plan
                # the slice materializes the ticket's own rows as a fresh
                # device array — tickets never pin the padded dispatch
                # sample (or its engines/records) past this block
                ticket._deliver(dst, result.sample[off:off + c],
                                result if self.retain else None)
                off += c
                if ticket._filled == ticket.batch:
                    ticket._finish(now)
                    self._completed += 1
                    if (ticket._deadline_t is not None
                            and now > ticket._deadline_t):
                        self._deadline_misses += 1
                    self._retire_locked(ticket)
            self._cv.notify_all()
        return result

    def _retire_locked(self, ticket: Ticket) -> None:
        self._live.pop(ticket.index, None)
        self._urgent.discard(ticket.index)
        if self.done is not None:
            self.done.put(ticket)

    def _dispatch_locked(self, group: _Group, rows: int, trigger: str
                         ) -> ServeResult | None:
        """Sync-mode dispatch: take + serve + deliver on the calling
        thread (the condition lock is re-entrant, so the nested acquire
        in _serve_and_deliver is fine)."""
        batch = self._take_locked(group, rows)
        return self._serve_and_deliver(group, batch, trigger)

    # ---------------------------------------------------------------- stats
    @property
    def pad_rows(self) -> int:
        """Replicated (wasted) rows across all dispatches so far."""
        return self._pad_rows

    def naive_pad_rows(self) -> int:
        """Pad rows the same submissions would have wasted as independent
        per-request ``serve()`` calls — the baseline the coalescing is
        beating (recorded by benchmarks/bench_scheduler.py)."""
        return self._naive_pad_rows

    def stats(self) -> dict[str, Any]:
        with self._cv:
            queued = sum(g.queued_rows for g in self._groups.values())
            out = {"submitted": self._n_submitted,
                    "submitted_rows": self._rows_submitted,
                    "queued_rows": queued,
                    "inflight": self._inflight,
                    "live_tickets": len(self._live),
                    "completed": self._completed,
                    "failed": self._failed,
                    "dispatches": self._n_dispatches,
                    "dispatched_rows": self._dispatched_rows,
                    "pad_rows": self._pad_rows,
                    "plan_groups": len(self._groups),
                    "triggers": dict(self._triggers),
                    "deadline_misses": self._deadline_misses,
                    "retries": self._retries,
                    "fallback_dispatches": self._fallbacks,
                    "shed": self._shed,
                    "died": self._died is not None}
            if self.mesh is None:
                out.update(self.session.stats())
            else:
                # per-shard sessions share ONE cache: sum the serving
                # counters across sessions, read the cache stats once
                for s in self._sessions:
                    with s._stats_lock:
                        out["batches"] = out.get("batches", 0) + s.batches_served
                        out["requests"] = (out.get("requests", 0)
                                           + s.requests_served)
                        out["watchdog_events"] = (out.get("watchdog_events", 0)
                                                  + s.watchdog_events)
                cache = getattr(self.session, "cache", None)
                if cache is not None:
                    out.update(cache.stats())
                out["mesh"] = {"n_devices": self.mesh.n_devices,
                               "dp": self.mesh.dp,
                               "n_shards": self._n_shards,
                               "shard_dispatches": list(self._shard_dispatches),
                               "shard_rows": list(self._shard_rows),
                               "steals": self._steals,
                               "stolen_rows": self._stolen_rows}
            return out
