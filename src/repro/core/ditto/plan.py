"""DittoPlan: the one authoritative execution-configuration object.

Every serving knob used to be a loose keyword argument threaded through
seven signatures (``kernels/ops.py`` -> ``core/ditto/compiled.py`` ->
``dit_runner.make_step_fn`` -> ``serve.cache`` -> ``sim.harness`` ->
``ServeSession`` -> the examples); adding one knob meant editing all of
them, and nothing guaranteed the knob reached the runner-cache key. A
:class:`DittoPlan` is a frozen, hashable dataclass holding the whole
configuration in three groups:

  kernel   : ``block``, ``interpret``, ``low_bits``, ``fused`` — what the
             Pallas step lowers to (validated once, at construction);
  mesh     : ``mesh_devices``, ``mesh_axis`` — the data-parallel submesh a
             dispatch executes on (``None`` = unsharded single-device);
  sampling : ``steps``, ``sampler``, ``policy`` — the denoising loop and
             the engine's mode policy;
  serve    : ``compiled``, ``collect_stats``, ``max_batch``,
             ``deadline_ms`` — runtime behavior of the serving layer.

A plan IS a trace identity: :meth:`cache_sig` returns the ordered tuple
of exactly the fields that select a distinct XLA lowering, and
``serve.cache.RunnerKey`` is ``(cfg_sig, mode_sig, plan.cache_sig(),
bucket)``. Per-request plans therefore compose naturally with the shared
runner cache — two requests whose plans agree on ``cache_sig()`` (and on
model/modes/bucket) replay one trace no matter how the rest of their
plans differ, and plans that lower differently can never collide.

Plans can vary across the denoising loop: :class:`PlanSchedule` maps
timestep ranges to deltas over the kernel-lowering fields
(:data:`SEGMENT_FIELDS`), normalizes sig-equal neighbors together, and
compiles one trace per distinct segment — see its docstring.

Deprecation shims: the legacy splatted-kwarg call styles still work
through :func:`plan_from_kwargs`, which rebuilds the equivalent plan and
warns once per call site name. New code should construct plans directly:

    plan = DittoPlan(steps=20, low_bits=4)
    sess = ServeSession(params, cfg, sched, plan=plan)
"""
from __future__ import annotations

import dataclasses
import warnings

from ...kernels.common import (DEFAULT_LOW_BITS, ShardedLoweringUnsupported,
                               require_native_kernels, resolve_interpret, validate_low_bits)

DEFAULT_MAX_BATCH = 64  # mirrored by repro.serve.bucketing

_SAMPLERS = ("ddim", "plms")
_POLICIES = ("act", "diff", "spatial", "defo", "defo+")

#: Plan fields a schedule segment may override — exactly the kernel-lowering
#: fields of :meth:`DittoPlan.cache_sig`. Loop-level fields (``steps``,
#: ``sampler``, ``policy``, ``compiled``, ``max_batch``) shape the loop
#: around the steps and must stay constant across a schedule. The tile
#: classification threshold is not a knob: it is fixed by the packed-int4
#: contract (``|delta| <= LOW_BIT_MAX`` so class-1 tiles pack losslessly).
SEGMENT_FIELDS = ("block", "interpret", "collect_stats", "low_bits", "fused")

#: Mesh/sharding-signature fields. These select how a compiled step's batch
#: axis is laid out across a ``jax.sharding.Mesh`` (a
#: ``sharding_constraint`` over an abstract ``(mesh_axis: mesh_devices)``
#: mesh is stamped into the traced step), so they ARE trace identity and
#: every one of them must be read by :meth:`DittoPlan.cache_sig` — sharded
#: and unsharded runners never collide in the runner cache. They are not
#: segment-schedulable (a mid-loop mesh change would reshard the carried
#: state) and not fallback-overridable (a degraded rung stays on its
#: shard's submesh). ``analysis.plan_rules.check_plan_rules`` enforces the
#: partition statically; steal/queue policy knobs live on
#: ``serve.mesh.ServeMesh`` and are checked to stay OUT of the sig.
MESH_SIG_FIELDS = ("mesh_devices", "mesh_axis")

#: Plan fields a degradation-ladder fallback delta may override: the
#: segment (kernel-lowering) fields plus ``compiled``, so the last rung can
#: drop to the eager engine. Loop/queueing fields stay fixed — a fallback
#: redispatch must cover the same tickets with the same loop shape.
FALLBACK_FIELDS = SEGMENT_FIELDS + ("compiled",)

#: Recovery-policy fields. None of these changes what a step lowers to, so
#: none may appear in :meth:`DittoPlan.cache_sig` — two plans differing
#: only in how they *recover* replay one trace.
#: ``analysis.plan_rules.check_plan_rules`` enforces this statically.
ROBUSTNESS_FIELDS = (
    "max_retries", "retry_backoff_ms", "fallbacks", "watchdog",
    "reanchor_full_frac",
)


def _canon_delta(delta) -> tuple:
    """Delta -> canonical sorted ``((field, value), ...)`` tuple."""
    if delta is None:
        return ()
    items = delta.items() if isinstance(delta, dict) else delta
    try:
        pairs = [(k, v) for k, v in items]
    except (TypeError, ValueError):
        raise ValueError(
            f"segment delta must be a dict or (field, value) pairs, got {delta!r}")
    return tuple(sorted(pairs))


@dataclasses.dataclass(frozen=True)
class DittoPlan:
    """Frozen, hashable execution plan for one request (or one session)."""

    # --- kernel config: selects the Pallas lowering -----------------------
    block: int = 128
    interpret: bool | None = None  # None = auto-detect backend
    low_bits: int = DEFAULT_LOW_BITS  # 4 = packed-int4 low-tile branch
    fused: bool = False  # single-pass fused diff-step kernel
    # --- mesh config: data-parallel layout of one dispatch ------------------
    mesh_devices: int | None = None  # devices per dispatch submesh; None = unsharded
    mesh_axis: str = "data"  # mesh axis name the batch dim shards over
    # --- sampling config: the denoising loop ------------------------------
    steps: int = 20
    sampler: str = "ddim"
    policy: str = "defo"
    # --- serve config: runtime behavior ------------------------------------
    compiled: bool = True
    collect_stats: bool = True
    max_batch: int = DEFAULT_MAX_BATCH
    deadline_ms: float | None = None  # per-request latency budget (SLO); None = no budget
    # --- recovery config: never part of cache_sig() ------------------------
    max_retries: int = 0  # extra dispatch attempts after the first fails
    retry_backoff_ms: float = 0.0  # base backoff, doubled per retry (capped)
    fallbacks: tuple = ()  # degradation ladder: plan deltas over FALLBACK_FIELDS
    watchdog: bool = False  # per-step finite guard + re-anchor on the diff path
    reanchor_full_frac: float | None = None  # Δ-saturation threshold; None = off

    def __post_init__(self):
        validate_low_bits(self.low_bits)
        self._validate_recovery()
        if self.block < 1:
            raise ValueError(f"block must be >= 1, got {self.block}")
        if self.steps < 1:
            raise ValueError(f"steps must be >= 1, got {self.steps}")
        if self.max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {self.max_batch}")
        if self.max_batch & (self.max_batch - 1):
            # the bucket ladder is {1, 2, 4, ..., max_batch}; a non-power-of-two
            # cap would let bucket_for emit non-canonical sizes (min(8, 6) = 6),
            # silently fragmenting the runner cache past log2(max_batch)+1
            raise ValueError(
                f"max_batch must be a power of two (the canonical bucket "
                f"ladder), got {self.max_batch}")
        if self.deadline_ms is not None and not self.deadline_ms > 0:
            raise ValueError(
                f"deadline_ms must be > 0 (or None for no budget), "
                f"got {self.deadline_ms}")
        if self.sampler not in _SAMPLERS:
            raise ValueError(f"sampler must be one of {_SAMPLERS}, got {self.sampler!r}")
        if self.policy not in _POLICIES:
            raise ValueError(f"policy must be one of {_POLICIES}, got {self.policy!r}")
        if self.mesh_devices is not None:
            if self.mesh_devices < 1 or self.mesh_devices & (self.mesh_devices - 1):
                # buckets are powers of two, so a pow2 submesh width divides
                # every bucket >= itself — the batch axis always shards evenly
                # (smaller buckets fall back to a replicated spec, same trace
                # family, still mesh-signed)
                raise ValueError(
                    f"mesh_devices must be a power of two >= 1 (or None for "
                    f"unsharded), got {self.mesh_devices}")
        if not (isinstance(self.mesh_axis, str) and self.mesh_axis.isidentifier()):
            raise ValueError(
                f"mesh_axis must be an identifier string, got {self.mesh_axis!r}")

    def _validate_recovery(self) -> None:
        if self.max_retries < 0:
            raise ValueError(
                f"max_retries must be >= 0, got {self.max_retries}")
        if self.retry_backoff_ms < 0:
            raise ValueError(
                f"retry_backoff_ms must be >= 0, got {self.retry_backoff_ms}")
        canon = tuple(_canon_delta(d) for d in tuple(self.fallbacks))
        object.__setattr__(self, "fallbacks", canon)
        for delta in canon:
            bad = sorted(k for k, _ in delta if k not in FALLBACK_FIELDS)
            if bad:
                raise ValueError(
                    f"fallback delta overrides non-fallback fields {bad}; "
                    f"allowed fields are {FALLBACK_FIELDS}")
            # each rung must itself be a valid plan
            self.replace(**dict(delta), fallbacks=())
        if self.reanchor_full_frac is not None:
            if not 0.0 < self.reanchor_full_frac <= 1.0:
                raise ValueError(
                    f"reanchor_full_frac must be in (0, 1], "
                    f"got {self.reanchor_full_frac}")
            if not self.watchdog:
                raise ValueError(
                    "reanchor_full_frac requires watchdog=True (the "
                    "saturation metric is read by the watchdog)")
            if not self.collect_stats:
                raise ValueError(
                    "reanchor_full_frac requires collect_stats=True (the "
                    "saturation metric is derived from the recorded "
                    "tile-class histograms)")

    # ------------------------------------------------------------------ api
    def replace(self, **kw) -> "DittoPlan":
        """A copy with fields overridden (re-validated)."""
        return dataclasses.replace(self, **kw)

    def normalized(self) -> "DittoPlan":
        """The plan with ``interpret=None`` resolved to its backend value,
        so auto-detected and explicit plans that lower identically compare
        (and hash) equal — the scheduler groups requests by this."""
        return self.replace(interpret=resolve_interpret(self.interpret))

    def cache_sig(self) -> tuple:
        """Ordered trace-identity tuple — the plan fields that select a
        distinct jitted step. ``RunnerKey`` embeds this verbatim; the
        field order is a stable contract (see ``RunnerKey``'s accessors).
        ``steps``/``sampler``/``policy``/``compiled``/``max_batch``/
        ``deadline_ms`` and the :data:`ROBUSTNESS_FIELDS` are
        deliberately absent: they shape the loop (or the serving/recovery
        policy) around the step, not the step itself, so
        plans differing only there share one trace
        (``steps`` counts how often the step runs — the trace-identity
        audit in ``repro.analysis.trace_audit`` proves it has no jaxpr
        effect, and keeping it in the sig re-traced the whole denoiser
        per step-count). The :data:`MESH_SIG_FIELDS` enter as the final
        :meth:`mesh_sig` element — a sharded step carries a
        ``sharding_constraint`` over its submesh in the jaxpr, so plans
        differing only in mesh layout lower differently and must never
        share a trace.
        """
        return (self.block, resolve_interpret(self.interpret), self.collect_stats,
                self.low_bits, self.fused, self.mesh_sig())

    def mesh_sig(self) -> tuple | None:
        """``(mesh_devices, mesh_axis)`` for a sharded plan, else ``None``.
        This is the whole mesh identity a compiled step sees: concrete
        device objects stay out (two shards of the same width replay one
        trace; placement is a dispatch-time concern of ``serve.mesh``)."""
        if self.mesh_devices is None:
            return None
        return (self.mesh_devices, self.mesh_axis)

    def kernel_blk(self) -> dict:
        """The kernel-config dict the ops wrappers accept (``bm/bn/bk``
        tile edges plus lowering knobs)."""
        return dict(bm=self.block, bn=self.block, bk=self.block,
                    interpret=self.interpret, low_bits=self.low_bits,
                    fused=self.fused)

    def fallback_plans(self) -> tuple:
        """The resolved degradation ladder: one :class:`DittoPlan` per
        ``fallbacks`` delta, in order. Rungs carry no recovery policy of
        their own (``max_retries=0``, no further fallbacks) — the ladder
        is walked by the scheduler, one rung per retry attempt, and must
        not recurse. ``watchdog``/``reanchor_full_frac`` are inherited:
        numerical health checks stay on while degraded."""
        return tuple(
            self.replace(**dict(delta), max_retries=0, retry_backoff_ms=0.0,
                         fallbacks=())
            for delta in self.fallbacks)


#: Default plan for the bare eager engine path (`make_denoise_fn` with no
#: plan): calibration/analysis runs, not the compiled serving fast path.
EAGER_PLAN = DittoPlan(compiled=False)


# ----------------------------------------------------------- plan schedules
@dataclasses.dataclass(frozen=True)
class PlanSchedule:
    """Frozen, hashable mapping of timestep ranges -> plan deltas.

    A schedule is a :class:`DittoPlan` whose kernel-lowering fields vary
    with the sampler step: ``segments`` is a tuple of ``(start, stop,
    delta)`` half-open ranges over ``[0, base.steps)`` where each delta
    overrides a subset of :data:`SEGMENT_FIELDS` on ``base``. Construction
    validates the partition (full cover, no gaps, no overlaps, no empty
    ranges) and that every delta yields a valid plan.

    Trace identity is per *segment*, not per step: the step loop in
    ``make_denoise_fn`` partitions by segment and each distinct
    ``cache_sig()`` compiles exactly one trace (per bucket). A schedule
    whose steps all resolve to one plan is *constant* and collapses to
    that bare plan everywhere that matters — same ``RunnerKey``, same
    scheduler bucket group, zero new traces.

        sched = PlanSchedule(DittoPlan(steps=12), [
            (0, 4, {}),                              # int8 two-pass early
            (4, 12, dict(low_bits=4, fused=True)),   # packed-int4 fused late
        ])
    """

    base: DittoPlan
    segments: tuple = ()

    def __post_init__(self):
        if not isinstance(self.base, DittoPlan):
            raise TypeError(
                f"PlanSchedule.base must be a DittoPlan, got {type(self.base).__name__}")
        canon = []
        for seg in tuple(self.segments):
            try:
                start, stop, delta = seg
            except (TypeError, ValueError):
                raise ValueError(
                    f"segment must be (start, stop, delta), got {seg!r}")
            canon.append((int(start), int(stop), _canon_delta(delta)))
        canon.sort(key=lambda s: (s[0], s[1]))
        object.__setattr__(self, "segments", tuple(canon))
        self._validate()

    def _validate(self) -> None:
        steps = self.base.steps
        if not self.segments:
            raise ValueError(f"schedule has no segments; must cover [0, {steps})")
        cursor = 0
        for start, stop, delta in self.segments:
            if stop <= start:
                raise ValueError(f"empty segment [{start}, {stop})")
            if start < cursor:
                raise ValueError(
                    f"segments overlap: [{start}, {stop}) begins before step {cursor}")
            if start > cursor:
                raise ValueError(f"gap: steps [{cursor}, {start}) are uncovered")
            if stop > steps:
                raise ValueError(
                    f"segment [{start}, {stop}) exceeds steps={steps}")
            bad = sorted(k for k, _ in delta if k not in SEGMENT_FIELDS)
            if bad:
                raise ValueError(
                    f"segment [{start}, {stop}) overrides non-segment fields "
                    f"{bad}; schedulable fields are {SEGMENT_FIELDS}")
            self.base.replace(**dict(delta))  # each delta must yield a valid plan
            cursor = stop
        if cursor != steps:
            raise ValueError(f"gap: steps [{cursor}, {steps}) are uncovered")

    # ----------------------------------------------- loop-level delegation
    # Constant across the schedule by construction — callers that only care
    # about the loop shape (samplers, chunking, bucketing) read these off a
    # schedule exactly as off a bare plan.
    @property
    def steps(self) -> int:
        return self.base.steps

    @property
    def sampler(self) -> str:
        return self.base.sampler

    @property
    def policy(self) -> str:
        return self.base.policy

    @property
    def compiled(self) -> bool:
        return self.base.compiled

    @property
    def max_batch(self) -> int:
        return self.base.max_batch

    @property
    def deadline_ms(self) -> float | None:
        return self.base.deadline_ms

    @property
    def collect_stats(self) -> bool:
        # engine-side oracle stats follow the base; the compiled per-segment
        # value comes from each segment plan
        return self.base.collect_stats

    # Mesh layout is loop-level: segments may not reshard mid-loop (the
    # carried state would need a cross-mesh transfer at every boundary), so
    # every segment plan inherits the base's submesh.
    @property
    def mesh_devices(self) -> int | None:
        return self.base.mesh_devices

    @property
    def mesh_axis(self) -> str:
        return self.base.mesh_axis

    def mesh_sig(self) -> tuple | None:
        return self.base.mesh_sig()

    # Recovery policy is loop-level too: the ladder/watchdog govern the
    # whole dispatch, not one segment, so they delegate to the base.
    @property
    def max_retries(self) -> int:
        return self.base.max_retries

    @property
    def retry_backoff_ms(self) -> float:
        return self.base.retry_backoff_ms

    @property
    def fallbacks(self) -> tuple:
        return self.base.fallbacks

    @property
    def watchdog(self) -> bool:
        return self.base.watchdog

    @property
    def reanchor_full_frac(self) -> float | None:
        return self.base.reanchor_full_frac

    def fallback_plans(self) -> tuple:
        """The ladder for a scheduled dispatch: rungs degrade to CONSTANT
        plans (the schedule's per-segment variation is abandoned once a
        dispatch has already failed — simplicity beats optimality on the
        failure path)."""
        return self.base.fallback_plans()

    # ------------------------------------------------------------------ api
    def plan_for(self, step: int) -> DittoPlan:
        """The fully-resolved plan governing sampler step ``step``."""
        for start, stop, delta in self.segments:
            if start <= step < stop:
                return self.base.replace(**dict(delta))
        raise ValueError(
            f"step {step} outside the schedule's [0, {self.base.steps}) range")

    def segment_plans(self) -> tuple:
        """``((start, stop, DittoPlan), ...)`` — the resolved partition."""
        return tuple((start, stop, self.base.replace(**dict(delta)))
                     for start, stop, delta in self.segments)

    def replace(self, **kw) -> "PlanSchedule":
        """A copy with ``base``/``segments`` overridden (re-validated)."""
        return dataclasses.replace(self, **kw)

    def normalized(self) -> "PlanSchedule":
        """Canonical form: base and segment plans normalized, adjacent
        segments whose deltas resolve to the same plan (⇔ same
        ``cache_sig()``, since every schedulable field is a sig field)
        merged, and each delta reduced to the fields that actually differ
        from the base. Two schedules spelling the same per-step behavior
        differently compare (and hash) equal after this — the scheduler
        groups by it, and trace count == number of distinct segment sigs.
        """
        base = self.base.normalized()
        merged: list = []
        for start, stop, plan in self.segment_plans():
            plan = plan.normalized()
            if merged and merged[-1][2] == plan:
                prev_start, _, prev_plan = merged.pop()
                merged.append((prev_start, stop, prev_plan))
            else:
                merged.append((start, stop, plan))
        segments = tuple(
            (start, stop, tuple(sorted(
                (f, getattr(plan, f)) for f in SEGMENT_FIELDS
                if getattr(plan, f) != getattr(base, f))))
            for start, stop, plan in merged)
        return dataclasses.replace(self, base=base, segments=segments)

    def cache_sigs(self) -> tuple:
        """Distinct segment ``cache_sig()`` tuples in first-use order — the
        schedule's trace budget (one jitted step per entry, per bucket)."""
        sigs: list = []
        for _, _, plan in self.segment_plans():
            sig = plan.cache_sig()
            if sig not in sigs:
                sigs.append(sig)
        return tuple(sigs)

    def is_constant(self) -> bool:
        """True when every step resolves to one plan (after normalization)."""
        return self.constant_plan() is not None

    def constant_plan(self) -> DittoPlan | None:
        """The single per-step plan when the schedule is constant, else
        ``None``. A constant schedule IS its plan: it lands on the same
        ``RunnerKey`` and scheduler group as the equivalent bare plan."""
        plans = {plan for _, _, plan in self.normalized().segment_plans()}
        if len(plans) == 1:
            return plans.pop()
        return None


def segment_resolved(plan):
    """Collapse ``plan`` to the one :class:`DittoPlan` step-level APIs need.

    ``make_step_fn``, the compiled ops, and the runner cache consume ONE
    segment-resolved plan per trace. A bare plan passes through; a
    constant :class:`PlanSchedule` resolves to its single plan (same
    ``RunnerKey`` as the bare plan — no trace duplication); a
    multi-segment schedule cannot be collapsed here and raises — it must
    be partitioned upstream (``make_denoise_fn`` and the serve layers
    accept the schedule itself and resolve per segment).
    """
    if isinstance(plan, PlanSchedule):
        const = plan.constant_plan()
        if const is None:
            raise TypeError(
                "a multi-segment PlanSchedule resolves per step; pass one "
                "segment's plan (PlanSchedule.plan_for / segment_plans) — "
                "make_denoise_fn and the serve layers accept the schedule "
                "itself and partition the loop by segment")
        return const
    return plan


def segment_view(plan):
    """``((start, stop, DittoPlan), ...)`` for plan OR schedule, normalized.

    A bare plan is one whole-loop segment. Normalization first means two
    spellings of the same per-step behavior produce equal views — the
    scheduler's grouping key is built from this."""
    if isinstance(plan, PlanSchedule):
        return plan.normalized().segment_plans()
    plan = plan.normalized()
    return ((0, plan.steps, plan),)


def require_native_lowering(plan) -> None:
    """Refuse, before anything is served, a plan (or schedule) that selects
    a lowering the chip does not have — in any segment and on any rung of
    its degradation ladder, so a refusal can never surface mid-dispatch as
    a fault the ladder recovers from. Under native lowering that is a
    kernel variant :func:`~repro.kernels.common.require_native_kernels`
    refuses, or a batch sharded over ``mesh_devices > 1`` chips
    (:class:`ShardedLoweringUnsupported`). Interpreted plans pass."""
    rungs = plan.fallback_plans() if hasattr(plan, "fallback_plans") else ()
    for p in [seg for _, _, seg in segment_view(plan)] + list(rungs):
        if resolve_interpret(p.interpret):
            continue
        require_native_kernels(fused=p.fused, low_bits=p.low_bits)
        if (p.mesh_devices or 1) > 1:
            raise ShardedLoweringUnsupported(
                f"mesh_devices={p.mesh_devices} shards one dispatch over "
                f"{p.mesh_devices} chips, but the Pallas kernels have no "
                f"partitioning rule; serve with dp=1 (one replica per chip)")


# --------------------------------------------------------- deprecation shim
class _Unset:
    """Sentinel distinguishing "kwarg not passed" from any real value."""

    def __repr__(self):  # pragma: no cover - repr only
        return "<unset>"


UNSET = _Unset()

_warned_sites: set[str] = set()


def reset_deprecation_warnings() -> None:
    """Forget which call sites already warned (tests use this)."""
    _warned_sites.clear()


def is_unset(v) -> bool:
    """True when ``v`` is the :data:`UNSET` sentinel (kwarg not passed)."""
    return isinstance(v, _Unset)


def plan_from_kwargs(site: str, plan: DittoPlan | None, *, default: DittoPlan | None = None,
                     **kw) -> DittoPlan:
    """Resolve a (plan, legacy-kwargs) call into one plan.

    ``kw`` maps legacy kwarg names to their passed values, with
    :data:`UNSET` marking "not passed". Passing any legacy kwarg emits a
    ``DeprecationWarning`` once per ``site`` and builds the equivalent
    plan; mixing a plan AND legacy kwargs is an error (two sources of
    truth). With neither, ``plan`` (or ``default``, or the default plan)
    is returned.
    """
    passed = {k: v for k, v in kw.items() if not isinstance(v, _Unset)}
    if not passed:
        if plan is not None:
            return plan
        return default if default is not None else DittoPlan()
    if plan is not None:
        raise TypeError(
            f"{site}: pass either plan= or the deprecated keyword arguments "
            f"({sorted(passed)}), not both")
    if site not in _warned_sites:
        _warned_sites.add(site)
        warnings.warn(
            f"{site}: the splatted keyword arguments {sorted(passed)} are "
            f"deprecated; construct a repro.core.ditto.DittoPlan and pass "
            f"plan= instead",
            DeprecationWarning, stacklevel=3)
    return DittoPlan(**passed)
