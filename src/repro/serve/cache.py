"""Persistent compiled-runner cache for the serving path.

PR 1's two-phase engine made the post-calibration denoising steps one
jitted Pallas function — but every serve batch still built its own
``CompiledDittoDiT``, whose step closed over that batch's params, so XLA
re-traced and re-compiled per batch. ``make_step_fn`` (core.ditto.
dit_runner) removed the closure: the step's only trace-static inputs are
the model config, the frozen per-layer modes and the plan's trace
identity. This module adds the cross-batch memory: ONE ``jax.jit``-
wrapped step per

    RunnerKey = (model-cfg signature, layer-mode signature,
                 plan.cache_sig(), batch bucket)

``plan.cache_sig()`` is the ordered tuple of exactly the
:class:`~repro.core.ditto.DittoPlan` fields that select a distinct XLA
lowering — ``(block, interpret, collect_stats, low_bits, fused,
mesh_sig)`` — so a plan IS a trace identity: serve configs that lower
different kernel bodies (``low_bits=4`` packed-int4, ``fused=True``
single-pass DMA-skipping) or different mesh layouts (``mesh_sig`` stamps
a batch-axis ``sharding_constraint`` into the step) can never share a
trace, while plans differing only in loop-level fields
(``steps``/``sampler``/``policy``/``max_batch``) always do — and all
shards of one :class:`~repro.serve.mesh.ServeMesh` DO share every trace,
because a shard's identity is its width and axis name, never its
concrete devices.

The same store holds each segment loop (``dit_runner.make_loop_fn``: a
segment's compiled steps and the sampler's update as one ``lax.scan``),
under the step's key plus the sampler's update and the loop's length
(``RunnerKey.loop``), with the same trace and AOT counters.

The key is shared by every subsequent batch that maps to it (and shapes —
which the batch bucket pins). The cache counts actual Python traces via a
trace-time side effect, so tests can assert "N same-bucket batches
compile exactly once" instead of inferring it from wall-clock.

The pre-plan keyword style (``block=...``, ``extra=(steps, bucket)``) is
a deprecated shim that builds the equivalent plan and lands on the SAME
RunnerKey, so migrating callers can share traces with un-migrated ones.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Any, Callable

import jax

from ..core.ditto import dit_runner
from ..core.ditto.plan import (UNSET, DittoPlan, is_unset, plan_from_kwargs,
                               segment_resolved, segment_view)


def _leaf_placement(leaf):
    """Normalized device placement of one leaf, for the AOT fingerprint.

    An AOT executable is pinned to concrete devices; calling it with
    arguments committed elsewhere (a non-zero mesh shard, a multi-device
    submesh) is an error, so placement must be part of the dispatch
    fingerprint. Residence on the default device alone normalizes to
    ``None`` — the same value an abstract warmup struct (no sharding)
    fingerprints to — so the pre-mesh solo path and shard 0 of a
    ``dp=1`` mesh both hit the warmed executable, while sibling shards
    fall back to the jitted path (shared trace, per-shard compile)."""
    sharding = getattr(leaf, "sharding", None)
    if sharding is None:
        return None
    ids = tuple(sorted(d.id for d in sharding.device_set))
    if ids == _DEFAULT_DEVICE_ID():
        return None
    return ids


def _DEFAULT_DEVICE_ID(_box=[]):
    if not _box:
        _box.append((jax.devices()[0].id,))
    return _box[0]


def _args_fingerprint(args) -> tuple:
    """Shape/dtype/treedef/placement identity of one step-call argument
    tuple.

    An AOT-compiled executable accepts exactly the avals (and devices) it
    was lowered for; the runner dispatches to it only when the live
    call's fingerprint matches the warmed one, falling back to the plain
    jitted path (which traces/compiles for the new shapes or placement)
    otherwise."""
    leaves, treedef = jax.tree_util.tree_flatten(args)
    return (str(treedef),
            tuple((tuple(l.shape), jax.numpy.dtype(l.dtype).name,
                   bool(getattr(l, "weak_type", False)), _leaf_placement(l))
                  for l in leaves))


class _AttributionFrame:
    """Per-thread trace counter yielded by ``CompiledRunnerCache.attribution``."""

    __slots__ = ("count",)

    def __init__(self):
        self.count = 0


class _Runner:
    """One cache entry: the jitted step plus an optional AOT-compiled
    executable installed by :meth:`CompiledRunnerCache.warmup`.

    Calls whose argument fingerprint matches the warmed one run the
    pre-compiled executable directly — ``jax.jit``'s own dispatch would
    re-COMPILE on its first call even though the trace (jaxpr) is shared,
    so without this indirection warmup would only remove trace cost, not
    compile cost. Any other shapes fall through to the jitted path."""

    # __weakref__: jax.eval_shape/make_jaxpr weakref the callable they
    # trace (the trace audit and schedule tests trace runners abstractly)
    __slots__ = ("jitted", "aot_fp", "aot_exe", "_cache", "__weakref__")

    def __init__(self, jitted, cache):
        self.jitted = jitted
        self.aot_fp = None
        self.aot_exe = None
        self._cache = cache

    def __call__(self, *args):
        exe = self.aot_exe
        if exe is not None and self.aot_fp == _args_fingerprint(args):
            self._cache._count_aot(hit=True)
            return exe(*args)
        if exe is not None:
            self._cache._count_aot(hit=False)
        return self.jitted(*args)


def cfg_signature(cfg) -> tuple:
    """Hashable signature of a model config dataclass (e.g. DiTCfg)."""
    if dataclasses.is_dataclass(cfg):
        return (type(cfg).__name__,) + dataclasses.astuple(cfg)
    return (type(cfg).__name__, repr(cfg))


@dataclasses.dataclass(frozen=True)
class RunnerKey:
    cfg_sig: tuple
    mode_sig: tuple
    plan_sig: tuple  # DittoPlan.cache_sig(), ordered — see accessors below
    bucket: int | None = None
    # (sampler update, steps) of a segment loop; None for the per-step runner
    loop: tuple | None = None

    # ------------------------------------------------- plan_sig accessors
    # plan_sig's field order is DittoPlan.cache_sig()'s stable contract
    @property
    def block(self) -> int:
        return self.plan_sig[0]

    @property
    def interpret(self) -> bool:
        return self.plan_sig[1]

    @property
    def collect_stats(self) -> bool:
        return self.plan_sig[2]

    @property
    def low_bits(self) -> int:
        return self.plan_sig[3]

    @property
    def fused(self) -> bool:
        return self.plan_sig[4]

    @property
    def mesh(self) -> tuple | None:
        """``(mesh_devices, mesh_axis)`` for a sharded runner, else None."""
        return self.plan_sig[5]


class CompiledRunnerCache:
    """Trace-once store of jitted compiled-runner step functions.

    ``step_for`` is the whole API surface the runner needs: it returns the
    cached jitted step for the key, building (but not yet tracing — jax
    traces lazily on first call per shape) it on a miss. ``trace_counts``
    records how many times XLA actually traced each key's step; under
    batch bucketing this stays at 1 per (key, bucket) no matter how many
    batches are served.

    Thread-safe: the serving layer may run batches from multiple request
    threads against one shared cache.
    """

    def __init__(self):
        self._steps: dict[RunnerKey, _Runner] = {}
        self.trace_counts: dict[RunnerKey, int] = {}
        self.hits = 0
        self.misses = 0
        self.aot_hits = 0
        self.aot_misses = 0
        self._lock = threading.RLock()
        self._tls = threading.local()  # per-thread attribution frames

    # ------------------------------------------------------- attribution
    def _attr_frames(self) -> list:
        frames = getattr(self._tls, "frames", None)
        if frames is None:
            frames = self._tls.frames = []
        return frames

    @contextlib.contextmanager
    def attribution(self):
        """Count the XLA traces THIS THREAD causes inside the block.

        Tracing runs on the thread that first calls a jitted step, so a
        per-thread counter attributes each trace to the serve call that
        actually paid for it. The old before/after reads of the shared
        ``n_traces`` misattributed traces across threads sharing one
        cache (the documented deployment shape). Yields an object with a
        ``count`` attribute; nested contexts each see their own traces."""
        frame = _AttributionFrame()
        frames = self._attr_frames()
        frames.append(frame)
        try:
            yield frame
        finally:
            frames.remove(frame)

    def _count_trace(self, key: RunnerKey) -> None:
        with self._lock:
            self.trace_counts[key] = self.trace_counts.get(key, 0) + 1
        for frame in self._attr_frames():
            frame.count += 1

    def _count_aot(self, *, hit: bool) -> None:
        with self._lock:
            if hit:
                self.aot_hits += 1
            else:
                self.aot_misses += 1

    # ------------------------------------------------------------ resolve
    @staticmethod
    def _resolve(site: str, modes, plan: DittoPlan | None, bucket, extra, legacy
                 ) -> tuple[DittoPlan, int | None, tuple]:
        """(plan | legacy kwargs + extra) -> (plan, bucket). The legacy
        ``extra`` was always the ``(steps, bucket)`` pair; steps moved
        onto the plan and bucket became a first-class key field. A
        constant ``PlanSchedule`` collapses to its bare plan here — the
        SAME RunnerKey, zero new traces — while a multi-segment schedule
        is rejected (one key = one segment's lowering; the denoise loop
        resolves segments before reaching the cache)."""
        steps = UNSET
        if not is_unset(extra):
            extra = tuple(extra)
            if len(extra) not in (0, 2):
                raise TypeError(
                    f"{site}: legacy extra must be (steps, bucket), got {extra!r}")
            if extra:
                steps, bucket = extra
        plan = segment_resolved(plan_from_kwargs(site, plan, steps=steps, **legacy))
        mode_sig = tuple(sorted(modes.items())) if isinstance(modes, dict) else tuple(modes)
        return plan, bucket, mode_sig

    # ------------------------------------------------------------------ api
    def key_for(self, cfg, modes: dict[str, str] | tuple, plan: DittoPlan | None = None,
                *, bucket: int | None = None, block=UNSET, interpret=UNSET,
                collect_stats=UNSET, low_bits=UNSET, fused=UNSET,
                extra=UNSET) -> RunnerKey:
        plan, bucket, mode_sig = self._resolve(
            "serve.CompiledRunnerCache.key_for", modes, plan, bucket, extra,
            dict(block=block, interpret=interpret, collect_stats=collect_stats,
                 low_bits=low_bits, fused=fused))
        return RunnerKey(cfg_signature(cfg), mode_sig, plan.cache_sig(), bucket)

    def step_for(self, cfg, modes: dict[str, str], plan: DittoPlan | None = None,
                 *, bucket: int | None = None, block=UNSET, interpret=UNSET,
                 collect_stats=UNSET, low_bits=UNSET, fused=UNSET,
                 extra=UNSET) -> Callable:
        """Jitted ``step(dparams, mparams, state, latents, t, labels)`` for
        the key; traced at most once per (key, input shapes)."""
        plan, bucket, mode_sig = self._resolve(
            "serve.CompiledRunnerCache.step_for", modes, plan, bucket, extra,
            dict(block=block, interpret=interpret, collect_stats=collect_stats,
                 low_bits=low_bits, fused=fused))
        key = RunnerKey(cfg_signature(cfg), mode_sig, plan.cache_sig(), bucket)
        with self._lock:
            if key in self._steps:
                self.hits += 1
                return self._steps[key]
            raw = dit_runner.make_step_fn(cfg, modes, plan)

            def counting_step(*args):
                # executes only while jax is TRACING (jit caches the jaxpr
                # afterwards), so this counts compilations, not calls —
                # and attributes them to the tracing thread's open
                # attribution frames (see ``attribution``)
                self._count_trace(key)
                return raw(*args)

            return self._add(key, jax.jit(counting_step))

    def loop_for(self, cfg, modes: dict[str, str], plan: DittoPlan, *, update,
                 length: int, bucket: int | None = None) -> Callable:
        """Jitted ``denoise_loop(dparams, mparams, state, latents, ts,
        labels, update)`` (``dit_runner.make_loop_fn``) running ``length``
        steps, keyed like :meth:`step_for` plus the sampler's ``update``
        and ``length``. The state argument is donated: the loop's carry
        takes its buffers, so the state is not held twice."""
        plan, bucket, mode_sig = self._resolve(
            "serve.CompiledRunnerCache.loop_for", modes, plan, bucket, UNSET, {})
        fn = update.func
        key = RunnerKey(cfg_signature(cfg), mode_sig, plan.cache_sig(), bucket,
                        loop=(f"{fn.__module__}.{fn.__qualname__}", length))
        with self._lock:
            if key in self._steps:
                self.hits += 1
                return self._steps[key]
            raw = dit_runner.make_loop_fn(cfg, modes, plan)

            def denoise_loop(*args):  # the XLA module's name: not a "step"
                self._count_trace(key)  # while tracing, as in step_for
                return raw(*args)

            return self._add(key, jax.jit(denoise_loop, donate_argnums=2))

    def _add(self, key: RunnerKey, jitted) -> _Runner:
        """Register a miss's jitted function (the lock is held)."""
        self.misses += 1
        runner = _Runner(jitted, self)
        self._steps[key] = runner
        self.trace_counts.setdefault(key, 0)
        return runner

    # ---------------------------------------------------------------- warmup
    def warmup(self, cfg, modes: dict[str, str] | tuple, plans, buckets,
               *, labels: bool = True, params=None, tail=None) -> dict:
        """AOT-compile the bucket ladder: one ``jax.jit(...).lower(...)
        .compile()`` per (segment plan, bucket), so the first REAL request
        of each key pays neither trace nor compile cost.

        ``tail=(update, first)`` compiles, instead of the per-step
        executable, the segment loop each segment runs over the compiled
        tail: the sampler's ``update`` (``diffusion.loop_update``) over
        the segment's steps from ``first`` (the first compiled step) on;
        a segment wholly inside the eager prefix compiles nothing.

        ``plans`` is an iterable of :class:`DittoPlan`/``PlanSchedule``
        (a schedule warms every distinct segment sig); ``buckets`` the
        batch sizes to pre-compile (typically the full power-of-two
        ladder up to ``max_batch``). Inputs are abstract
        ``ShapeDtypeStruct`` trees mirroring the runtime call exactly —
        no weights are materialized and no kernel executes; ``labels``
        selects the class-conditional argument shape (must match real
        requests' label presence or the warmed executable won't be hit).
        ``params`` (the live model param tree) pins the abstract mparams
        to the SAME pytree structure the runtime passes — trees of equal
        shapes but different node types (freshly-``init``-ed Param
        wrappers vs checkpoint-restored plain dicts) fingerprint
        differently, and a mismatch silently turns every warmed key into
        an ``aot_miss``; omit it only when the runtime params are known
        to be freshly initialized.
        The compiled executable is installed on the cache entry; later
        calls with matching shapes dispatch to it directly (``jax.jit``
        would otherwise re-compile on its own first call despite the
        shared trace). Returns ``{"aot_compiled": n, "traces": m}``.
        """
        from ..analysis.trace_audit import abstract_inputs, abstract_state

        compiled = 0
        traces0 = self.n_traces
        states: dict[int, Any] = {}
        # identity eval_shape: the struct tree with the runtime's treedef
        real_mparams = (None if params is None
                        else jax.eval_shape(lambda p: p, params))
        if tail is not None:
            update, first = tail
            abstract_update = jax.eval_shape(lambda u: u, update)
        for plan in plans:
            for lo, hi, seg in segment_view(plan):
                length = None if tail is None else hi - max(lo, first)
                if length is not None and length <= 0:
                    continue
                for bucket in buckets:
                    if length is None:
                        fn = self.step_for(cfg, modes, seg, bucket=bucket)
                    else:
                        fn = self.loop_for(cfg, modes, seg, bucket=bucket,
                                           update=update, length=length)
                    if fn.aot_exe is not None:
                        continue
                    dparams, mparams, lat, t, lab = abstract_inputs(cfg, bucket)
                    if real_mparams is not None:
                        mparams = real_mparams
                    if bucket not in states:
                        states[bucket] = abstract_state(cfg, bucket)
                    args = (dparams, mparams, states[bucket], lat, t,
                            lab if labels else None)
                    if length is not None:
                        ts = jax.ShapeDtypeStruct((length + 1,), jax.numpy.int32)
                        args = args[:4] + (ts, args[5], abstract_update)
                    fn.aot_exe = fn.jitted.lower(*args).compile()
                    fn.aot_fp = _args_fingerprint(args)
                    compiled += 1
        return {"aot_compiled": compiled, "traces": self.n_traces - traces0}

    # ---------------------------------------------------------------- stats
    @property
    def n_traces(self) -> int:
        return sum(self.trace_counts.values())

    def __len__(self) -> int:
        return len(self._steps)

    def executables(self) -> dict:
        """``{RunnerKey: executable}`` for the steps :meth:`warmup` compiled."""
        with self._lock:
            return {k: r.aot_exe for k, r in self._steps.items() if r.aot_exe is not None}

    def stats(self) -> dict[str, Any]:
        return {"runners": len(self._steps), "traces": self.n_traces,
                "hits": self.hits, "misses": self.misses,
                "aot_hits": self.aot_hits, "aot_misses": self.aot_misses}

    def clear(self) -> None:
        with self._lock:
            self._steps.clear()
            self.trace_counts.clear()
            self.hits = self.misses = 0
            self.aot_hits = self.aot_misses = 0
