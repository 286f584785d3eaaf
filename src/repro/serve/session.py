"""ServeSession: the stateful front-end of the persistent serving runtime.

One session owns the model (params + config + schedule), a
:class:`CompiledRunnerCache`, and a default :class:`DittoPlan`. Each
``serve(x, labels)`` call is one request batch; the session

  1. chunks oversized requests to ``plan.max_batch``,
  2. pads each chunk up to its power-of-two batch bucket
     (:mod:`repro.serve.bucketing` — replication padding, bit-exact),
  3. runs the two-phase Ditto pass (eager calibration + Defo decision,
     then the jitted Pallas steps) through ``sim.harness.serve_records``
     with the shared runner cache, and
  4. slices the sample back to the true batch.

``serve(..., plan=...)`` overrides the session plan for one request while
still sharing the session's runner cache — the per-request-plan hook the
continuous-batching scheduler (:mod:`repro.serve.scheduler`) builds on.
Across a request stream this turns one-XLA-trace-per-batch into
one-trace-per-(mode-signature, bucket): the first batch of a bucket pays
trace + compile, every later batch replays the cached runner.

The pre-plan constructor keywords (``steps=``, ``low_bits=``, ...) are a
deprecated shim that builds the equivalent plan and warns once.

Each call is one ``serve.dispatch`` host span, and each chunk reports the
engine's counters (``ChunkResult.counters``; see docs/architecture.md,
"Observability"), summed into :meth:`ServeSession.stats`.
"""
from __future__ import annotations

import dataclasses
import itertools
import threading
import time
from typing import Any

import jax

from ..core import spans
from ..core.ditto.plan import (UNSET, DittoPlan, PlanSchedule, plan_from_kwargs,
                               require_native_lowering)
from ..sim import harness
from . import faults
from .bucketing import bucket_for
from .cache import CompiledRunnerCache

#: scalar counters a served chunk reports and ``ServeSession.stats()`` sums
STEP_COUNTERS = ("host_reads", "eager_steps", "compiled_steps", "looped_steps")
#: a layer whose call-site name holds this runs on an MMDiT text stream
TEXT_STREAM = ".txt."


@dataclasses.dataclass
class ChunkResult:
    """One served chunk (<= max_batch requests, one bucket)."""
    sample: jax.Array  # (true chunk batch, ...)
    records: list
    engine: Any
    batch: int
    bucket: int | None  # padded dispatch size; None = eager (unbucketed) chunk
    wall_s: float
    traces_delta: int  # new XLA traces this chunk caused (0 = full cache hit)
    # the engine's counters (DittoEngine.counters): host_reads, eager_steps,
    # compiled_steps, looped_steps, and tile_hist {diff layer: (zero, low, full)}
    counters: dict = dataclasses.field(default_factory=dict)

    @property
    def pad_rows(self) -> int:
        """Wasted (replicated) batch rows this chunk computed."""
        return 0 if self.bucket is None else self.bucket - self.batch


@dataclasses.dataclass
class ServeResult:
    sample: jax.Array  # (true request batch, ...) — chunks re-concatenated
    chunks: list[ChunkResult]

    @property
    def records(self) -> list:
        return [r for c in self.chunks for r in c.records]

    @property
    def wall_s(self) -> float:
        return sum(c.wall_s for c in self.chunks)

    @property
    def traces_delta(self) -> int:
        return sum(c.traces_delta for c in self.chunks)

    @property
    def pad_rows(self) -> int:
        return sum(c.pad_rows for c in self.chunks)

    @property
    def counters(self) -> dict:
        """The chunks' counters summed; ``tile_hist`` per layer."""
        out = dict.fromkeys(STEP_COUNTERS, 0)
        tiles: dict[str, tuple] = {}
        for c in self.chunks:
            for k in STEP_COUNTERS:
                out[k] += c.counters.get(k, 0)
            for name, h in c.counters.get("tile_hist", {}).items():
                tiles[name] = tuple(a + b for a, b in zip(tiles.get(name, (0, 0, 0)), h))
        out["tile_hist"] = tiles
        return out


class ServeSession:
    """Persistent compiled serving runtime for one model.

    ``plan`` is the session's default :class:`DittoPlan`; omitting it
    means ``DittoPlan()`` — the documented defaults (20-step ddim, defo
    policy, compiled serving), not an error. ``cache`` may be shared
    between sessions serving the same model (e.g. one per request
    thread) — the runner key includes the model-config signature, so
    distinct models never collide. ``plan.low_bits=4`` serves the packed-
    int4 low-tile path and ``plan.fused=True`` the single-pass fused
    kernel (both bit-identical samples); each is part of the runner key
    (``plan.cache_sig()``), so plans differing in either knob never share
    a trace even when they share one cache.

    ``plan`` may also be a :class:`repro.core.ditto.PlanSchedule` — per-
    timestep kernel config: the denoise loop partitions by segment, each
    distinct segment sig compiles once into the shared cache, and a
    constant schedule reuses the bare plan's trace (same RunnerKey).
    """

    def __init__(self, params, cfg, sched, plan: DittoPlan | PlanSchedule | None = None, *,
                 cache: CompiledRunnerCache | None = None, mesh=None, steps=UNSET,
                 sampler=UNSET, policy=UNSET, compiled=UNSET, interpret=UNSET,
                 collect_stats=UNSET, block=UNSET, low_bits=UNSET, fused=UNSET,
                 max_batch=UNSET):
        # mesh: the concrete shard submesh this session dispatches onto
        # (mesh-aware schedulers run one session per shard). None + a
        # mesh-signed plan resolves a default mesh at dispatch time; the
        # params are committed (replicated) onto the submesh once here so
        # every dispatch finds them shard-local.
        self.plan = plan_from_kwargs("serve.ServeSession", plan, steps=steps,
                                     sampler=sampler, policy=policy, compiled=compiled,
                                     interpret=interpret, collect_stats=collect_stats,
                                     block=block, low_bits=low_bits, fused=fused,
                                     max_batch=max_batch)
        require_native_lowering(self.plan)
        self.mesh = mesh
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            params = jax.device_put(params, NamedSharding(mesh, PartitionSpec()))
        self.params = params
        self.cfg = cfg
        self.sched = sched
        self.cache = cache if cache is not None else CompiledRunnerCache()
        self.batches_served = 0
        self.requests_served = 0
        self.watchdog_events = 0  # re-anchor steps across all served chunks
        self.counters = dict.fromkeys(STEP_COUNTERS, 0)
        self.tiles = [0, 0, 0]  # (zero, low, full) diff tiles, all layers
        self.tiles_txt = [0, 0, 0]  # the same, layers of a text stream (".txt.")
        self._dispatch_ids = itertools.count()  # serve.dispatch span index
        # sessions are documented as shareable across request threads (one
        # shared cache); bare += on the counters would drop increments
        self._stats_lock = threading.Lock()

    # ------------------------------------------------------------------ api
    def serve(self, x: jax.Array, labels=None, *,
              plan: DittoPlan | PlanSchedule | None = None) -> ServeResult:
        """Serve one request batch; returns the sample at the TRUE batch
        size plus per-chunk records/engines for the design-point simulator.
        ``plan`` (a DittoPlan or PlanSchedule) overrides the session
        default for this request only (same shared runner cache)."""
        fault = faults.fire("session.serve")
        if fault is not None:
            faults.perform(fault)
        if plan is None:
            plan = self.plan
        else:
            require_native_lowering(plan)
        n = x.shape[0]
        chunks: list[ChunkResult] = []
        samples = []
        bounds = [(lo, min(lo + plan.max_batch, n)) for lo in range(0, n, plan.max_batch)]
        buckets = ",".join(str(bucket_for(hi - lo, max_batch=plan.max_batch)
                               if plan.compiled else hi - lo) for lo, hi in bounds)
        with spans.dispatch(next(self._dispatch_ids), rows=n, buckets=buckets):
            for lo, hi in bounds:
                lc = None if labels is None else labels[lo:hi]
                chunks.append(self._serve_chunk(x[lo:hi], lc, plan))
                samples.append(chunks[-1].sample)
        events = sum(
            len(getattr(c.engine, "watchdog_events", ()) or ()) for c in chunks)
        sample = samples[0] if len(samples) == 1 else jax.numpy.concatenate(samples, axis=0)
        result = ServeResult(sample=sample, chunks=chunks)
        counters = result.counters
        with self._stats_lock:
            self.batches_served += 1
            self.requests_served += n
            self.watchdog_events += events
            for k in STEP_COUNTERS:
                self.counters[k] += counters[k]
            for name, h in counters["tile_hist"].items():
                self.tiles = [a + b for a, b in zip(self.tiles, h)]
                if TEXT_STREAM in name:
                    self.tiles_txt = [a + b for a, b in zip(self.tiles_txt, h)]
        return result

    def _serve_chunk(self, x, labels, plan: DittoPlan | PlanSchedule) -> ChunkResult:
        b = x.shape[0]
        # eager chunks run unbucketed (no trace to share) — bucket=None,
        # so pad accounting and the serve log can't claim a padded dispatch
        bucket = bucket_for(b, max_batch=plan.max_batch) if plan.compiled else None
        t0 = time.monotonic()
        # per-thread attribution: traces_delta counts the traces THIS call's
        # thread caused, not whatever other threads did to the shared
        # cache.n_traces between two reads
        # mesh only when set: meshless sessions keep the exact pre-mesh
        # call signature (tests duck-type serve_records without a mesh kwarg)
        mesh_kw = {} if self.mesh is None else {"mesh": self.mesh}
        with self.cache.attribution() as att:
            records, sample, eng = harness.serve_records(
                self.params, self.cfg, self.sched, x, labels, plan,
                runner_cache=self.cache, bucket=bucket, **mesh_kw,
            )
            with spans.span("serve.block"):  # the host waiting on the device
                jax.block_until_ready(sample)
        wall = time.monotonic() - t0
        counters = eng.counters() if hasattr(eng, "counters") else {}
        return ChunkResult(sample=sample, records=records, engine=eng, batch=b,
                           bucket=bucket, wall_s=wall, traces_delta=att.count,
                           counters=counters)

    # ---------------------------------------------------------------- stats
    def stats(self) -> dict:
        with self._stats_lock:
            return {"batches": self.batches_served, "requests": self.requests_served,
                    "watchdog_events": self.watchdog_events, **self.counters,
                    "tiles": list(self.tiles), "tiles_txt": list(self.tiles_txt),
                    **self.cache.stats()}
