"""Compiled execution pass of the DittoEngine (paper §IV-C deployment).

The eager :class:`~repro.core.ditto.engine.DittoEngine` is the
*calibration* pass: it quantizes with per-layer scales held from step 1,
collects the class statistics / cycle records Defo needs, and decides each
layer's mode after the step-2 diff probe. Everything it bakes in —
activation scales, weight q-tensors, the per-layer mode — is static from
then on, so the remaining denoising steps can run as ONE ``jax.jit``-able
function in which:

  act   layers route through the ``int8_matmul`` Pallas kernel (the ITC
        baseline Compute Unit);
  diff  layers run ``diff_encode`` -> ``ditto_diff_matmul``, so zero tiles
        are actually skipped on-device (``@pl.when`` gates the MXU dot)
        instead of only being priced in the cost model; with ``low_bits=4``
        class-1 (low) tiles additionally execute the packed-int4 branch —
        bit-identical, since the class verdict bounds |Δ| inside the exact
        pack/unpack range — and the measured per-step tile-class histogram
        (``tile_hist`` in the aux pytree) feeds the pricing; with
        ``fused=True`` they run the single-pass fused kernel instead
        (``kernels.fused_step``: encode+Δ-cache in one pass, skipped
        tiles' DMAs elided via scalar-prefetch hold maps, y_prev as an
        epilogue) — bit-identical, different lowering;
  spatial layers (Defo+) execute the direct GEMM — exactly what the eager
        spatial branch computes — via ``int8_matmul``; their row-delta
        statistics are still reduced for the records.

Configuration arrives as ONE :class:`~repro.core.ditto.DittoPlan`
(``linear_apply(..., plan=plan)``): the kernel lowering knobs it carries
are the same fields ``RunnerKey`` keys traces by, so an op and its cache
entry can never disagree about what was lowered.

Token and feature dims are zero-padded to the 128-tile grid inside the
kernels' ops wrappers; padding is exact in the int32 domain, so the
compiled pass is bit-identical to the eager engine (property-tested in
tests/test_compiled_engine.py).

Per-layer temporal state (x_prev int8, y_prev int32, attention operands)
is threaded functionally as a pytree so the step function stays pure; the
batched attention identity S_t = S_prev + Q_t ΔK^T + ΔQ K_prev^T runs the
two sub-operations through the same diff kernel under ``lax.scan`` over
the (batch x heads) leading dim (one kernel trace, not one per element).

With ``collect_stats=True`` the step also reduces zero/low/full class
fractions on-device into a per-layer aux pytree; the host engine
synthesizes cost-model records from them (``record_compiled_step``) so the
design-point simulator keeps working across compiled steps. Set it False
for the pure serving fast path. Either way each diff layer's measured
tile-class histogram (``tile_hist``) is in the aux pytree, and the step
adds it to the state's :data:`TILE_TOTALS` leaf, a device-side counter the
host reads once per sample. The step returns its aux packed
(:func:`pack_stats`): one float32 vector of every class fraction and one
int32 vector of every ``tile_hist`` count, laid out by the aux pytree's own
flattened structure, so the host reads a step's statistics with one
transfer instead of one per scalar.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ...kernels import ops
from . import classify, quant
from .engine import DittoEngine
from .plan import UNSET, DittoPlan, plan_from_kwargs, segment_resolved

#: state key of the (layers, 3) int32 running (zero, low, full) tile totals,
#: one row per layer in sorted name order
TILE_TOTALS = "tile_totals"


@jax.tree_util.register_pytree_node_class
class PackedStats:
    """One compiled step's per-layer aux pytree in two flat arrays.

    ``fracs`` (float32) holds every floating leaf — the class fractions —
    and ``tiles`` (int32) every integer leaf — the ``tile_hist`` counts —
    each in the aux pytree's flattened order. ``layout`` is that pytree's
    treedef and each leaf's (integer?, shape): static pytree data, so it
    leaves ``jax.jit`` with the arrays and always describes the step that
    produced them. :meth:`unpack` rebuilds the aux pytree, with the same
    values, from the host copy that ``jax.device_get`` returns."""

    def __init__(self, fracs, tiles, layout):
        self.fracs, self.tiles, self.layout = fracs, tiles, layout

    def tree_flatten(self):
        return (self.fracs, self.tiles), self.layout

    @classmethod
    def tree_unflatten(cls, layout, children):
        return cls(*children, layout)

    def unpack(self) -> dict:
        treedef, specs = self.layout
        offsets = {False: 0, True: 0}
        leaves = []
        for is_int, shape in specs:
            vec = self.tiles if is_int else self.fracs
            off, size = offsets[is_int], math.prod(shape)
            leaves.append(vec[off:off + size].reshape(shape))
            offsets[is_int] = off + size
        return jax.tree_util.tree_unflatten(treedef, leaves)

    def unstack(self) -> list:
        """One :class:`PackedStats` per step of a stack that a segment loop
        returns (``fracs``/``tiles`` with a leading step axis)."""
        return [PackedStats(f, t, self.layout) for f, t in zip(self.fracs, self.tiles)]


def pack_stats(aux: dict) -> PackedStats:
    """Pack a step's aux pytree into a :class:`PackedStats` (inside the
    jitted step). Exact: every fraction is a float32 scalar and every
    tile count an int32."""
    leaves, treedef = jax.tree_util.tree_flatten(aux)
    leaves = [jnp.asarray(v) for v in leaves]
    is_int = [bool(jnp.issubdtype(v.dtype, jnp.integer)) for v in leaves]

    def flat(kind, dtype):
        parts = [v.reshape(-1).astype(dtype) for v, i in zip(leaves, is_int) if i == kind]
        return jnp.concatenate(parts) if parts else jnp.zeros((0,), dtype)

    layout = (treedef, tuple((i, v.shape) for v, i in zip(leaves, is_int)))
    return PackedStats(flat(False, jnp.float32), flat(True, jnp.int32), layout)


def _class_fractions(d: jax.Array) -> tuple:
    """(zero, low, full) fractions of an int-domain Δ tensor, on-device.

    Matches classify.element_classes bit-for-bit (same reductions).
    """
    c = classify.element_classes(d)
    return (c["zero"], c["low"], c["full"])


def _tile_hist(classes: jax.Array) -> jax.Array:
    """(n_zero, n_low, n_full) int32 histogram of a diff_encode class map —
    the tiles the kernel actually skipped / narrowed / ran at int8."""
    c = classes.reshape(-1)
    return jnp.stack([jnp.sum(c == 0), jnp.sum(c == 1), jnp.sum(c == 2)])


def _act_fractions(q: jax.Array) -> tuple:
    """cls_act triple of the eager engine: (zero, 0, nonzero)."""
    c = classify.element_classes(q)
    return (c["zero"], 0.0, c["low"] + c["full"])


def _spatial_fractions(q2: jax.Array) -> tuple:
    """cls_spatial triple of the eager oracle: row-delta fractions with the
    full-precision first row folded in at weight 1/t."""
    t = q2.shape[0]
    z, l, f = _class_fractions(classify.spatial_diff(q2, axis=0)[1:])
    w0 = 1.0 / t
    return (z * (1 - w0), l * (1 - w0), f * (1 - w0) + w0)


def linear_apply(p: dict, mode: str, x: jax.Array, st: dict, *,
                 plan: DittoPlan) -> tuple[jax.Array, dict, dict]:
    """Pure compiled linear op: params in, state in -> (y fp32, state, aux).

    Functional core of :meth:`CompiledDittoEngine.linear`. Everything
    data-dependent — weight q-tensors, calibrated scales, temporal state —
    arrives as arguments rather than closure constants, so one traced step
    function can be REUSED across serve batches (repro.serve's runner
    cache); only ``mode`` and the plan's kernel config are trace-static.
    Bit-identical int32 y_prev to the eager path for every mode.
    ``plan`` must be segment-resolved (a constant ``PlanSchedule`` is
    accepted and collapses; a multi-segment one raises here).
    """
    plan = segment_resolved(plan)
    collect_stats = plan.collect_stats
    x2 = x.reshape(-1, x.shape[-1])
    n = p["w_q"].shape[1]
    q_t = quant.quantize(x2, p["x_scale"])

    aux: dict = {}
    if mode == "diff":
        y_i32, classes = ops.ditto_linear_step(q_t, st["x_prev"], p["w_q"], st["y_prev"],
                                               plan=plan)
        aux["tile_hist"] = _tile_hist(classes)
    else:  # act, and spatial (whose eager branch computes the direct GEMM)
        y_i32 = ops.int8_act_matmul(q_t, p["w_q"], plan=plan)
    if collect_stats:
        # executed-mode stats for pricing this step, plus candidate
        # temporal/spatial fractions for every layer so the simulator
        # can re-price other designs' mode choices at scaled dims
        if mode == "spatial":
            aux["cls_diff"] = _class_fractions(classify.spatial_diff(q_t, axis=0)[1:])
        else:
            d = q_t.astype(jnp.int16) - st["x_prev"].astype(jnp.int16)
            aux["cls_diff"] = _class_fractions(d)
        if q_t.shape[0] > 1:
            aux["cls_spatial"] = _spatial_fractions(q_t)
        aux["cls_act"] = _act_fractions(q_t)

    new_st = dict(x_prev=q_t, y_prev=y_i32)
    y = y_i32.astype(jnp.float32) * p["x_scale"] * p["w_scale"][None, :]
    if p["bias"] is not None:
        y = y + p["bias"]
    return y.reshape(x.shape[:-1] + (n,)), new_st, aux


def attention_apply(p: dict, mode: str, a: jax.Array, b: jax.Array, st: dict, *,
                    plan: DittoPlan) -> tuple[jax.Array, dict, dict]:
    """Pure compiled attention matmul (a @ b^T per leading-dim element).

    Functional core of :meth:`CompiledDittoEngine.attention_matmul`: diff
    mode composes the paper's two-sub-op identity from the diff kernel
    (ops.attention_delta), act mode runs int8_matmul; ``lax.scan`` over the
    (batch x heads) leading dim keeps one kernel trace. Params/state are
    arguments so the trace is shareable across batches. ``plan`` must be
    segment-resolved, exactly as in :func:`linear_apply`.
    """
    plan = segment_resolved(plan)
    collect_stats = plan.collect_stats
    lead = a.shape[:-2]
    m, d_ = a.shape[-2], a.shape[-1]
    n = b.shape[-2]
    a2 = a.reshape(-1, m, d_)
    b2 = b.reshape(-1, n, d_)
    qa = quant.quantize(a2, p["a_scale"])
    qb = quant.quantize(b2, p["b_scale"])

    aux: dict = {}
    if mode == "diff":
        def body(c, ins):
            qa_i, qb_i, ap_i, bp_i, yp_i = ins
            y_i, (cls_dk, cls_dq) = ops.attention_delta(qa_i, ap_i, qb_i, bp_i, yp_i,
                                                        plan=plan)
            return c, (y_i, _tile_hist(cls_dk) + _tile_hist(cls_dq))

        xs = (qa, qb, st["a_prev"], st["b_prev"], st["y_prev"])
        _, (y_i32, hists) = jax.lax.scan(body, 0, xs)
        aux["tile_hist"] = hists.sum(axis=0)  # both sub-ops, all scan elems
    else:
        def body(c, ins):
            qa_i, qb_i = ins
            return c, ops.int8_act_matmul(qa_i, qb_i.T, plan=plan)

        _, y_i32 = jax.lax.scan(body, 0, (qa, qb))
    if collect_stats:
        da = qa.astype(jnp.int16) - st["a_prev"].astype(jnp.int16)
        db = qb.astype(jnp.int16) - st["b_prev"].astype(jnp.int16)
        aux["cls_diff"] = _class_fractions(jnp.concatenate([da.reshape(-1), db.reshape(-1)]))
        aux["cls_act"] = _act_fractions(jnp.concatenate([qa.reshape(-1), qb.reshape(-1)]))

    new_st = dict(a_prev=qa, b_prev=qb, y_prev=y_i32)
    y = y_i32.astype(jnp.float32) * p["a_scale"] * p["b_scale"]
    return y.reshape(lead + (m, n)), new_st, aux


def _placed_like_step_output(x: jax.Array, ref: jax.Array) -> jax.Array:
    """``x`` placed where the jitted step returns an unsharded leaf when its
    state is placed like ``ref``: replicated on ``ref``'s mesh if ``ref`` is
    committed, else left uncommitted. The first compiled step then sees the
    same argument placements as every later one, so it compiles once."""
    if not ref.committed:
        return x
    sharding = ref.sharding
    if isinstance(sharding, jax.sharding.NamedSharding):
        sharding = jax.sharding.NamedSharding(sharding.mesh, jax.sharding.PartitionSpec())
    return jax.device_put(x, sharding)


class CompiledDittoEngine:
    """Per-layer compiled ops with static modes, built from a calibrated
    eager engine. All methods are pure (state in, state out) and
    jit-traceable; mode selection happens at trace time."""

    def __init__(self, engine: DittoEngine, *, plan: DittoPlan | None = None,
                 interpret=UNSET, block=UNSET, collect_stats=UNSET, low_bits=UNSET,
                 fused=UNSET):
        if not engine.ready_for_compiled():
            raise ValueError(
                "engine not calibrated: run >= 1 eager step (>= 2 for defo policies, "
                "whose mode decision lands after the step-2 diff probe) before "
                f"compiling (step_idx={engine.step_idx}, decided={engine._decided})")
        # plan construction validates low_bits/block once for the whole pass;
        # one compiled engine serves one segment's lowering
        self.plan = segment_resolved(plan_from_kwargs(
            "core.ditto.CompiledDittoEngine", plan, interpret=interpret,
            block=block, collect_stats=collect_stats, low_bits=low_bits,
            fused=fused))
        self.engine = engine
        self.modes = engine.compiled_modes()
        self.meta = engine.meta
        self.params: dict[str, dict] = {}
        for name, st in engine.layers.items():
            if st.w is not None:
                self.params[name] = dict(w_q=st.w.q, w_scale=st.w.scale,
                                         bias=st.bias, x_scale=st.x_scale)
            else:
                self.params[name] = dict(a_scale=st.a_scale, b_scale=st.b_scale)

    # ---------------------------------------------------------------- state
    def init_state(self) -> dict:
        """Initial temporal state = the eager engine's state after its last
        calibration step (int8 x_prev / int32 y_prev per layer), plus zeroed
        :data:`TILE_TOTALS`."""
        state: dict = {}
        for name, st in self.engine.layers.items():
            if st.w is not None:
                state[name] = dict(x_prev=st.x_prev, y_prev=st.y_prev)
            else:
                state[name] = dict(a_prev=st.a_prev, b_prev=st.b_prev, y_prev=st.y_prev)
        state[TILE_TOTALS] = _placed_like_step_output(
            jnp.zeros((len(self.engine.layers), 3), jnp.int32),
            jax.tree_util.tree_leaves(state)[0])
        return state

    # ------------------------------------------------- plan-field accessors
    @property
    def block(self) -> int:
        return self.plan.block

    @property
    def interpret(self) -> bool | None:
        return self.plan.interpret

    @property
    def collect_stats(self) -> bool:
        return self.plan.collect_stats

    @property
    def low_bits(self) -> int:
        return self.plan.low_bits

    @property
    def fused(self) -> bool:
        return self.plan.fused

    # --------------------------------------------------------------- linear
    def linear(self, name: str, x: jax.Array, st: dict) -> tuple[jax.Array, dict, dict]:
        """Mirror of DittoEngine.linear with the mode baked in statically.

        Returns (y fp32, new_state, aux). Bit-identical int32 y_prev to the
        eager path for every mode. Delegates to :func:`linear_apply`.
        """
        return linear_apply(self.params[name], self.modes[name], x, st, plan=self.plan)

    # ------------------------------------------------------------ attention
    def attention_matmul(self, name: str, a: jax.Array, b: jax.Array,
                         st: dict) -> tuple[jax.Array, dict, dict]:
        """Mirror of DittoEngine.attention_matmul: a @ b^T per leading-dim
        element, diff mode via the paper's two-sub-op identity composed
        from the diff kernel (ops.attention_delta), act mode via
        int8_matmul. lax.scan over the batch keeps one kernel trace.
        Delegates to :func:`attention_apply`."""
        return attention_apply(self.params[name], self.modes[name], a, b, st,
                               plan=self.plan)
