"""DiT denoiser executed through the DittoEngine (quantized serving path).

Mirrors repro.nn.dit.apply with every linear op routed through the engine.
``_dit_forward`` is the single source of truth for the block structure; it
takes the two engine ops as callables, so the eager calibration pass
(:class:`DittoDiT`) and the jit-compiled Pallas execution pass
(:class:`CompiledDittoDiT`) share the exact same forward — a structural
divergence between the two phases is impossible by construction.

``make_denoise_fn(..., plan)`` with ``plan.compiled=True`` runs eager
steps until the engine is calibrated (>= 1 step; for Defo policies, until
the step-2 decision), then hands the remaining denoising steps to the
compiled per-step function in which each layer's mode is a static
bake-in: act-mode layers hit the ``int8_matmul`` Pallas kernel, diff-mode
layers ``diff_encode`` -> ``ditto_diff_matmul`` (zero tiles skipped
on-device). The plan (one ``repro.core.ditto.DittoPlan``) carries every
knob; its ``cache_sig()`` is the runner-cache trace identity. fp32-mode
equivalence against nn.dit.apply is tested in tests/test_ditto_engine.py;
eager/compiled bit-identity in tests/test_compiled_engine.py.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ...distributed.sharding import constrain_batch
from ...kernels.common import DEFAULT_LOW_BITS
from ...nn import core as nncore
from ...nn import dit as dit_mod
from . import compiled as compiled_mod
from . import defo
from .compiled import CompiledDittoEngine
from .engine import DittoEngine, LayerMeta
from .plan import (EAGER_PLAN, UNSET, DittoPlan, PlanSchedule, is_unset,
                   plan_from_kwargs, segment_resolved)


def _resolve_legacy(site, plan, bucket, cache_extra, *, default=None, **legacy):
    """Map a deprecated (splatted kwargs + cache_extra) call onto
    (plan, bucket). The legacy ``cache_extra`` was always the
    ``(steps, padded batch)`` pair the old harness threaded into the
    runner-cache key; its components live on the plan (``steps``) and the
    key's ``bucket`` field now."""
    steps = UNSET
    if not is_unset(cache_extra):
        extra = tuple(cache_extra)
        if len(extra) == 2:
            steps, bucket = extra
        elif extra:  # () was the legacy signature's own default — allowed
            raise TypeError(
                f"{site}: legacy cache_extra must be (steps, bucket), got {extra!r}")
    plan = plan_from_kwargs(site, plan, default=default, steps=steps, **legacy)
    return plan, bucket


def _v(tree, *path):
    cur = tree
    for p in path:
        cur = cur[p]
    return np.asarray(nncore.val(cur))


def _cond_dense(p, c):
    """``nncore.dense`` over per-sample conditioning rows ``(B, d)``, with a
    lone row duplicated. XLA:TPU lowers a one-row f32 matmul as a VPU
    multiply-reduce (f32 products) but two or more rows on the MXU, and the
    two round differently: without this a sample served alone would not
    equal the same sample served in a batch. The barrier keeps the compiler
    from slicing the duplicate away before the matmul."""
    if c.shape[0] != 1:
        return nncore.dense(p, c)
    return jax.lax.optimization_barrier(nncore.dense(p, jnp.concatenate([c, c])))[:1]


def _dit_forward(params, cfg: dit_mod.DiTCfg, linear, attention, latents, t, labels):
    """One DiT forward with every quantized op injected.

    ``linear(name, x)`` and ``attention(name, a, b)`` are the engine ops —
    eager (stateful) or compiled (closures threading a state pytree).
    Patch embed / conditioning / norms / softmax stay fp32 (VPU-side ops).
    """
    b, hh, ww, ch = latents.shape
    pp = cfg.patch
    x = latents.reshape(b, hh // pp, pp, ww // pp, pp, ch)
    x = x.transpose(0, 1, 3, 2, 4, 5).reshape(b, cfg.n_tokens, cfg.patch_dim)
    x = nncore.dense(params["patch_embed"], x) + nncore.val(params["pos_embed"])[None]
    c = dit_mod.timestep_embedding(t, 256)
    c = _cond_dense(params["t_mlp2"], jax.nn.silu(_cond_dense(params["t_mlp1"], c)))
    if labels is not None and "label_embed" in params:
        c = c + nncore.val(params["label_embed"])[labels]
    c_act = jax.nn.silu(c)

    nh = cfg.n_heads
    hd = cfg.head_dim
    scale = 1.0 / math.sqrt(hd)
    for i in range(cfg.n_layers):
        bk = f"blk{i}"
        mod = linear(f"{bk}.mod", c_act)
        sh_a, sc_a, g_a, sh_m, sc_m, g_m = jnp.split(mod, 6, axis=-1)
        h = dit_mod._modulate(dit_mod._ln(x), sh_a, sc_a)
        q = linear(f"{bk}.wq", h).reshape(b, cfg.n_tokens, nh, hd)
        k = linear(f"{bk}.wk", h).reshape(b, cfg.n_tokens, nh, hd)
        v = linear(f"{bk}.wv", h).reshape(b, cfg.n_tokens, nh, hd)
        qf = q.transpose(0, 2, 1, 3).reshape(b * nh, cfg.n_tokens, hd)
        kf = k.transpose(0, 2, 1, 3).reshape(b * nh, cfg.n_tokens, hd)
        vf = v.transpose(0, 2, 1, 3).reshape(b * nh, cfg.n_tokens, hd)
        scores = attention(f"{bk}.qk", qf, kf) * scale
        probs = jax.nn.softmax(scores, axis=-1)
        av = attention(f"{bk}.pv", probs, vf.swapaxes(-1, -2))
        av = av.reshape(b, nh, cfg.n_tokens, hd).transpose(0, 2, 1, 3).reshape(b, cfg.n_tokens, nh * hd)
        a = linear(f"{bk}.wo", av)
        x = x + g_a[:, None, :] * a
        h = dit_mod._modulate(dit_mod._ln(x), sh_m, sc_m)
        hmid = jax.nn.gelu(linear(f"{bk}.wi", h))
        x = x + g_m[:, None, :] * linear(f"{bk}.wd", hmid)

    modf = _cond_dense(params["final_mod"], c_act)
    shift, scl = jnp.split(modf, 2, axis=-1)
    x = dit_mod._modulate(dit_mod._ln(x), shift, scl)
    x = linear("final.out", x)
    x = x.reshape(b, hh // pp, ww // pp, pp, pp, ch).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, hh, ww, ch)


class DittoDiT:
    """Eager calibration pass (per-layer python loop — each layer's
    execution mode may differ per step, which is the point of Defo).
    Weights are registered once from the same param tree used for
    training."""

    def __init__(self, params, cfg: dit_mod.DiTCfg, engine: DittoEngine):
        self.cfg = cfg
        self.engine = engine
        self.params = params
        metas = defo.analyze(defo.dit_graph(cfg.n_layers))
        blocks = params["blocks"]

        def blk(i, *path):
            cur = blocks
            for p in path:
                cur = cur[p]
            return np.asarray(nncore.val(cur))[i]

        for i in range(cfg.n_layers):
            b = f"blk{i}"
            engine.register_linear(metas[f"{b}.mod"], blk(i, "mod", "w"), blk(i, "mod", "b"))
            for nm, pth in (("wq", ("attn", "wq")), ("wk", ("attn", "wk")), ("wv", ("attn", "wv")),
                            ("wo", ("attn", "wo"))):
                w = blk(i, *pth, "w")
                bias = blk(i, *pth, "b")
                engine.register_linear(metas[f"{b}.{nm}"], w, bias)
            engine.register_attention(metas[f"{b}.qk"])
            engine.register_attention(metas[f"{b}.pv"])
            engine.register_linear(metas[f"{b}.wi"], blk(i, "mlp", "wi", "w"), blk(i, "mlp", "wi", "b"))
            engine.register_linear(metas[f"{b}.wd"], blk(i, "mlp", "wo", "w"), blk(i, "mlp", "wo", "b"))
        engine.register_linear(metas["final.out"], _v(params, "final_out", "w"), _v(params, "final_out", "b"))

    def __call__(self, latents, t, labels=None):
        eng = self.engine
        return _dit_forward(self.params, self.cfg, eng.linear, eng.attention_matmul,
                            latents, t, labels)


def make_step_fn(cfg: dit_mod.DiTCfg, modes: dict[str, str], plan: DittoPlan | None = None,
                 *, block=UNSET, interpret=UNSET, collect_stats=UNSET,
                 low_bits=UNSET, fused=UNSET):
    """Build the pure per-step function of the compiled execution pass.

    Returns ``step(ditto_params, model_params, state, latents, t, labels)
    -> (eps_hat, new_state, aux)``. Everything data-dependent — the
    per-layer Ditto params (weight q-tensors, calibrated scales, biases),
    the fp32 model params for the VPU-side glue, and the temporal state —
    is an ARGUMENT, so the only trace-static inputs are ``cfg``, the
    frozen per-layer ``modes``, and the plan's trace identity
    (``plan.cache_sig()``: block / interpret / collect_stats / low_bits /
    fused). Two serve batches that share those statics (and
    shapes) can therefore share ONE ``jax.jit`` trace: this is what
    :class:`repro.serve.CompiledRunnerCache` keys on to amortize
    compilation across the whole request stream. ``plan.low_bits == 4``
    routes class-1 diff tiles through the packed-int4 kernel branch
    (bit-identical output, distinct cache key); ``plan.fused`` runs diff
    layers through the single-pass fused kernel with scalar-prefetch DMA
    skipping (bit-identical output, distinct cache key — a different
    lowering entirely). The per-knob keywords are a deprecated shim.

    ``plan`` must be segment-resolved: one trace serves one kernel
    lowering, so a multi-segment :class:`PlanSchedule` is rejected here
    (a constant schedule collapses to its bare plan) — ``make_denoise_fn``
    partitions the step loop by segment and builds one step per sig.
    """
    plan = segment_resolved(plan_from_kwargs(
        "core.ditto.make_step_fn", plan, block=block, interpret=interpret,
        collect_stats=collect_stats, low_bits=low_bits, fused=fused))
    modes = dict(modes)
    # Sharded plans stamp their submesh into the trace: the batch axis of
    # the latents (and of eps_hat) is constrained onto the plan's abstract
    # (mesh_axis: mesh_devices) mesh, so two plans differing only in
    # mesh_sig() lower to different jaxprs — which is exactly why
    # MESH_SIG_FIELDS are cache_sig() fields. mesh_sig=None leaves the
    # jaxpr untouched (bit-for-bit the pre-mesh trace).
    msig = plan.mesh_sig()

    def step(dparams, mparams, state, latents, t, labels):
        latents = constrain_batch(latents, msig)
        new_state: dict = {}
        aux: dict = {}

        def lin(name, x):
            y, st2, a = compiled_mod.linear_apply(dparams[name], modes[name], x,
                                                  state[name], plan=plan)
            new_state[name], aux[name] = st2, a
            return y

        def attn(name, a_, b_):
            y, st2, a = compiled_mod.attention_apply(dparams[name], modes[name], a_, b_,
                                                     state[name], plan=plan)
            new_state[name], aux[name] = st2, a
            return y

        out = _dit_forward(mparams, cfg, lin, attn, latents, t, labels)
        return constrain_batch(out, msig), new_state, aux

    return step


class CompiledDittoDiT:
    """Compiled execution pass: ONE jitted per-step function over the whole
    denoiser, built from a calibrated engine. Per-layer temporal state
    (x_prev/y_prev/attention operands) is threaded functionally; modes are
    frozen at trace time. With collect_stats, on-device class fractions
    come back as an aux pytree and the engine synthesizes cost-model
    records for the step.

    With ``cache`` (a :class:`repro.serve.CompiledRunnerCache`) the jitted
    step is fetched from / registered in the cache instead of being jitted
    per instance, so later batches with the same (cfg, modes,
    ``plan.cache_sig()``, ``bucket``, shapes) reuse the existing trace."""

    def __init__(self, params, cfg: dit_mod.DiTCfg, engine: DittoEngine,
                 plan: DittoPlan | None = None, *, cache=None, bucket: int | None = None,
                 interpret=UNSET, collect_stats=UNSET, block=UNSET, low_bits=UNSET,
                 fused=UNSET, cache_extra=UNSET):
        plan, bucket = _resolve_legacy(
            "core.ditto.CompiledDittoDiT", plan, bucket, cache_extra,
            interpret=interpret, collect_stats=collect_stats, block=block,
            low_bits=low_bits, fused=fused)
        plan = segment_resolved(plan)  # one runner = one segment's lowering
        self.cfg = cfg
        self.engine = engine
        self.params = params
        self.plan = plan
        self.ceng = CompiledDittoEngine(engine, plan=plan)
        self.state = self.ceng.init_state()
        if cache is not None:
            self._step = cache.step_for(cfg, self.ceng.modes, plan, bucket=bucket)
        else:
            self._step = jax.jit(make_step_fn(cfg, self.ceng.modes, plan))

    def __call__(self, latents, t, labels=None):
        out, self.state, aux = self._step(self.ceng.params, self.params, self.state,
                                          latents, t, labels)
        if self.ceng.collect_stats:
            self.engine.record_compiled_step(aux)
        return out


def make_denoise_fn(params, cfg: dit_mod.DiTCfg, engine: DittoEngine,
                    plan: DittoPlan | None = None, *, runner_cache=None,
                    bucket: int | None = None, compiled=UNSET, interpret=UNSET,
                    collect_stats=UNSET, block=UNSET, low_bits=UNSET, fused=UNSET,
                    cache_extra=UNSET):
    """denoise_fn(x, t, labels) for repro.core.diffusion samplers; calls
    engine.end_step() after each sampler step.

    With no ``plan`` this is the bare eager path (:data:`EAGER_PLAN` —
    calibration / analysis runs). ``plan.compiled=True``: once the engine
    is calibrated (engine.ready_for_compiled), the remaining steps run
    through the jitted Pallas path, seeded with the eager pass's temporal
    state. A new compiled runner object is built per sample (begin_sample
    resets state and Defo may re-decide modes), but with ``runner_cache``
    the underlying jitted step function is shared across samples/batches
    whose (cfg, modes, ``plan.cache_sig()``, ``bucket``, shapes) agree —
    one trace per runner-cache key instead of one per batch. The
    per-knob keywords are a deprecated shim (their ``compiled`` default
    stays False, matching the legacy signature).

    ``plan`` may be a :class:`PlanSchedule`: the compiled step loop is
    partitioned by segment. At a segment boundary the current runner is
    swapped for one built from the new segment's plan — same runner cache,
    so each distinct ``cache_sig()`` compiles once — and the temporal
    state pytree is transplanted across the swap, so outputs stay
    bit-identical to the matching constant plan at every step. Eager
    calibration steps predate the compiled path and ignore segment kernel
    knobs (the eager engine has none).

    ``plan.watchdog=True`` arms the numerical health watchdog on the
    compiled path: every step's output is finite-guarded, and (with
    ``plan.reanchor_full_frac``) the measured tile-class histograms are
    watched for Δ-saturation — too many full-precision tiles means the
    quantized temporal deltas have drifted out of range. Either signal
    triggers a RE-ANCHOR: the paper's initial-step semantics applied
    mid-trajectory — the step re-runs with every layer in act mode (full
    direct int8 GEMMs, no temporal differencing) under one canonical
    plan (``fused=False``, default ``low_bits``; act-mode lowering
    ignores both, so every kernel-family serving plan shares ONE audited
    re-anchor trace), refreshing ``x_prev``/``y_prev`` so later diff
    steps difference against a clean anchor. Events land on
    ``engine.watchdog_events``; output that is STILL non-finite raises a
    typed ``repro.serve.faults.NumericalFault``.
    """
    legacy = dict(compiled=compiled, interpret=interpret, collect_stats=collect_stats,
                  block=block, low_bits=low_bits, fused=fused)
    if any(not is_unset(v) for v in legacy.values()) or not is_unset(cache_extra):
        if is_unset(legacy["compiled"]):
            legacy["compiled"] = False  # the legacy signature's default
    plan, bucket = _resolve_legacy("core.ditto.make_denoise_fn", plan, bucket,
                                   cache_extra, default=EAGER_PLAN, **legacy)
    schedule = plan.normalized() if isinstance(plan, PlanSchedule) else None
    watchdog = bool(getattr(plan, "watchdog", False))
    reanchor_frac = getattr(plan, "reanchor_full_frac", None)
    if watchdog:
        # the typed error + poison probe live with the other fault machinery;
        # imported lazily so core.ditto never hard-depends on repro.serve
        from ...serve import faults as faults_mod
    runner = DittoDiT(params, cfg, engine)
    box: dict = {}

    def reanchor_step(x, t, labels, trigger: str, extra: dict):
        """Run THIS step full-bit-width (all layers act mode) under the
        canonical re-anchor plan, refreshing the temporal anchors."""
        cur = box["runner"]
        rplan = cur.plan.replace(fused=False, low_bits=DEFAULT_LOW_BITS)
        act_modes = {name: "act" for name in cur.ceng.modes}
        rsig = rplan.cache_sig()
        if box.get("reanchor_sig") != rsig:
            if runner_cache is not None:
                box["reanchor_fn"] = runner_cache.step_for(
                    cfg, act_modes, rplan, bucket=bucket)
            else:
                box["reanchor_fn"] = jax.jit(make_step_fn(cfg, act_modes, rplan))
            box["reanchor_sig"] = rsig
        out, cur.state, aux = box["reanchor_fn"](
            cur.ceng.params, params, cur.state, x, t, labels)
        if cur.ceng.collect_stats:
            engine.record_compiled_step(aux, modes=act_modes, reanchor=True)
        engine.watchdog_events.append(
            {"step": engine.step_idx, "trigger": trigger, **extra})
        return out

    def guarded_step(x, t, labels):
        """One compiled step under the watchdog: finite guard (re-run the
        step re-anchored on NaN/Inf) + Δ-saturation tracking (re-anchor
        the NEXT step when the measured full-tile fraction crosses
        ``reanchor_full_frac``)."""
        fault = faults_mod.fire("denoise.step")
        x_in = x
        if fault is not None and fault.kind == "drift":
            x_in = faults_mod.corrupt(fault, x)  # saturate the temporal Δs
        due = box.pop("reanchor_due", None)
        if due is not None:
            return reanchor_step(x_in, t, labels, "saturation",
                                 {"full_frac": due})
        cur = box["runner"]
        pre_state = cur.state
        n0 = len(engine.records)
        out = cur(x_in, t, labels)
        if fault is not None and fault.kind in ("poison_nan", "poison_inf"):
            # poison the step OUTPUT: the int8 path launders input NaNs
            # (quantization clips them to an integer), so output poisoning
            # is the faithful stand-in for an fp32-side corruption
            out = faults_mod.corrupt(fault, out)
        if not bool(jnp.isfinite(out).all()):
            # roll back the poisoned step (state AND its records) and
            # re-run it re-anchored from the pre-step temporal state,
            # with the UN-corrupted input
            cur.state = pre_state
            del engine.records[n0:]
            return reanchor_step(x, t, labels, "nonfinite", {})
        if reanchor_frac is not None:
            hists = [r["tile_hist"] for r in engine.records[n0:]
                     if "tile_hist" in r]
            total = sum(sum(h) for h in hists)
            full = sum(h[2] for h in hists)
            if total and full >= reanchor_frac * total:
                box["reanchor_due"] = full / total
        return out

    def fn(x, t, labels):
        if plan.compiled and engine.ready_for_compiled():
            # engine.step_idx is the current sampler step (end_step() below
            # advances it; both samplers call this fn once per step)
            seg_plan = (schedule.plan_for(engine.step_idx) if schedule is not None
                        else plan)
            sig = seg_plan.cache_sig()
            if box.get("built_for") is not engine.records:  # rebuilt per begin_sample
                box["runner"] = CompiledDittoDiT(params, cfg, engine, seg_plan,
                                                 cache=runner_cache, bucket=bucket)
                box["built_for"] = engine.records
                box["sig"] = sig
                box.pop("reanchor_due", None)  # saturation never crosses samples
            elif box["sig"] != sig:  # segment boundary: swap lowering, carry state
                prev = box["runner"]
                box["runner"] = CompiledDittoDiT(params, cfg, engine, seg_plan,
                                                 cache=runner_cache, bucket=bucket)
                box["runner"].state = prev.state
                box["sig"] = sig
            if watchdog:
                out = guarded_step(x, t, labels)
                if not bool(jnp.isfinite(out).all()):
                    raise faults_mod.NumericalFault(engine.step_idx)
            else:
                out = box["runner"](x, t, labels)
        else:
            out = runner(x, t, labels)
        engine.end_step()
        return out

    return fn
