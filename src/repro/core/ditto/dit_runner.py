"""DiT and MMDiT denoisers executed through the DittoEngine (quantized
serving path).

Mirrors repro.nn.dit.apply (``DiTCfg``) and repro.nn.mmdit.apply
(``MMDiTCfg``) with every linear op routed through the engine.
``_dit_forward`` and ``_mmdit_forward`` are the single sources of truth for
their block structures; each takes the two engine ops as callables, so the
eager calibration pass (:class:`DittoDiT`) and the jit-compiled Pallas
execution pass (:class:`CompiledDittoDiT`) share the exact same forward — a
structural divergence between the two phases is impossible by
construction. The config's type picks the forward, the Defo graph and the
weights the engine registers (:func:`_arch`); the step, the runner and the
denoising loop are one code path for both.

``make_denoise_fn(..., plan)`` with ``plan.compiled=True`` runs eager
steps until the engine is calibrated (>= 1 step; for Defo policies, until
the step-2 decision), then hands the remaining denoising steps to the
compiled per-step function in which each layer's mode is a static
bake-in: act-mode layers hit the ``int8_matmul`` Pallas kernel, diff-mode
layers ``diff_encode`` -> ``ditto_diff_matmul`` (zero tiles skipped
on-device). Under DDIM with the watchdog off, the sampler hands those
steps over at once (``denoise_fn.segment_loop``): each plan segment runs
as one jitted ``lax.scan`` of that same step and the sampler's update
(:func:`make_loop_fn`). The plan (one ``repro.core.ditto.DittoPlan``)
carries every knob; its ``cache_sig()`` is the runner-cache trace
identity. fp32-mode
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
from ...nn import mmdit as mmdit_mod
from ..spans import span
from . import compiled as compiled_mod
from . import defo
from .compiled import TILE_TOTALS, CompiledDittoEngine
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


def _patchify(latents, cfg):
    b, hh, ww, ch = latents.shape
    pp = cfg.patch
    x = latents.reshape(b, hh // pp, pp, ww // pp, pp, ch)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(b, cfg.n_tokens, cfg.patch_dim)


def _unpatchify(x, shape, cfg):
    b, hh, ww, ch = shape
    pp = cfg.patch
    x = x.reshape(b, hh // pp, ww // pp, pp, pp, ch).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, hh, ww, ch)


def _dit_forward(params, cfg: dit_mod.DiTCfg, linear, attention, latents, t, labels):
    """One DiT forward with every quantized op injected.

    ``linear(name, x)`` and ``attention(name, a, b)`` are the engine ops —
    eager (stateful) or compiled (closures threading a state pytree).
    Patch embed / conditioning / norms / softmax stay fp32 (VPU-side ops).
    Each block runs under ``jax.named_scope("blk<i>")`` with ``attn`` and
    ``mlp`` sub-scopes, so device ops carry their layer in their metadata.
    """
    b = latents.shape[0]
    x = nncore.dense(params["patch_embed"], _patchify(latents, cfg))
    x = x + nncore.val(params["pos_embed"])[None]
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
        with jax.named_scope(bk):
            mod = linear(f"{bk}.mod", c_act)
            sh_a, sc_a, g_a, sh_m, sc_m, g_m = jnp.split(mod, 6, axis=-1)
            with jax.named_scope("attn"):
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
                av = av.reshape(b, nh, cfg.n_tokens, hd).transpose(0, 2, 1, 3).reshape(
                    b, cfg.n_tokens, nh * hd)
                a = linear(f"{bk}.wo", av)
                x = x + g_a[:, None, :] * a
            with jax.named_scope("mlp"):
                h = dit_mod._modulate(dit_mod._ln(x), sh_m, sc_m)
                hmid = jax.nn.gelu(linear(f"{bk}.wi", h))
                x = x + g_m[:, None, :] * linear(f"{bk}.wd", hmid)

    modf = _cond_dense(params["final_mod"], c_act)
    shift, scl = jnp.split(modf, 2, axis=-1)
    x = dit_mod._modulate(dit_mod._ln(x), shift, scl)
    x = linear("final.out", x)
    return _unpatchify(x, latents.shape, cfg)


def _mmdit_forward(params, cfg: mmdit_mod.MMDiTCfg, linear, attention, latents, t, labels):
    """One MMDiT forward with every quantized op injected, the contract of
    :func:`_dit_forward`.

    Per block, each stream's linears are ``blk<i>.{img,txt}.{mod,wq,wk,wv,
    wo,wi,wd}`` (the last block's text stream has ``mod``, 2d wide, and
    q/k/v only) and the joint attention's two products over ``[image;
    text]`` tokens are ``blk<i>.qk`` and ``blk<i>.pv``. Patch embedding,
    the timestep and pooled-text MLPs, ``context_embed`` and the final
    modulation stay fp32. Scopes: ``blk<i>/img``, ``blk<i>/txt`` and
    ``blk<i>/joint_attn``. ``labels`` are prompt ids (required).
    """
    b = latents.shape[0]
    x = nncore.dense(params["patch_embed"], _patchify(latents, cfg))
    x = x + nncore.val(params["pos_embed"])[None]
    c = dit_mod.timestep_embedding(t, 256)
    c = _cond_dense(params["t_mlp2"], jax.nn.silu(_cond_dense(params["t_mlp1"], c)))
    pooled = nncore.val(params["prompt_pooled"])[labels]
    c = c + _cond_dense(params["pool_mlp2"],
                        jax.nn.silu(_cond_dense(params["pool_mlp1"], pooled)))
    c_act = jax.nn.silu(c)
    txt = nncore.dense(params["context_embed"], nncore.val(params["prompt_ctx"])[labels])

    nh, hd, ti = cfg.n_heads, cfg.head_dim, cfg.n_tokens
    n = ti + cfg.ctx_tokens
    scale = 1.0 / math.sqrt(hd)

    def heads(parts):  # [(b, T_s, d) per stream] -> (b * nh, n, hd)
        z = jnp.concatenate(parts, axis=1).reshape(b, n, nh, hd)
        return z.transpose(0, 2, 1, 3).reshape(b * nh, n, hd)

    def qkv(pre, h):
        return [linear(f"{pre}.{nm}", h) for nm in ("wq", "wk", "wv")]

    def out_and_mlp(pre, z, a, mods):
        _, _, g_a, sh_m, sc_m, g_m = mods
        z = z + g_a[:, None, :] * linear(f"{pre}.wo", a)
        h = dit_mod._modulate(dit_mod._ln(z), sh_m, sc_m)
        return z + g_m[:, None, :] * linear(f"{pre}.wd", jax.nn.gelu(linear(f"{pre}.wi", h)))

    for i in range(cfg.n_layers):
        bk = f"blk{i}"
        pre_only = i == cfg.n_layers - 1
        with jax.named_scope(bk):
            with jax.named_scope("img"):
                im = jnp.split(linear(f"{bk}.img.mod", c_act), 6, axis=-1)
                qi = qkv(f"{bk}.img", dit_mod._modulate(dit_mod._ln(x), im[0], im[1]))
            with jax.named_scope("txt"):
                if pre_only:  # AdaLayerNormContinuous: scale, then shift
                    t_sc, t_sh = jnp.split(linear(f"{bk}.txt.mod", c_act), 2, axis=-1)
                else:
                    tm = jnp.split(linear(f"{bk}.txt.mod", c_act), 6, axis=-1)
                    t_sh, t_sc = tm[0], tm[1]
                qt = qkv(f"{bk}.txt", dit_mod._modulate(dit_mod._ln(txt), t_sh, t_sc))
            with jax.named_scope("joint_attn"):
                qf, kf, vf = (heads([a, b_]) for a, b_ in zip(qi, qt))
                scores = attention(f"{bk}.qk", qf, kf) * scale
                probs = jax.nn.softmax(scores, axis=-1)
                av = attention(f"{bk}.pv", probs, vf.swapaxes(-1, -2))
                av = av.reshape(b, nh, n, hd).transpose(0, 2, 1, 3).reshape(b, n, nh * hd)
            with jax.named_scope("img"):
                x = out_and_mlp(f"{bk}.img", x, av[:, :ti], im)
            if not pre_only:
                with jax.named_scope("txt"):
                    txt = out_and_mlp(f"{bk}.txt", txt, av[:, ti:], tm)

    scl, shift = jnp.split(_cond_dense(params["final_mod"], c_act), 2, axis=-1)
    x = linear("final.out", dit_mod._modulate(dit_mod._ln(x), shift, scl))
    return _unpatchify(x, latents.shape, cfg)


def _register_dit(engine: DittoEngine, params, cfg: dit_mod.DiTCfg) -> None:
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


def mmdit_linear_weights(params, cfg: mmdit_mod.MMDiTCfg):
    """``(call-site name, w, b)`` of every engine linear of an MMDiT, as
    numpy arrays, in call order."""
    for i in range(cfg.n_layers):
        last = i == cfg.n_layers - 1
        for s in ("img", "txt"):
            names = (mmdit_mod.PRE_ONLY_LINEARS if last and s == "txt"
                     else mmdit_mod.STREAM_LINEARS)
            for nm in names:
                if last:
                    p = params["last"][s][nm]
                    w, bias = _v(p, "w"), _v(p, "b")
                else:
                    p = params["blocks"][s][nm]
                    w, bias = _v(p, "w")[i], _v(p, "b")[i]
                yield f"blk{i}.{s}.{nm}", w, bias
    yield "final.out", _v(params, "final_out", "w"), _v(params, "final_out", "b")


def _register_mmdit(engine: DittoEngine, params, cfg: mmdit_mod.MMDiTCfg) -> None:
    metas = defo.analyze(defo.mmdit_graph(cfg.n_layers))
    for name, w, bias in mmdit_linear_weights(params, cfg):
        engine.register_linear(metas[name], w, bias)
    for i in range(cfg.n_layers):
        engine.register_attention(metas[f"blk{i}.qk"])
        engine.register_attention(metas[f"blk{i}.pv"])


def _arch(cfg):
    """``(forward, register)`` of the denoiser the config describes."""
    if isinstance(cfg, mmdit_mod.MMDiTCfg):
        return _mmdit_forward, _register_mmdit
    return _dit_forward, _register_dit


class DittoDiT:
    """Eager calibration pass (per-layer python loop — each layer's
    execution mode may differ per step, which is the point of Defo).
    Weights are registered once from the same param tree used for
    training."""

    def __init__(self, params, cfg: dit_mod.DiTCfg | mmdit_mod.MMDiTCfg, engine: DittoEngine):
        self.cfg = cfg
        self.engine = engine
        self.params = params
        self._forward, register = _arch(cfg)
        register(engine, params, cfg)

    def __call__(self, latents, t, labels=None):
        eng = self.engine
        return self._forward(self.params, self.cfg, eng.linear, eng.attention_matmul,
                             latents, t, labels)


def make_step_fn(cfg: dit_mod.DiTCfg | mmdit_mod.MMDiTCfg, modes: dict[str, str], plan: DittoPlan | None = None,
                 *, block=UNSET, interpret=UNSET, collect_stats=UNSET,
                 low_bits=UNSET, fused=UNSET):
    """Build the pure per-step function of the compiled execution pass.

    Returns ``step(ditto_params, model_params, state, latents, t, labels)
    -> (eps_hat, new_state, stats)``, where ``stats`` is the per-layer aux
    pytree packed into two arrays (:class:`compiled.PackedStats`).
    Everything data-dependent — the per-layer Ditto params (weight
    q-tensors, calibrated scales, biases), the fp32 model params for the
    VPU-side glue, and the temporal state — is an ARGUMENT, so the only
    trace-static inputs are ``cfg``, the frozen per-layer ``modes``, and
    the plan's trace identity
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

    ``state[TILE_TOTALS]`` (``compiled.TILE_TOTALS``) is the running
    (zero, low, full) tile count per layer, rows in sorted layer order:
    the step adds each diff layer's ``tile_hist`` to it on the device,
    whatever ``collect_stats`` says, and passes it through unchanged when
    no layer runs in diff mode.
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
    forward, _ = _arch(cfg)

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

        out = forward(mparams, cfg, lin, attn, latents, t, labels)
        totals = state[TILE_TOTALS]
        if any("tile_hist" in a for a in aux.values()):
            zero = jnp.zeros((3,), jnp.int32)
            totals = totals + jnp.stack([aux[name].get("tile_hist", zero)
                                         for name in sorted(modes)])
        new_state[TILE_TOTALS] = totals
        return constrain_batch(out, msig), new_state, compiled_mod.pack_stats(aux)

    return step


def make_loop_fn(cfg: dit_mod.DiTCfg | mmdit_mod.MMDiTCfg, modes: dict[str, str],
                 plan: DittoPlan | None = None):
    """Build the pure segment loop of the compiled execution pass.

    Returns ``denoise_loop(ditto_params, model_params, state, latents, ts,
    labels, update) -> (latents, new_state, stats)``: ``len(ts) - 1``
    steps of :func:`make_step_fn`'s ``step`` as one ``lax.scan``, each
    followed by the sampler's ``update(x, eps_hat, t, t_prev)``
    (``diffusion.loop_update``). ``ts`` holds the segment's timesteps and
    then the one after it (-1 after the sample's last step). The carry is
    ``(latents, state)``; ``stats`` is each step's
    :class:`compiled.PackedStats`, stacked on a leading step axis. The
    timesteps and the update (with its schedule) are arguments, so one
    trace serves any schedule of the same length; ``cfg``, ``modes`` and
    the segment-resolved ``plan`` are trace-static, as for the step."""
    step = make_step_fn(cfg, modes, plan)

    def denoise_loop(dparams, mparams, state, latents, ts, labels, update):
        def body(carry, tt):
            x, st = carry
            t, t_prev = tt
            eps, st, stats = step(dparams, mparams, st, x,
                                  jnp.full((x.shape[0],), t, jnp.int32), labels)
            return (update(x, eps, t, t_prev), st), stats

        (latents, state), stats = jax.lax.scan(body, (latents, state), (ts[:-1], ts[1:]))
        return latents, state, stats

    return denoise_loop


class CompiledDittoDiT:
    """Compiled execution pass: ONE jitted per-step function over the whole
    denoiser, built from a calibrated engine. Per-layer temporal state
    (x_prev/y_prev/attention operands) is threaded functionally; modes are
    frozen at trace time. With collect_stats, on-device class fractions
    come back packed with the tile histograms, are read in one transfer,
    and the engine synthesizes cost-model records for the step.
    :meth:`loop` runs several steps of one segment in one call instead.

    With ``cache`` (a :class:`repro.serve.CompiledRunnerCache`) the jitted
    step or loop is fetched from / registered in the cache when first
    called instead of being jitted per instance, so later batches with the
    same (cfg, modes, ``plan.cache_sig()``, ``bucket``, shapes) reuse the
    existing trace."""

    def __init__(self, params, cfg: dit_mod.DiTCfg | mmdit_mod.MMDiTCfg, engine: DittoEngine,
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
        self._cache, self._bucket = cache, bucket
        self._step = self._loop = None

    def __call__(self, latents, t, labels=None):
        if self._step is None:
            self._step = (self._cache.step_for(self.cfg, self.ceng.modes, self.plan,
                                               bucket=self._bucket)
                          if self._cache is not None else
                          jax.jit(make_step_fn(self.cfg, self.ceng.modes, self.plan)))
        out, self.state, stats = self._step(self.ceng.params, self.params, self.state,
                                            latents, t, labels)
        if self.ceng.collect_stats:
            with span("ditto.record_step", step=self.engine.step_idx):
                self.engine.record_compiled_step(stats)
        return out

    def loop(self, latents, ts, labels, update):
        """Run the ``len(ts) - 1`` steps of one segment from
        ``engine.step_idx`` on (:func:`make_loop_fn`) in one call, then
        read their stacked statistics in one transfer and record every
        step. The input state is donated: the loop carries the only live
        copy of it."""
        n = len(ts) - 1
        if self._cache is not None:
            fn = self._cache.loop_for(self.cfg, self.ceng.modes, self.plan,
                                      bucket=self._bucket, update=update, length=n)
        else:
            if self._loop is None:
                self._loop = jax.jit(make_loop_fn(self.cfg, self.ceng.modes, self.plan),
                                     donate_argnums=2)
            fn = self._loop
        with span("ditto.loop", step=self.engine.step_idx, steps=n):
            out, self.state, stats = fn(self.ceng.params, self.params, self.state,
                                        latents, ts, labels, update)
        if self.ceng.collect_stats:
            with span("ditto.record_step", step=self.engine.step_idx):
                self.engine.record_compiled_steps(stats)
        return out


def make_denoise_fn(params, cfg: dit_mod.DiTCfg | mmdit_mod.MMDiTCfg, engine: DittoEngine,
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

    With ``plan.compiled``, the engine calibrated and the watchdog off,
    ``fn.segment_loop`` is ready: ``ddim_sample`` then hands it the
    remaining timesteps and its update, and it runs them as one
    :meth:`CompiledDittoDiT.loop` call per plan segment, advancing
    ``engine.step_idx`` by each segment's length and counting its steps in
    ``compiled_steps`` and ``looped_steps``. Records, tile totals and the
    state carried across segment boundaries are those of the per-step
    path, which still runs under the watchdog, PLMS and a sampler
    ``callback``.

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
    # the step after each segment's last; a bare plan is one open segment
    seg_stops = ([stop for _, stop, _ in schedule.segment_plans()]
                 if schedule is not None else [])

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
        out, cur.state, stats = box["reanchor_fn"](
            cur.ceng.params, params, cur.state, x, t, labels)
        if cur.ceng.collect_stats:
            with span("ditto.record_step", step=engine.step_idx):
                engine.record_compiled_step(stats, modes=act_modes, reanchor=True)
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
        if not engine.host_read(jnp.isfinite(out).all(), bool):
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

    def runner_for(step: int) -> CompiledDittoDiT:
        """The compiled runner of the segment holding sampler step ``step``."""
        seg_plan = schedule.plan_for(step) if schedule is not None else plan
        sig = seg_plan.cache_sig()
        if box.get("built_for") is not engine.records:  # rebuilt per begin_sample
            with span("ditto.runner_build"):
                box["runner"] = CompiledDittoDiT(params, cfg, engine, seg_plan,
                                                 cache=runner_cache, bucket=bucket)
            box["built_for"] = engine.records
            box["sig"] = sig
            box.pop("reanchor_due", None)  # saturation never crosses samples
        elif box["sig"] != sig:  # segment boundary: swap lowering, carry state
            prev = box["runner"]
            with span("ditto.runner_build"):
                box["runner"] = CompiledDittoDiT(params, cfg, engine, seg_plan,
                                                 cache=runner_cache, bucket=bucket)
            box["runner"].state = prev.state
            box["sig"] = sig
        return box["runner"]

    def compiled_step(x, t, labels):
        # engine.step_idx is the current sampler step (end_step() advances
        # it; both samplers call fn once per step)
        runner_for(engine.step_idx)
        if watchdog:
            out = guarded_step(x, t, labels)
            if not engine.host_read(jnp.isfinite(out).all(), bool):
                raise faults_mod.NumericalFault(engine.step_idx)
        else:
            out = box["runner"](x, t, labels)
        engine.tile_totals = box["runner"].state[TILE_TOTALS]
        return out

    def fn(x, t, labels):
        compiled = plan.compiled and engine.ready_for_compiled()
        with span("ditto.compiled_step" if compiled else "ditto.eager_step",
                  step=engine.step_idx):
            if compiled:
                out = compiled_step(x, t, labels)
                engine.compiled_steps += 1
            else:
                out = runner(x, t, labels)
                engine.eager_steps += 1
            engine.end_step()
        return out

    def segment_loop(x, ts, labels, update):
        """Run the sampler's remaining timesteps ``ts`` from
        ``engine.step_idx`` on, one device loop per plan segment."""
        ts = np.append(ts, -1).astype(np.int32)  # -1: the step after the last
        done = 0
        while done < len(ts) - 1:
            k = engine.step_idx
            left = len(ts) - 1 - done
            n = min(next((s - k for s in seg_stops if s > k), left), left)
            cur = runner_for(k)
            x = cur.loop(x, ts[done:done + n + 1], labels, update)
            engine.tile_totals = cur.state[TILE_TOTALS]
            engine.step_idx += n
            engine.compiled_steps += n
            engine.looped_steps += n
            done += n
        return x

    segment_loop.ready = lambda: (plan.compiled and not watchdog
                                  and engine.ready_for_compiled())
    fn.segment_loop = segment_loop
    return fn
