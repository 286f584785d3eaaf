"""MMDiT (Esser et al., arXiv:2403.03206, Stable Diffusion 3): seeded
weights, the program's view of them, and the plain float32 reference.

The reference is written here from the paper's block equations and imports
nothing of the program. Two token streams, image and text, each with its
own weights: adaLN-Zero modulation (shift, scale, gate for attention and
MLP from ``silu(t_emb + pooled_emb)``), LayerNorm without affine
parameters (eps 1e-6) and q/k/v projections. One softmax attention, scale
``1/sqrt(head_dim)``, runs over the tokens ``[image; text]``; its output is
split back, each part through its stream's output projection, a gated
residual and a gated tanh-GELU MLP. The last block is ``context_pre_only``:
its text stream is modulated by ``AdaLayerNormContinuous`` (scale, then
shift) and only feeds q/k/v. The final norm is ``AdaLayerNormContinuous``
too, then a linear head to the patch pixels. The timestep embedding is the
256-wide sinusoid (cos first) through 256 -> d -> d with SiLU; the pooled
text embedding goes pooled -> d -> d with SiLU and is added to it; the text
stream starts as a linear ``context_dim -> d`` of the prompt's context.

A request's label is a prompt id: the weights hold a bank of
``num_classes`` prompt encodings (``context_tokens x context_dim`` context
and ``pooled_dim`` pooled), which stands in for the text encoders' output.

``bits`` fake-quantizes every matmul the program quantizes (both streams'
linears, the head and both attention products), as in
:mod:`bench.families.dit`: at 4 bits it is the benchmark's control.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.families.dit import (EMBED_STDDEV, MOD_STDDEV, T_FREQ_DIM, _bmm, _linear, _ln,
                                _modulate, _t_embed, cosine_alpha_bars, ddim_timesteps,
                                latent_shape)

__all__ = ["init_weights", "program_model", "latent_shape", "forward", "sample"]

STREAM = ("mod", "wq", "wk", "wv", "wo", "wi", "wd")
PRE_ONLY = ("mod", "wq", "wk", "wv")  # the last block's text stream


def _sizes(config: dict) -> dict:
    d = config["hidden_size"]
    p, c = config["patch_size"], config["in_channels"]
    return {"d": d, "ff": int(config["mlp_ratio"] * d), "patch_dim": p * p * c,
            "tokens": (config["input_size"] // p) ** 2, "layers": config["depth"]}


def _stream_shapes(d: int, ff: int, mod_width: int) -> dict:
    return {"mod": (d, mod_width), "wq": (d, d), "wk": (d, d), "wv": (d, d),
            "wo": (d, d), "wi": (d, ff), "wd": (ff, d)}


def init_weights(config: dict, key) -> dict:
    """Every weight from ``key``, on the device, in one jitted call: dense
    weights lecun-normal (every modulation at ``MOD_STDDEV``), biases and
    the positional table normal at ``EMBED_STDDEV``, the prompt bank
    standard normal. Full blocks are stacked on a leading layer axis; the
    last (pre-only) block is apart. A plain nested dict of float32."""
    s = _sizes(config)
    d, ff, n_full = s["d"], s["ff"], s["layers"] - 1

    def draw(key):
        keys = iter(jax.random.split(key, 64))

        def dense(w_shape, std=None):
            kw, kb = jax.random.split(next(keys))
            std = 1.0 / math.sqrt(w_shape[-2]) if std is None else std
            return {"w": jax.random.normal(kw, w_shape) * std,
                    "b": jax.random.normal(kb, w_shape[:-2] + w_shape[-1:]) * EMBED_STDDEV}

        def stream(names, mod_width, lead=()):
            shapes = _stream_shapes(d, ff, mod_width)
            return {nm: dense(lead + shapes[nm], MOD_STDDEV if nm == "mod" else None)
                    for nm in names}

        out = {
            "patch_embed": dense((s["patch_dim"], d)),
            "t_mlp1": dense((T_FREQ_DIM, d)),
            "t_mlp2": dense((d, d)),
            "pool_mlp1": dense((config["pooled_dim"], d)),
            "pool_mlp2": dense((d, d)),
            "context_embed": dense((config["context_dim"], d)),
            "final_mod": dense((d, 2 * d), MOD_STDDEV),
            "final_out": dense((d, s["patch_dim"])),
            "blocks": {"img": stream(STREAM, 6 * d, (n_full,)),
                       "txt": stream(STREAM, 6 * d, (n_full,))},
            "last": {"img": stream(STREAM, 6 * d), "txt": stream(PRE_ONLY, 2 * d)},
        }
        out["pos_embed"] = jax.random.normal(next(keys), (s["tokens"], d)) * EMBED_STDDEV
        out["prompt_ctx"] = jax.random.normal(
            next(keys), (config["num_classes"], config["context_tokens"], config["context_dim"]))
        out["prompt_pooled"] = jax.random.normal(
            next(keys), (config["num_classes"], config["pooled_dim"]))
        return out

    return jax.jit(draw)(key)


# ------------------------------------------------------- the program's view
def program_model(config: dict, weights: dict):
    """The program's ``(params, MMDiTCfg)`` for the same weights (the arrays
    are shared, not copied)."""
    from repro.nn.core import Param
    from repro.nn.mmdit import MMDiTCfg

    def wrap(tree, lead=()):
        if set(tree) == {"w", "b"}:
            return {"w": Param(tree["w"], lead + (None, None)),
                    "b": Param(tree["b"], lead + (None,))}
        return {k: wrap(v, lead) for k, v in tree.items()}

    params = {k: wrap(weights[k]) for k in (
        "patch_embed", "t_mlp1", "t_mlp2", "pool_mlp1", "pool_mlp2", "context_embed",
        "final_mod", "final_out", "last")}
    params["blocks"] = wrap(weights["blocks"], ("layer",))
    params["pos_embed"] = Param(weights["pos_embed"], (None, None))
    params["prompt_ctx"] = Param(weights["prompt_ctx"], (None, None, None))
    params["prompt_pooled"] = Param(weights["prompt_pooled"], (None, None))
    cfg = MMDiTCfg(d_model=config["hidden_size"], n_layers=config["depth"],
                   n_heads=config["num_heads"], patch=config["patch_size"],
                   in_channels=config["in_channels"], input_size=config["input_size"],
                   mlp_ratio=config["mlp_ratio"], ctx_tokens=config["context_tokens"],
                   ctx_dim=config["context_dim"], pooled_dim=config["pooled_dim"],
                   n_prompts=config["num_classes"])
    return params, cfg


# ----------------------------------------------------------- the reference
def _dense(p, x):
    return x @ p["w"] + p["b"]


def _joint_block(bp, h, e, c, heads, bits, pre_only=False):
    """One block: image tokens ``h`` (B, Ti, d), text tokens ``e`` (B, Tt, d),
    ``c`` = silu(conditioning). Returns (h, e); e is None if ``pre_only``."""
    b, ti, d = h.shape
    hd = d // heads
    ia = jnp.split(_linear(bp["img"]["mod"], c, bits), 6, axis=-1)
    tm = _linear(bp["txt"]["mod"], c, bits)
    if pre_only:
        t_scale, t_shift = jnp.split(tm, 2, axis=-1)
    else:
        ta = jnp.split(tm, 6, axis=-1)
        t_shift, t_scale = ta[0], ta[1]
    y = _modulate(_ln(h), ia[0], ia[1])
    z = _modulate(_ln(e), t_shift, t_scale)

    def split_heads(n):
        u = jnp.concatenate([_linear(bp["img"][n], y, bits), _linear(bp["txt"][n], z, bits)], 1)
        return u.reshape(b, -1, heads, hd).transpose(0, 2, 1, 3)

    q, k, v = split_heads("wq"), split_heads("wk"), split_heads("wv")
    probs = jax.nn.softmax(_bmm(q, k, bits) / math.sqrt(hd), axis=-1)
    att = _bmm(probs, v.swapaxes(-1, -2), bits).transpose(0, 2, 1, 3).reshape(b, -1, d)

    def rest(p, x, a, mods):
        x = x + mods[2][:, None, :] * _linear(p["wo"], a, bits)
        u = _modulate(_ln(x), mods[3], mods[4])
        u = _linear(p["wd"], jax.nn.gelu(_linear(p["wi"], u, bits), approximate=True), bits)
        return x + mods[5][:, None, :] * u

    h = rest(bp["img"], h, att[:, :ti], ia)
    return h, (None if pre_only else rest(bp["txt"], e, att[:, ti:], ta))


def forward(config: dict, weights: dict, x, t, labels, bits: int = 0):
    """Predicted noise for latents ``x`` (B, H, W, C) at timesteps ``t``
    for prompt ids ``labels``."""
    b, hh, ww, ch = x.shape
    p = config["patch_size"]
    heads = config["num_heads"]
    tok = (hh // p) * (ww // p)
    h = x.reshape(b, hh // p, p, ww // p, p, ch).transpose(0, 1, 3, 2, 4, 5)
    h = _dense(weights["patch_embed"], h.reshape(b, tok, p * p * ch)) + weights["pos_embed"][None]
    c = _dense(weights["t_mlp2"], jax.nn.silu(_dense(weights["t_mlp1"], _t_embed(t))))
    pooled = weights["prompt_pooled"][labels]
    c = c + _dense(weights["pool_mlp2"], jax.nn.silu(_dense(weights["pool_mlp1"], pooled)))
    c = jax.nn.silu(c)
    e = _dense(weights["context_embed"], weights["prompt_ctx"][labels])

    def body(carry, bp):
        return _joint_block(bp, *carry, c, heads, bits), None

    (h, e), _ = jax.lax.scan(body, (h, e), weights["blocks"])
    h, _ = _joint_block(weights["last"], h, e, c, heads, bits, pre_only=True)
    scale, shift = jnp.split(_dense(weights["final_mod"], c), 2, axis=-1)
    h = _linear(weights["final_out"], _modulate(_ln(h), shift, scale), bits)
    h = h.reshape(b, hh // p, ww // p, p, p, ch).transpose(0, 1, 3, 2, 4, 5)
    return h.reshape(b, hh, ww, ch)


@functools.lru_cache(maxsize=8)
def _jitted_forward(config_json: str, bits: int):
    """One jitted forward per (configuration, bits)."""
    return jax.jit(functools.partial(forward, json.loads(config_json), bits=bits))


def sample(config: dict, weights: dict, x, labels, steps: int, bits: int = 0,
           T: int = 1000) -> np.ndarray:
    """Deterministic DDIM (eta 0) from noise ``x``, float32 at the highest
    matmul precision; returns the final latents on the host."""
    abar = cosine_alpha_bars(T)
    fwd = _jitted_forward(json.dumps(config, sort_keys=True), bits)
    ts = ddim_timesteps(T, steps)
    with jax.default_matmul_precision("highest"):
        for i, t in enumerate(ts):
            a_t = float(abar[t])
            a_p = float(abar[ts[i + 1]]) if i + 1 < len(ts) else 1.0
            eps = fwd(weights, x, jnp.full((x.shape[0],), t, jnp.int32), labels)
            x0 = (x - math.sqrt(1 - a_t) * eps) / math.sqrt(a_t)
            x = math.sqrt(a_p) * x0 + math.sqrt(1 - a_p) * eps
    return np.asarray(x)
