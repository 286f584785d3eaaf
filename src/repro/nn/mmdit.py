"""MMDiT (Esser et al., "Scaling Rectified Flow Transformers for
High-Resolution Image Synthesis", arXiv:2403.03206): the two-stream
joint-attention denoiser of Stable Diffusion 3. Plain float32 reference.

Each block holds an image stream and a text stream with their own weights.
Per stream: adaLN-Zero modulation ``silu(c) -> 6d`` (shift, scale, gate
for attention and MLP), ``LN(no affine, eps 1e-6) * (1 + scale) + shift``,
and q/k/v projections. One softmax attention (scale ``1/sqrt(head_dim)``)
runs over the concatenated tokens ``[image; text]``; its output is split
back, each part through its stream's ``wo``, a gated residual and a gated
tanh-GELU MLP. The last block is ``context_pre_only``: its text stream has
a 2d ``AdaLayerNormContinuous`` modulation (scale, then shift) and q/k/v
only, with no ``wo``, no MLP and no text output. The final norm is
``AdaLayerNormContinuous`` too (scale, then shift), then ``proj_out`` to
the patch pixels.

Conditioning: ``c = t_mlp(sinusoid_256(t)) + pool_mlp(pooled)``, where
``t_mlp`` is 256 -> d -> d and ``pool_mlp`` pooled -> d -> d, both with a
SiLU between; the text stream starts as ``context_embed(context)``.

Departures from the published model:

- a request's label is a prompt id into ``prompt_ctx``
  (n_prompts, ctx_tokens, ctx_dim) and ``prompt_pooled``
  (n_prompts, pooled_dim), held in the params: the bank stands in for the
  CLIP and T5 text encoders' per-request output, as DiT's label table is
  an id-to-vector table;
- a learned (tokens, d) positional table in place of the fixed sin-cos
  grid cropped from ``pos_embed_max_size``;
- the patch embedding is a linear over flattened patches (the published
  stride-``patch`` convolution computes the same map);
- the noise head emits ``in_channels`` per patch pixel, as SD3 does; the
  sampler here is DDIM (the published one is a rectified-flow Euler).
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from . import core
from .core import Param, val
from .dit import _ln, _modulate, timestep_embedding

#: the linears of one stream of a full block, by call-site leaf name
STREAM_LINEARS = ("mod", "wq", "wk", "wv", "wo", "wi", "wd")
#: the text stream of the last (``context_pre_only``) block
PRE_ONLY_LINEARS = ("mod", "wq", "wk", "wv")


@dataclasses.dataclass(frozen=True)
class MMDiTCfg:
    d_model: int
    n_layers: int  # the last of them is the context_pre_only block
    n_heads: int
    patch: int = 2
    in_channels: int = 16
    input_size: int = 64  # latent H=W
    mlp_ratio: float = 4.0
    ctx_tokens: int = 154  # 77 CLIP + 77 T5
    ctx_dim: int = 4096  # joint_attention_dim
    pooled_dim: int = 2048
    n_prompts: int = 64

    def __post_init__(self):
        if self.n_layers < 2:
            raise ValueError(f"MMDiT needs a full block before the pre-only one, "
                             f"got n_layers={self.n_layers}")

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def n_tokens(self) -> int:
        """Image tokens."""
        return (self.input_size // self.patch) ** 2

    @property
    def patch_dim(self) -> int:
        return self.patch * self.patch * self.in_channels

    @property
    def ff(self) -> int:
        return int(self.mlp_ratio * self.d_model)


def _mod_init(key, shape, dtype=jnp.float32):
    """adaLN modulation weights at stddev 0.02 (zero in the published
    initialisation), so that every gate is live at random weights."""
    return core.normal_init(key, shape, 0.02, dtype)


def _stream_init(key, cfg: MMDiTCfg, names, mod_width: int, dtype) -> dict:
    d, ff = cfg.d_model, cfg.ff
    shapes = {"mod": (d, mod_width), "wq": (d, d), "wk": (d, d), "wv": (d, d),
              "wo": (d, d), "wi": (d, ff), "wd": (ff, d)}
    keys = jax.random.split(key, len(names))
    return {nm: core.dense_init(k, *shapes[nm], bias=True, axes=(None, None), dtype=dtype,
                                init=_mod_init if nm == "mod" else core.lecun_init)
            for k, nm in zip(keys, names)}


def init(key, cfg: MMDiTCfg, *, dtype=jnp.float32) -> dict:
    """Seeded float32 params: dense weights lecun-normal, modulations and
    tables at stddev 0.02, the prompt bank standard normal."""
    d = cfg.d_model
    keys = jax.random.split(key, 13)
    none2 = (None, None)

    def dense(k, n_in, n_out, init=core.lecun_init):
        return core.dense_init(k, n_in, n_out, bias=True, axes=none2, init=init, dtype=dtype)

    p: dict = {
        "patch_embed": dense(keys[0], cfg.patch_dim, d),
        "pos_embed": Param(core.normal_init(keys[1], (cfg.n_tokens, d), 0.02, dtype), none2),
        "t_mlp1": dense(keys[2], 256, d),
        "t_mlp2": dense(keys[3], d, d),
        "pool_mlp1": dense(keys[4], cfg.pooled_dim, d),
        "pool_mlp2": dense(keys[5], d, d),
        "context_embed": dense(keys[6], cfg.ctx_dim, d),
        "prompt_ctx": Param(jax.random.normal(
            keys[7], (cfg.n_prompts, cfg.ctx_tokens, cfg.ctx_dim), dtype), (None,) * 3),
        "prompt_pooled": Param(jax.random.normal(
            keys[8], (cfg.n_prompts, cfg.pooled_dim), dtype), none2),
        "final_mod": dense(keys[9], d, 2 * d, init=_mod_init),
        "final_out": dense(keys[10], d, cfg.patch_dim),
    }

    def full_block(k):
        ki, kt = jax.random.split(k)
        return {"img": _stream_init(ki, cfg, STREAM_LINEARS, 6 * d, dtype),
                "txt": _stream_init(kt, cfg, STREAM_LINEARS, 6 * d, dtype)}

    blocks = [full_block(k) for k in jax.random.split(keys[11], cfg.n_layers - 1)]
    p["blocks"] = jax.tree.map(lambda *xs: Param(jnp.stack([x.value for x in xs]),
                                                 ("layer",) + xs[0].axes),
                               *blocks, is_leaf=core.is_param)
    ki, kt = jax.random.split(keys[12])
    p["last"] = {"img": _stream_init(ki, cfg, STREAM_LINEARS, 6 * d, dtype),
                 "txt": _stream_init(kt, cfg, PRE_ONLY_LINEARS, 2 * d, dtype)}
    return p


def conditioning(params: dict, cfg: MMDiTCfg, t: jax.Array, labels: jax.Array):
    """(c, txt): the conditioning vector (B, d) and the text stream's input
    (B, ctx_tokens, d) of prompt ids ``labels``."""
    c = timestep_embedding(t, 256)
    c = core.dense(params["t_mlp2"], jax.nn.silu(core.dense(params["t_mlp1"], c)))
    pooled = val(params["prompt_pooled"])[labels]
    c = c + core.dense(params["pool_mlp2"], jax.nn.silu(core.dense(params["pool_mlp1"], pooled)))
    txt = core.dense(params["context_embed"], val(params["prompt_ctx"])[labels])
    return c, txt


def _heads(x, cfg: MMDiTCfg):
    b, n, _ = x.shape
    return x.reshape(b, n, cfg.n_heads, cfg.head_dim).transpose(0, 2, 1, 3)


def block_apply(bp: dict, cfg: MMDiTCfg, x, txt, c, *, pre_only: bool = False):
    """One joint block. x: (B, Ti, d) image, txt: (B, Tt, d) text, c: (B, d).
    Returns (x, txt); txt is None after a pre-only block."""
    ci = jax.nn.silu(c)
    sh_a, sc_a, g_a, sh_m, sc_m, g_m = jnp.split(core.dense(bp["img"]["mod"], ci), 6, axis=-1)
    tm = core.dense(bp["txt"]["mod"], ci)
    if pre_only:
        t_sc_a, t_sh_a = jnp.split(tm, 2, axis=-1)
    else:
        t_sh_a, t_sc_a, t_g_a, t_sh_m, t_sc_m, t_g_m = jnp.split(tm, 6, axis=-1)
    h = _modulate(_ln(x), sh_a, sc_a)
    ht = _modulate(_ln(txt), t_sh_a, t_sc_a)
    q, k, v = (jnp.concatenate([core.dense(bp["img"][n], h), core.dense(bp["txt"][n], ht)],
                               axis=1) for n in ("wq", "wk", "wv"))
    q, k, v = _heads(q, cfg), _heads(k, cfg), _heads(v, cfg)
    probs = jax.nn.softmax(jnp.einsum("bhmd,bhnd->bhmn", q, k) / math.sqrt(cfg.head_dim),
                           axis=-1)
    att = jnp.einsum("bhmn,bhnd->bhmd", probs, v).transpose(0, 2, 1, 3)
    att = att.reshape(x.shape[0], -1, cfg.d_model)
    ti = x.shape[1]
    x = x + g_a[:, None, :] * core.dense(bp["img"]["wo"], att[:, :ti])
    h = _modulate(_ln(x), sh_m, sc_m)
    x = x + g_m[:, None, :] * core.dense(bp["img"]["wd"],
                                         jax.nn.gelu(core.dense(bp["img"]["wi"], h)))
    if pre_only:
        return x, None
    txt = txt + t_g_a[:, None, :] * core.dense(bp["txt"]["wo"], att[:, ti:])
    ht = _modulate(_ln(txt), t_sh_m, t_sc_m)
    txt = txt + t_g_m[:, None, :] * core.dense(bp["txt"]["wd"],
                                               jax.nn.gelu(core.dense(bp["txt"]["wi"], ht)))
    return x, txt


def apply(params: dict, cfg: MMDiTCfg, latents: jax.Array, t: jax.Array, labels: jax.Array):
    """latents: (B, H, W, C) -> predicted noise (B, H, W, C). t: (B,);
    labels: (B,) prompt ids."""
    b, hh, ww, ch = latents.shape
    pp = cfg.patch
    x = latents.reshape(b, hh // pp, pp, ww // pp, pp, ch)
    x = x.transpose(0, 1, 3, 2, 4, 5).reshape(b, cfg.n_tokens, cfg.patch_dim)
    x = core.dense(params["patch_embed"], x) + val(params["pos_embed"])[None]
    c, txt = conditioning(params, cfg, t, labels)

    def body(carry, bp):
        return block_apply(bp, cfg, *carry, c), None

    (x, txt), _ = jax.lax.scan(body, (x, txt), params["blocks"])
    x, _ = block_apply(params["last"], cfg, x, txt, c, pre_only=True)
    scale, shift = jnp.split(core.dense(params["final_mod"], jax.nn.silu(c)), 2, axis=-1)
    x = core.dense(params["final_out"], _modulate(_ln(x), shift, scale))
    x = x.reshape(b, hh // pp, ww // pp, pp, pp, ch).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, hh, ww, ch)
