"""DiT (Peebles & Xie, arXiv:2212.09748): seeded weights, the program's view
of them, and the plain float32 reference.

The reference is written here from the paper's block equations and imports
nothing of the program: adaLN-Zero conditioning (shift, scale and gate for
attention and MLP from ``silu(t_emb + y_emb)``), LayerNorm without affine
parameters (eps 1e-6), multi-head self-attention without positional
rotation, a tanh-approximated GELU MLP, a modulated final LayerNorm and a
linear head. The weights are the benchmark's own, drawn on the device in
one jitted call; the program receives the same arrays wrapped in its own
parameter tree.

``bits`` runs the same forward with every matmul the program
quantizes (all linears and both attention products) fake-quantized to a
symmetric signed grid of that width: weights per output channel,
activations per sample. At 4 bits it is the benchmark's control, the
reference one precision below the int8 the configuration states.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

MOD_STDDEV = 0.02  # adaLN modulation weights: re-drawn so every gate is live
EMBED_STDDEV = 0.02  # positional and label tables, biases
T_FREQ_DIM = 256  # sinusoidal timestep embedding width (DiT)


def _shapes(config: dict) -> dict:
    d = config["hidden_size"]
    p, c = config["patch_size"], config["in_channels"]
    out_c = 2 * c if config.get("learn_sigma") else c
    ff = int(config["mlp_ratio"] * d)
    tokens = (config["input_size"] // p) ** 2
    n = config["depth"]
    return {
        "patch_embed": ((p * p * c, d), (d,)),
        "t_mlp1": ((T_FREQ_DIM, d), (d,)),
        "t_mlp2": ((d, d), (d,)),
        "final_mod": ((d, 2 * d), (2 * d,)),
        "final_out": ((d, p * p * out_c), (p * p * out_c,)),
        "blocks": {"mod": ((n, d, 6 * d), (n, 6 * d)), "wq": ((n, d, d), (n, d)),
                   "wk": ((n, d, d), (n, d)), "wv": ((n, d, d), (n, d)),
                   "wo": ((n, d, d), (n, d)), "wi": ((n, d, ff), (n, ff)),
                   "wd": ((n, ff, d), (n, d))},
        "pos_embed": (tokens, d),
        "label_embed": (config["num_classes"] + 1, d),  # + the null class
    }


def init_weights(config: dict, key) -> dict:
    """Every weight from ``key``, on the device, in one jitted call: dense
    weights lecun-normal (adaLN ``mod`` at ``MOD_STDDEV``), biases and
    tables normal at ``EMBED_STDDEV``. A plain nested dict of float32."""
    shapes = _shapes(config)

    def draw(key):
        def dense(k, w_shape, b_shape, std=None):
            kw, kb = jax.random.split(k)
            std = 1.0 / math.sqrt(w_shape[-2]) if std is None else std
            return {"w": jax.random.normal(kw, w_shape) * std,
                    "b": jax.random.normal(kb, b_shape) * EMBED_STDDEV}

        keys = iter(jax.random.split(key, 16))
        out = {name: dense(next(keys), *shapes[name])
               for name in ("patch_embed", "t_mlp1", "t_mlp2", "final_mod", "final_out")}
        out["blocks"] = {name: dense(next(keys), *s, std=MOD_STDDEV if name == "mod" else None)
                         for name, s in shapes["blocks"].items()}
        for name in ("pos_embed", "label_embed"):
            out[name] = jax.random.normal(next(keys), shapes[name]) * EMBED_STDDEV
        return out

    return jax.jit(draw)(key)


# ------------------------------------------------------- the program's view
def program_model(config: dict, weights: dict):
    """The program's ``(params, DiTCfg)`` for the same weights (the arrays
    are shared, not copied)."""
    from repro.nn.core import Param
    from repro.nn.dit import DiTCfg

    def dense(p, axes):
        return {"w": Param(p["w"], axes), "b": Param(p["b"], (axes[-1],))}

    b = weights["blocks"]
    lay = lambda axes: ("layer",) + axes  # noqa: E731
    blocks = {
        "mod": dense(b["mod"], lay(("embed", None))),
        "attn": {n: dense(b[n], lay(ax)) for n, ax in (
            ("wq", ("embed", "heads")), ("wk", ("embed", "kv")),
            ("wv", ("embed", "kv")), ("wo", ("heads", "embed")))},
        "mlp": {"wi": dense(b["wi"], lay(("embed", "mlp"))),
                "wo": dense(b["wd"], lay(("mlp", "embed")))},
    }
    params = {
        "patch_embed": dense(weights["patch_embed"], (None, "embed")),
        "pos_embed": Param(weights["pos_embed"], (None, "embed")),
        "t_mlp1": dense(weights["t_mlp1"], (None, "embed")),
        "t_mlp2": dense(weights["t_mlp2"], ("embed", "embed2")),
        "final_mod": dense(weights["final_mod"], ("embed", None)),
        "final_out": dense(weights["final_out"], ("embed", None)),
        "label_embed": Param(weights["label_embed"], (None, "embed")),
        "blocks": blocks,
    }
    cfg = DiTCfg(d_model=config["hidden_size"], n_layers=config["depth"],
                 n_heads=config["num_heads"], patch=config["patch_size"],
                 in_channels=config["in_channels"], input_size=config["input_size"],
                 mlp_ratio=config["mlp_ratio"], n_classes=config["num_classes"])
    return params, cfg


def latent_shape(config: dict) -> tuple[int, int, int]:
    s = config["input_size"]
    return (s, s, config["in_channels"])


# ----------------------------------------------------------- the reference
def _fake_quant(x, axes, bits):
    """Symmetric round-to-nearest onto ``2**(bits-1) - 1`` levels, with the
    max-abs scale taken over ``axes``."""
    qmax = 2 ** (bits - 1) - 1
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    scale = jnp.where(amax > 0, amax / qmax, 1.0)
    return jnp.clip(jnp.round(x / scale), -qmax, qmax) * scale


def _linear(p, x, bits):
    """x (B, ..., K) @ w (K, N) + b; quantized per sample and per channel."""
    w = p["w"]
    if bits:
        x = _fake_quant(x, tuple(range(1, x.ndim)), bits)
        w = _fake_quant(w, (0,), bits)
    return x @ w + p["b"]


def _bmm(a, b, bits):
    """a (B, H, M, D) @ b (B, H, N, D)^T, quantized per (sample, head)."""
    if bits:
        a = _fake_quant(a, (2, 3), bits)
        b = _fake_quant(b, (2, 3), bits)
    return jnp.einsum("bhmd,bhnd->bhmn", a, b)


def _ln(x, eps=1e-6):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)


def _modulate(x, shift, scale):
    return x * (1 + scale[:, None, :]) + shift[:, None, :]


def _t_embed(t, dim=T_FREQ_DIM, max_period=10000.0):
    half = dim // 2
    freqs = jnp.exp(-math.log(max_period) * jnp.arange(half, dtype=jnp.float32) / half)
    args = t.astype(jnp.float32)[:, None] * freqs[None]
    return jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)


def forward(config: dict, weights: dict, x, t, labels, bits: int = 0):
    """Predicted noise for latents ``x`` (B, H, W, C) at timesteps ``t``."""
    b, hh, ww, ch = x.shape
    p = config["patch_size"]
    heads = config["num_heads"]
    d = config["hidden_size"]
    hd = d // heads
    tok = (hh // p) * (ww // p)
    h = x.reshape(b, hh // p, p, ww // p, p, ch).transpose(0, 1, 3, 2, 4, 5)
    h = h.reshape(b, tok, p * p * ch)
    h = h @ weights["patch_embed"]["w"] + weights["patch_embed"]["b"] + weights["pos_embed"][None]
    c = _t_embed(t)
    c = jax.nn.silu(c @ weights["t_mlp1"]["w"] + weights["t_mlp1"]["b"])
    c = c @ weights["t_mlp2"]["w"] + weights["t_mlp2"]["b"]
    c = jax.nn.silu(c + weights["label_embed"][labels])

    def block(h, bp):
        mod = _linear(bp["mod"], c, bits)
        sh_a, sc_a, g_a, sh_m, sc_m, g_m = jnp.split(mod, 6, axis=-1)
        y = _modulate(_ln(h), sh_a, sc_a)
        q, k, v = (_linear(bp[n], y, bits).reshape(b, tok, heads, hd).transpose(0, 2, 1, 3)
                   for n in ("wq", "wk", "wv"))
        probs = jax.nn.softmax(_bmm(q, k, bits) / math.sqrt(hd), axis=-1)
        att = _bmm(probs, v.swapaxes(-1, -2), bits)  # (B, H, T, hd)
        att = att.transpose(0, 2, 1, 3).reshape(b, tok, d)
        h = h + g_a[:, None, :] * _linear(bp["wo"], att, bits)
        y = _modulate(_ln(h), sh_m, sc_m)
        y = _linear(bp["wd"], jax.nn.gelu(_linear(bp["wi"], y, bits), approximate=True), bits)
        return h + g_m[:, None, :] * y, None

    h, _ = jax.lax.scan(block, h, weights["blocks"])
    shift, scale = jnp.split(c @ weights["final_mod"]["w"] + weights["final_mod"]["b"], 2, axis=-1)
    h = _linear(weights["final_out"], _modulate(_ln(h), shift, scale), bits)
    out_c = h.shape[-1] // (p * p)
    h = h.reshape(b, hh // p, ww // p, p, p, out_c).transpose(0, 1, 3, 2, 4, 5)
    return h.reshape(b, hh, ww, out_c)[..., :ch]


def cosine_alpha_bars(T: int = 1000, s: float = 8e-3) -> np.ndarray:
    """Improved-DDPM cosine schedule (Nichol & Dhariwal), betas clipped to
    [1e-6, 0.999], as cumulative products of (1 - beta)."""
    t = np.arange(T + 1, dtype=np.float64) / T
    f = np.cos((t + s) / (1 + s) * np.pi / 2) ** 2
    betas = np.clip(1 - (f[1:] / f[0]) / (f[:-1] / f[0]), 1e-6, 0.999)
    return np.cumprod(1.0 - betas)


def ddim_timesteps(T: int, steps: int) -> list[int]:
    stride = max(T // steps, 1)
    return list(range(0, T, stride))[:steps][::-1]


@functools.lru_cache(maxsize=8)
def _jitted_forward(config_json: str, bits: int):
    """One jitted forward per (configuration, bits), so every block of
    rows a run compares reuses one compiled program."""
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
