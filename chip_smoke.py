"""Chip smoke run: the Ditto serving path on a TPU at DiT-XL/2 widths.

Builds DiT-XL/2 at its published widths (``configs/dit_xl2.py``) from a
fixed seed, serves a few ragged class-conditional requests through the
user entry point (``ServeScheduler.submit(...).result()`` ->
``ServeSession`` -> ``sim.harness.serve_records`` -> ``make_denoise_fn``
-> compiled runner cache -> Pallas kernels), once with ``policy="defo"``
and once with ``policy="diff"``, and fails unless

  (a) the compiled step lowered natively: the interpreter is off and the
      step's compiled HLO holds ``tpu_custom_call``;
  (b) ``ops.int8_act_matmul`` and ``ops.ditto_linear_step`` (both weight
      layouts, a seeded mix of zero/low/full tiles) equal the
      ``kernels/ref.py`` oracle exactly at real widths;
  (c) every served sample is finite and within ``REL_L2_BOUND`` of a
      float32 DDIM run of ``nn.dit.apply`` (highest matmul precision) from
      the same noise;
  (d) a one-row request served alone (bucket 1) equals, bit for bit, its
      rows from the coalesced stream: the batch invariance that
      ``ServeMesh`` replicas and work stealing rely on.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # ServeMesh(4, dp=1) vs solo serving; dp=4 refused

Without a TPU it exits non-zero before serving. The last stdout line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
every other report goes on earlier lines. Wall times printed here are
smoke timings (compiles included), not benchmark results.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.compile_cache import use_compile_cache
from repro.core import diffusion
from repro.kernels import ShardedLoweringUnsupported, ops, ref
from repro.kernels.common import pad2, resolve_interpret
from repro.launch.steps import make_dit_model
from repro.nn import dit as dit_mod
from repro.nn.core import Param
from repro.serve import DittoPlan, ServeMesh, ServeScheduler, ServeSession

SEED = 0
STEPS = 10
MAX_BATCH = 4
REQUEST_SIZES = (3, 1, 2, 2)  # ragged: coalesces into two full bucket-4 dispatches
MESH_REQUEST_SIZES = (3, 1, 2, 1, 3, 2, 1, 3)
MESH_LAYERS = 2  # the four-chip phase cuts depth; widths stay published
MOD_STDDEV = 0.02  # re-drawn adaLN-Zero projection, so every block's gates are live
# Relative L2 distance of a served sample from the float32 reference. On
# the CPU (interpret mode, full width, 10 steps, 2 requests) serve() measured
# 0.0200 at 2 layers and 0.0275 at 4, growing about as depth**0.46, which
# puts 28 layers near 0.07. The bound leaves twice that for the chip's
# default f32 matmul precision in the glue that is not quantized.
REL_L2_BOUND = 0.15


class NoAccelerator(RuntimeError):
    """JAX found no TPU: the smoke run measures nothing on any other backend."""


class CheckFailed(AssertionError):
    """A smoke check failed (raised explicitly, so ``python -O`` keeps it)."""


def check(ok, message) -> None:
    if not ok:
        raise CheckFailed(message)


def require_tpu() -> dict:
    """The device as JAX reports it; raises :class:`NoAccelerator` off-TPU."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoAccelerator(
            f"chip_smoke.py needs a TPU; JAX found {devices[0].platform!r}")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def report(**fields) -> None:
    print(json.dumps(fields, default=str), flush=True)


# ---------------------------------------------------------------- model
def build_model(n_layers: int | None = None, seed: int = SEED):
    """DiT-XL/2 at published widths (depth optionally cut) from ``seed``,
    with the zero-initialised adaLN ``mod`` weights re-drawn."""
    arch = configs.get("dit-xl2")
    if n_layers is not None:
        arch = dataclasses.replace(arch, n_layers=n_layers)
    cfg = make_dit_model(arch)
    k_init, k_mod = jax.random.split(jax.random.PRNGKey(seed))
    params = dit_mod.init(k_init, cfg)
    w = params["blocks"]["mod"]["w"]
    params["blocks"]["mod"]["w"] = Param(
        jax.random.normal(k_mod, w.value.shape, w.value.dtype) * MOD_STDDEV, w.axes)
    return params, cfg


def make_requests(cfg, sizes, seed: int = SEED):
    """Seeded (noise, class labels) per request."""
    out = []
    for i, b in enumerate(sizes):
        kx, kl = jax.random.split(jax.random.fold_in(jax.random.PRNGKey(seed + 1), i))
        x = jax.random.normal(
            kx, (b, cfg.input_size, cfg.input_size, cfg.in_channels), jnp.float32)
        out.append((x, jax.random.randint(kl, (b,), 0, cfg.n_classes)))
    return out


# -------------------------------------------------------------- serving
def serve(params, cfg, sched, requests, *, policy: str, steps: int = STEPS,
          max_batch: int = MAX_BATCH) -> dict:
    """AOT-warm the full bucket, serve ``requests`` through one scheduler
    (the cold stream), then serve the first request again (warm). Returns
    the stream's samples with the scheduler, timings, and the act/diff
    layer counts of the warmed step (the modes Defo froze in the warmup
    probe)."""
    plan = DittoPlan(steps=steps, policy=policy, max_batch=max_batch)
    scheduler = ServeScheduler(params, cfg, sched, plan)
    warm = scheduler.warmup(buckets=[max_batch])
    (key,) = scheduler.session.cache.executables()
    modes = [m for _, m in key.mode_sig]
    t0 = time.monotonic()
    tickets = [scheduler.submit(x, labels) for x, labels in requests]
    samples = [np.asarray(t.result()) for t in tickets]
    cold_s = time.monotonic() - t0
    t0 = time.monotonic()
    np.asarray(scheduler.submit(*requests[0]).result())
    warm_s = time.monotonic() - t0
    return {"samples": samples, "scheduler": scheduler, "plan": plan, "warmup_s": warm["wall_s"],
            "cold_stream_s": cold_s, "warm_request_s": warm_s,
            "act_layers": modes.count("act"), "diff_layers": modes.count("diff")}


def check_native(scheduler) -> int:
    """(a): native lowering, and Mosaic kernels in every warmed step."""
    check(resolve_interpret(None) is False, "Pallas would run in interpret mode")
    exes = scheduler.session.cache.executables()
    check(exes, "warmup installed no compiled step")
    for exe in exes.values():
        check("tpu_custom_call" in exe.as_text(), "compiled step holds no Mosaic kernel")
    return len(exes)


def reference_samples(params, cfg, sched, requests, steps: int = STEPS) -> list:
    """float32 DDIM over ``nn.dit.apply`` at the highest matmul precision,
    all requests in one batch (rows are independent), split back."""
    x = jnp.concatenate([x for x, _ in requests])
    labels = jnp.concatenate([lab for _, lab in requests])
    apply = jax.jit(dit_mod.apply, static_argnums=1)
    with jax.default_matmul_precision("highest"):
        out = diffusion.ddim_sample(
            sched, lambda x_, t, lab: apply(params, cfg, x_, t, lab), x,
            steps=steps, labels=labels)
    out = np.asarray(out)
    bounds = np.cumsum([0] + [r[0].shape[0] for r in requests])
    return [out[a:b] for a, b in zip(bounds[:-1], bounds[1:])]


def check_samples(samples, refs) -> float:
    """(c): finite samples within ``REL_L2_BOUND`` of the reference."""
    worst = 0.0
    for s, r in zip(samples, refs):
        check(s.shape == r.shape, (s.shape, r.shape))
        check(np.isfinite(s).all(), "served sample is not finite")
        worst = max(worst, float(np.linalg.norm(s - r) / np.linalg.norm(r)))
    check(worst <= REL_L2_BOUND, f"rel L2 {worst} > bound {REL_L2_BOUND}")
    return worst


def check_batch_invariance(params, cfg, sched, served, requests) -> float:
    """(d): the first one-row request, served alone through a session
    sharing the scheduler's runner cache, equals its coalesced rows."""
    i = [x.shape[0] for x, _ in requests].index(1)
    solo = ServeSession(params, cfg, sched, served["plan"],
                        cache=served["scheduler"].session.cache)
    t0 = time.monotonic()
    alone = np.asarray(solo.serve(*requests[i]).sample)
    check(np.array_equal(alone, served["samples"][i]),
          "a request served alone differs from its rows in a coalesced batch")
    return time.monotonic() - t0


# -------------------------------------------------------------- kernels
def _tile_mix(rng, m: int, k: int, tile: int = 128):
    """(x_t, x_prev) int8 whose (tile x tile) blocks cycle zero/low/full."""
    gm, gk = -(-m // tile), -(-k // tile)
    cls = rng.permutation(np.arange(gm * gk) % 3).reshape(gm, gk)
    cls = np.repeat(np.repeat(cls, tile, 0), tile, 1)[:m, :k]
    x_prev = rng.integers(-127, 128, (m, k))
    low = np.clip(x_prev + rng.integers(-7, 8, (m, k)), -127, 127)
    full = rng.integers(-127, 128, (m, k))
    x_t = np.where(cls == 0, x_prev, np.where(cls == 1, low, full))
    return jnp.asarray(x_t, jnp.int8), jnp.asarray(x_prev, jnp.int8)


def _int8(rng, shape):
    return jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)


def _on_host(fn, *args) -> np.ndarray:
    """Run a ``ref.py`` oracle on the host CPU backend, so the oracle does
    not share the device under test."""
    with jax.default_device(jax.devices("cpu")[0]):
        return np.asarray(fn(*(np.asarray(a) for a in args)))


def check_kernels(cfg, tokens: int, seed: int = SEED) -> dict:
    """(b): act and diff kernels vs the ref.py oracle, exact, at the
    model's widths: the (tokens x d)(d x d_ff) and (tokens x d_ff)(d_ff x d)
    GEMMs, the diff step in (K, N) layout with y_prev, and in the (N, K)
    layout at a linear's and at attention's (tokens x head_dim) shapes."""
    rng = np.random.default_rng(seed)
    d, ff, hd = cfg.d_model, int(cfg.mlp_ratio * cfg.d_model), cfg.head_dim
    out = {}
    for k, n in ((d, ff), (ff, d)):
        x, w = _int8(rng, (tokens, k)), _int8(rng, (k, n))
        got = np.asarray(ops.int8_act_matmul(x, w))
        check(np.array_equal(got, _on_host(ref.int8_matmul_ref, x, w)), f"int8_act_matmul {k}x{n}")
        out[f"act_{k}x{n}"] = "exact"
    for label, (m, k, n), w_t, with_yp in (("diff_kn", (tokens, d, ff), False, True),
                                           ("diff_nk", (tokens, ff, d), True, False),
                                           ("diff_attn_nk", (tokens, hd, tokens), True, False)):
        x_t, x_prev = _tile_mix(rng, m, k)
        w = _int8(rng, (n, k) if w_t else (k, n))
        y_prev = (jnp.asarray(rng.integers(-2**20, 2**20, (m, n)), jnp.int32)
                  if with_yp else jnp.zeros((m, n), jnp.int32))
        got, classes = ops.ditto_linear_step(x_t, x_prev, w, y_prev if with_yp else None,
                                             w_transposed=w_t)
        want = _on_host(ref.ditto_diff_matmul_ref, x_t, x_prev, w.T if w_t else w, y_prev)
        check(np.array_equal(np.asarray(got), want), f"ditto_linear_step {label}")
        want_cls = _on_host(lambda a, b: ref.diff_encode_ref(a, b, (128, 128)),
                            pad2(x_t, 128, 128), pad2(x_prev, 128, 128))
        check(np.array_equal(np.asarray(classes), want_cls), f"diff_encode {label}")
        out[label] = {"exact": True,
                      "tiles": [int((np.asarray(classes) == c).sum()) for c in range(3)]}
    return out


def cache_entries(cache_dir: str):
    """Entries in the persistent compile cache (None before it exists); the
    growth over a run counts the compiles it did not find there."""
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else None


def peak_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# ---------------------------------------------------------------- phases
def one_chip() -> None:
    sched = diffusion.cosine_schedule(1000)
    t0 = time.monotonic()
    params, cfg = build_model()
    requests = make_requests(cfg, REQUEST_SIZES)
    report(phase="model", layers=cfg.n_layers, d_model=cfg.d_model,
           heads=cfg.n_heads, tokens=cfg.n_tokens, classes=cfg.n_classes,
           requests=list(REQUEST_SIZES), steps=STEPS, build_s=time.monotonic() - t0)

    report(phase="kernels", **check_kernels(cfg, MAX_BATCH * cfg.n_tokens))

    served = {}
    for policy in ("defo", "diff"):
        r = serve(params, cfg, sched, requests, policy=policy)
        n_exe = check_native(r["scheduler"])
        served[policy] = r
        report(phase=f"serve_{policy}", act_layers=r["act_layers"],
               diff_layers=r["diff_layers"], native_executables=n_exe,
               smoke_warmup_s=r["warmup_s"], smoke_cold_stream_s=r["cold_stream_s"],
               smoke_warm_request_s=r["warm_request_s"],
               stats={k: v for k, v in r["scheduler"].stats().items()
                      if k in ("dispatches", "pad_rows", "traces", "aot_hits", "aot_misses")})

    solo_s = check_batch_invariance(params, cfg, sched, served["diff"], requests)
    report(phase="batch_invariance", policy="diff", request=REQUEST_SIZES.index(1),
           bitidentical=True, smoke_solo_s=solo_s)

    refs = reference_samples(params, cfg, sched, requests)
    rel = {p: check_samples(served[p]["samples"], refs) for p in served}
    gap = max(float(np.abs(a - b).max())
              for a, b in zip(served["defo"]["samples"], served["diff"]["samples"]))
    report(phase="reference", rel_l2=rel, bound=REL_L2_BOUND,
           defo_vs_diff_max_abs=gap, peak_bytes_in_use=peak_bytes())


def four_chips(n_layers: int = MESH_LAYERS) -> None:
    """ServeMesh(4, dp=1) against solo one-chip serving; dp=4 must be refused."""
    sched = diffusion.cosine_schedule(1000)
    params, cfg = build_model(n_layers)
    requests = make_requests(cfg, MESH_REQUEST_SIZES)
    plan = DittoPlan(steps=STEPS, max_batch=MAX_BATCH)
    t0 = time.monotonic()
    solo = ServeSession(params, cfg, sched, plan)
    want = [np.asarray(solo.serve(x, labels).sample) for x, labels in requests]
    solo_s = time.monotonic() - t0
    t0 = time.monotonic()
    with ServeScheduler(params, cfg, sched, plan, mesh=ServeMesh(4, dp=1),
                        async_mode=True) as scheduler:
        tickets = [scheduler.submit(x, labels) for x, labels in requests]
        got = [np.asarray(t.result(timeout=900)) for t in tickets]
        stats = scheduler.stats()
    mesh_s = time.monotonic() - t0
    same = [bool(np.array_equal(a, b)) for a, b in zip(got, want)]
    report(phase="mesh_dp1", layers=cfg.n_layers, requests=list(MESH_REQUEST_SIZES),
           bitidentical=same, mesh=stats["mesh"], dispatches=stats["dispatches"],
           smoke_solo_s=solo_s, smoke_mesh_s=mesh_s)
    check(all(same), f"mesh samples differ from solo serving: {same}")
    try:
        ServeScheduler(params, cfg, sched, plan, mesh=ServeMesh(4, dp=4))
    except ShardedLoweringUnsupported as e:
        report(phase="mesh_dp4", refused=str(e))
    else:
        raise CheckFailed("a dp=4 plan under native lowering was not refused")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: serve and check on one chip; 4: the ServeMesh phase only")
    args = ap.parse_args(argv)
    try:
        device = require_tpu()
    except NoAccelerator as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 2
    if device["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {device['count']} visible", file=sys.stderr)
        return 2
    cache_dir = use_compile_cache()
    before = cache_entries(cache_dir)
    if args.chips == 4:
        four_chips()
    else:
        one_chip()
    report(phase="compile_cache", dir_from_env="JAX_COMPILATION_CACHE_DIR" in os.environ,
           min_compile_s=jax.config.jax_persistent_cache_min_compile_time_secs,
           entries_before=before, entries_after=cache_entries(cache_dir))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
