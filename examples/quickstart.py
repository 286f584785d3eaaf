"""Quickstart: the paper's pipeline in one script.

1. Train a tiny DiT denoiser on a synthetic latent distribution.
2. Serve it with FP32 DDIM sampling.
3. Serve it with Ditto (quantized temporal-difference processing + Defo).
4. Print the similarity/zero/BOPs stats and the simulated hardware win.

    PYTHONPATH=src python examples/quickstart.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.compile_cache import use_compile_cache
from repro.core import diffusion
from repro.data.synthetic import DataCfg, batch_for
from repro.launch import steps as steps_mod
from repro.nn import dit as dit_mod
from repro.sim import harness


def main():
    use_compile_cache()
    # ---- 1. train a small denoiser -------------------------------------
    arch = dataclasses.replace(
        configs.get("dit-xl2").smoke(), n_layers=3, d_model=64, input_size=16, n_classes=8
    )
    dcfg = steps_mod.make_dit_model(arch)
    opt = steps_mod.make_optimizer(arch, base_lr=2e-3, total=200)
    state = steps_mod.init_state(arch, jax.random.PRNGKey(0), opt)
    train = jax.jit(steps_mod.make_train_step(arch, opt))
    dc = DataCfg(seed=0, batch=16, seq_len=1)
    for step in range(200):
        state, metrics = train(state, batch_for(arch, dc, step))
        if step % 50 == 0:
            print(f"[train] step {step:4d} loss {float(metrics['loss']):.4f}")
    params = state["params"]

    # ---- 2. FP32 reference sampling ------------------------------------
    sched = diffusion.cosine_schedule(1000)
    key = jax.random.PRNGKey(7)
    x = jax.random.normal(key, (4, arch.input_size, arch.input_size, arch.in_channels))
    labels = jnp.arange(4) % arch.n_classes

    def fp32_fn(xt, t, lab):
        return dit_mod.apply(params, dcfg, xt, t.astype(jnp.float32), lab)

    ref = diffusion.ddim_sample(sched, fp32_fn, x, steps=25, labels=labels)

    # ---- 3./4. Ditto serving + design-point simulation ------------------
    records, sample, eng = harness.collect_records(params, dcfg, sched, x, labels, steps=25)
    rel = float(jnp.linalg.norm(sample - ref) / jnp.linalg.norm(ref))
    recs = [r for r in records if r["step"] >= 1 and "cls_diff" in r]
    zero = float(np.mean([r["cls_diff"][0] for r in recs]))
    le4 = float(np.mean([r["cls_diff"][0] + r["cls_diff"][1] for r in recs]))
    s = eng.summary()
    print(f"[ditto] FP32-vs-Ditto rel L2          : {rel:.4f}")
    print(f"[ditto] temporal-diff zero fraction   : {zero:.1%}")
    print(f"[ditto] temporal-diff <=4-bit fraction: {le4:.1%}")
    print(f"[ditto] BOPs vs quantized baseline    : {s['bops']/s['bops_act']:.1%}")

    res = harness.run_designs(records, t_mult=64, d_mult=18)  # DiT-XL/2 scale
    t_itc = res["itc"]["time_s"]
    for d in ("gpu-a100", "itc", "diffy", "cambricon-d", "ditto", "ditto+"):
        r = res[d]
        print(f"[sim]  {d:12s} {r['time_s']*1e3:8.2f} ms/batch  "
              f"speedup vs ITC {t_itc/r['time_s']:5.2f}x  energy {r['energy_j']:.3f} J")


if __name__ == "__main__":
    main()
