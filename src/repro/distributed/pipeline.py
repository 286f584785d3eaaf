"""Pipeline parallelism over a mesh axis (GPipe-style schedule).

The multi-pod mesh's 'pod' axis defaults to pure DP; this module provides
the alternative: treat an axis as PIPELINE STAGES. Layers are split into
S contiguous stages; microbatches stream through with
``jax.lax.ppermute`` moving activations stage->stage inside ``shard_map``.

Schedule: GPipe (fill, steady state, drain) — S + M - 1 ticks for M
microbatches over S stages; bubble fraction (S-1)/(S+M-1). Each device
executes only its own stage's layers (the stage's parameter slice arrives
pre-sharded on the stage axis), so per-device weight memory is 1/S of the
stack — the PP memory win.

This is a *library* facility with a correctness test
(tests/test_pipeline.py): outputs are bit-comparable to the sequential
layer stack. The in-repo serving path takes the other branch —
:class:`repro.serve.mesh.ServeMesh` keeps every shard data-parallel
(params replicated, batch axis sharded), which is collective-cheaper at
serving batch sizes; ``pipeline_apply`` stays the opt-in layout for
deployments whose per-device weight memory, not throughput, is the
binding constraint.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(
    layer_fn,
    stacked_params,
    x,
    *,
    mesh: Mesh,
    stage_axis: str = "pod",
    n_microbatches: int | None = None,
):
    """Run ``layer_fn(params_slice, x) -> x`` through pipeline stages.

    stacked_params: pytree with leading dim L (layers); L must divide into
    S stages of L/S layers. x: (B, ...) with B divisible by the microbatch
    count M (default: S). Returns the same value as sequentially scanning
    the L layers.
    """
    s = mesh.shape[stage_axis]
    m = n_microbatches or s
    b = x.shape[0]
    assert b % m == 0, (b, m)
    n_layers = jax.tree.leaves(stacked_params)[0].shape[0]
    assert n_layers % s == 0, (n_layers, s)
    per_stage = n_layers // s

    # reshape params to (S, per_stage, ...) so each stage holds its slice
    staged = jax.tree.map(lambda p: p.reshape((s, per_stage) + p.shape[1:]), stacked_params)

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(stage_axis), P()),  # params sharded by stage, x replicated
        out_specs=P(),
        check_vma=False,
    )
    def run(stage_params, x_rep):
        stage_params = jax.tree.map(lambda p: p[0], stage_params)  # local (per_stage, ...)
        idx = jax.lax.axis_index(stage_axis)
        mbs = x_rep.reshape((m, b // m) + x_rep.shape[1:])
        out = jnp.zeros_like(mbs)
        buf = jnp.zeros_like(mbs[0])  # activation in flight on this stage

        def stage_compute(h):
            def body(h, p):
                return layer_fn(p, h), None

            h, _ = jax.lax.scan(body, h, stage_params)
            return h

        n_ticks = m + s - 1
        perm = [(i, (i + 1) % s) for i in range(s)]  # stage i -> i+1

        def tick(carry, t):
            out, buf = carry
            # stage 0 ingests microbatch t (when in range)
            take = jnp.clip(t, 0, m - 1)
            injected = jnp.where(idx == 0, 1.0, 0.0)
            h_in = jnp.where(injected > 0, mbs[take], buf)
            h_out = stage_compute(h_in)
            # last stage writes microbatch (t - (s-1)) when valid
            write_idx = jnp.clip(t - (s - 1), 0, m - 1)
            do_write = jnp.logical_and(idx == s - 1, t >= s - 1)
            out = jax.lax.cond(
                do_write,
                lambda o: o.at[write_idx].set(h_out),
                lambda o: o,
                out,
            )
            # shift activations to the next stage
            buf = jax.lax.ppermute(h_out, stage_axis, perm)
            return (out, buf), None

        (out, _), _ = jax.lax.scan(tick, (out, buf), jnp.arange(n_ticks))
        # the result lives on the last stage; share it with everyone
        out = jax.lax.psum(
            jnp.where(idx == s - 1, out, jnp.zeros_like(out)), stage_axis
        )
        return out.reshape(x_rep.shape)

    return run(staged, x)
