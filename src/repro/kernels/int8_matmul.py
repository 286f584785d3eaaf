"""Tiled INT8 matmul Pallas kernel (the ITC-baseline compute path).

Tile shapes / grid
    Grid (M/bm, N/bn, K/bk), K innermost for accumulation; int8 tiles are
    MXU-fed with int32 accumulation in a VMEM scratch that is zeroed at
    k==0 and stored at k==n_k-1. Block sizes default to MXU-aligned 128s —
    (bm,bk) and (bk,bn) int8 tiles are 16KB each, well inside the ~16MB
    v5e VMEM budget with double buffering.

128-tile zero-padding contract
    The raw kernel asserts M % bm == N % bn == K % bk == 0. Callers go
    through :func:`repro.kernels.ops.int8_act_matmul`, which zero-pads
    both operands up to the 128-tile grid and slices the result back;
    zero rows/columns contribute exactly 0 to every int32 partial sum, so
    the sliced output is bit-identical to the unpadded matmul (this is
    the contract the compiled engine's eager/compiled bit-identity tests
    rely on).

interpret=None backend auto-detection
    ``interpret=None`` resolves to native Mosaic lowering when
    ``jax.default_backend() == "tpu"`` and to the Pallas interpreter
    everywhere else; the interpreter executes the identical integer math,
    so CPU CI validates the same kernel body bit-for-bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from .common import resolve_interpret


def _kernel(x_ref, w_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # int8 x int8 operands straight into the MXU; int32 accumulation
    acc_ref[...] += jax.lax.dot(x_ref[...], w_ref[...], preferred_element_type=jnp.int32)

    @pl.when(k == n_k - 1)
    def _store():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def int8_matmul(
    x_q: jax.Array,
    w_q: jax.Array,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """interpret=None auto-detects: native lowering on TPU, interpreter
    (bit-identical math) everywhere else."""
    interpret = resolve_interpret(interpret)
    m, k = x_q.shape
    k2, n = w_q.shape
    assert k == k2 and m % bm == 0 and n % bn == 0 and k % bk == 0, (x_q.shape, w_q.shape)
    n_k = k // bk
    grid = (m // bm, n // bn, n_k)
    return pl.pallas_call(
        functools.partial(_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(x_q, w_q)
