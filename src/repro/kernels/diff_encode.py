"""Encoding-Unit kernel: temporal-difference classification per tile.

TPU adaptation of the paper's Encoding Unit (§V-B): instead of
element-granular zero/low/full classification + reorder queues (an ASIC
datapath the MXU cannot express), one pass over (x_t, x_prev) produces a
per-(bm, bk)-tile class:

    0 = zero tile (max|Δ| == 0)             -> the matmul kernel skips it
    1 = low  tile (max|Δ| <= LOW_BIT_MAX)   -> packed-int4 path (signed 4-bit)
    2 = full tile                           -> full 8-bit path

:data:`LOW_BIT_MAX` (= 7, the largest signed-4-bit magnitude) defined
here is THE low-bit threshold of the whole repo — ``core.ditto.classify``,
``core.ditto.bops``, ``kernels.ref`` and ``kernels.int4_pack`` all import
it, so the Encoding-Unit verdict, the element-granular accounting and the
int4 pack contract can never disagree.

The Δ itself is NOT written back to HBM: the consumer kernel re-derives it
from the same int8 operands in VMEM (subtract-on-the-fly, exactly like the
Encoding Unit feeding the Compute Unit through the pipeline).

Tile shapes / grid
    Grid (M/bm, K/bk) over (bm, bk) int8 input tiles (128x128 default);
    the output is ONE int32 class per tile, shape (M/bm, K/bk) — the map
    ``ditto_diff_matmul`` consumes through its scalar-prefetch slot. The
    map is one whole-array SMEM output that grid step (i, j) writes at
    [i, j]: a per-tile (1, 1) VMEM block breaks Mosaic's (8, 128) rule
    for the last two block dims.

128-tile zero-padding contract
    The raw kernel asserts M % bm == K % bk == 0; callers use
    :func:`repro.kernels.ops.encode_classes`, which zero-pads BOTH
    operands identically. Padding rows/cols contribute Δ == 0, so they
    can only lower a tile's max|Δ| toward the zero class — never flip a
    zero tile to nonzero — and the padded classification stays exact for
    the real data (an all-padding tile is class 0 and is skipped).

interpret=None backend auto-detection
    ``interpret=None`` -> native Mosaic lowering on TPU, Pallas
    interpreter (bit-identical integer math) on any other backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from .common import resolve_interpret

LOW_BIT_MAX = 7  # largest |Δ| a signed 4-bit lane holds; see module docstring


def _kernel(xt_ref, xp_ref, cls_ref):
    d = xt_ref[...].astype(jnp.int32) - xp_ref[...].astype(jnp.int32)
    amax = jnp.max(jnp.abs(d))
    cls = jnp.where(amax == 0, 0, jnp.where(amax <= LOW_BIT_MAX, 1, 2)).astype(jnp.int32)
    cls_ref[pl.program_id(0), pl.program_id(1)] = cls


@functools.partial(jax.jit, static_argnames=("bm", "bk", "interpret"))
def diff_encode(
    x_t: jax.Array,
    x_prev: jax.Array,
    *,
    bm: int = 128,
    bk: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """x_*: (M, K) int8 -> tile classes (M/bm, K/bk) int32.

    interpret=None auto-detects: native lowering on TPU, interpreter
    (bit-identical math) everywhere else."""
    interpret = resolve_interpret(interpret)
    m, k = x_t.shape
    assert m % bm == 0 and k % bk == 0, (x_t.shape, bm, bk)
    grid = (m // bm, k // bk)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j: (i, j)),
            pl.BlockSpec((bm, bk), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((m // bm, k // bk), jnp.int32),
        interpret=interpret,
    )(x_t, x_prev)
