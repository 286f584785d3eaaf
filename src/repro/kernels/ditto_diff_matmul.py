"""Ditto Compute-Unit kernel: tile-skipping temporal-difference matmul.

    y_t = y_prev + (x_t - x_prev) @ W        (all-int32 exact)

TPU adaptation of the paper's zero-skipping adder-tree PE (PAPER.md):
the grid runs over (M/bm, N/bn, K/bk); for each (i, kk) the per-tile class
from ``diff_encode`` gates the MXU contribution with ``@pl.when`` — a
zero-class tile issues NO dot (its Δ is all-zero, so skipping is exact).
Δ spans [-254, 254] and does not fit int8, and the MXU takes int8
operands, so a non-zero tile contributes ``x_t·W - x_prev·W``: two
int8 x int8 dots, each exact in the int32 accumulator. No Δ tensor ever
lands in HBM, and no operand is widened before the MXU.

``classes`` rides the scalar-prefetch slot (PrefetchScalarGridSpec) so a
production TPU lowering can in principle skip the HBM->VMEM copies of
skipped tiles too; in interpret mode it is a plain operand. This module
is the TWO-PASS path (encode pass, then this matmul pass) and serves as
the reference oracle for the single-pass fused kernel
(``kernels.fused_step``), which additionally remaps skipped tiles' block
indices through prefetched hold maps so their DMAs are elided.

int4 low-tile execution branch (``low_bits=4``)
    Class-1 tiles (``max|Δ| <= LOW_BIT_MAX``) execute through the packed
    int4 path instead of the full int8 dot: the Δ tile re-derived in VMEM
    is packed two int4 lanes per int8 (``kernels.int4_pack``), the packed
    words are unpacked by bit arithmetic, and the even/odd K lanes are
    dotted against the even/odd weight rows into the SAME int32
    accumulator. Because pack->unpack is exact for |Δ| <= 7 — which the
    class-1 verdict guarantees — the int4 branch is BIT-IDENTICAL to the
    int8 branch on every class-1 tile (tests/test_kernel_properties.py
    proves this across the shape matrix). Class-2 tiles always take the
    two int8 dots. With the default ``low_bits=8`` the class-1/class-2
    predicate stays merged and low tiles run int8 (the pre-int4 behavior).
    The int4 branch runs in interpret mode only: Mosaic refuses its
    lane-splitting reshape, and ``ops.ditto_linear_step`` refuses
    ``low_bits=4`` under native lowering.

Optional y_prev operand
    ``y_prev=None`` drops the (bm, bn) int32 y_prev operand entirely —
    the accumulator seeds from zero and the kernel returns the bare diff
    contribution ``(x_t - x_prev) @ W``. The int32 y_prev block is the
    single largest per-grid-step operand (4x an int8 tile), so callers
    that add y_prev elsewhere (the attention identity, the fused path's
    epilogue) should never pass a zeros tensor just to satisfy the
    operand list.

Transposed-weight layout (``w_transposed=True``)
    ``w_q`` arrives as (N, K) — the natural layout of an activation used
    as the stationary operand in the attention identity (Q_t, K_prev) —
    and the kernel's weight index map fetches (bn, bk) blocks at (j, kk),
    contracting the shared K axis via ``dot_general``. No (K, N)
    transpose is ever materialized in HBM.

Tile shapes / grid
    Grid (M/bm, N/bn, K/bk), K innermost; (bm,bk) int8 x/x_prev tiles and
    a (bk,bn) int8 weight tile feed the MXU, accumulating into a (bm,bn)
    int32 VMEM scratch seeded from y_prev (or zeros) at k==0. Defaults
    are the MXU-aligned 128s (``low_bits=4`` additionally needs bk even
    to pair lanes). ``classes`` has shape (M/bm, K/bk) — one class per
    (i, kk) tile from ``diff_encode``.

Zero-tile skipping
    ``@pl.when(tile_cls > 0)`` gates the subtract + dot: a zero-class
    tile issues NO MXU work. Skipping is exact (not approximate) because
    class 0 means max|Δ| == 0, i.e. the skipped contribution is
    identically zero — so the output is bit-identical to the dense diff
    matmul regardless of how many tiles were skipped.

128-tile zero-padding contract
    The raw kernel asserts all dims divide the block sizes; callers use
    :func:`repro.kernels.ops.ditto_linear_step`, which zero-pads x_t,
    x_prev, W and y_prev to the tile grid. Padded Δ regions are exactly 0
    (both operands get the same padding), so padded tiles classify as
    zero/skippable and the sliced result is bit-identical to unpadded.

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

from .common import DEFAULT_LOW_BITS, resolve_interpret, validate_low_bits
from .int4_pack import pack_int4, unpack_int4_lanes


def _dot_w(d, w_tile, *, w_t: bool):
    """d (bm, k') @ weight tile -> (bm, bn) int32; the tile is (k', bn)
    normally or (bn, k') when ``w_t`` (contract the shared last axis)."""
    if w_t:
        return jax.lax.dot_general(
            d, w_tile, (((1,), (1,)), ((), ())), preferred_element_type=jnp.int32)
    return jax.lax.dot(d, w_tile, preferred_element_type=jnp.int32)


def _w_lane_pair(w_tile, *, w_t: bool):
    """Split a weight tile into (even, odd) K-lane halves matching the
    int4 lane planes: each half contracts a k'=bk/2 axis."""
    if w_t:
        bn, bk = w_tile.shape
        pairs = w_tile.reshape(bn, bk // 2, 2)
        return pairs[:, :, 0], pairs[:, :, 1]
    bk, bn = w_tile.shape
    pairs = w_tile.reshape(bk // 2, 2, bn)
    return pairs[:, 0, :], pairs[:, 1, :]


def _kernel(cls_ref, xt_ref, xp_ref, w_ref, *rest, n_k: int, split_low: bool,
            has_yp: bool, w_t: bool):
    """``split_low`` (trace-static, = ``low_bits == 4``) splits the merged
    class>0 predicate: class-1 tiles take the packed-int4 branch, class-2
    the int8 dot. One body for both modes keeps the accumulator seeding /
    store and the full dot a single source of truth. ``has_yp`` selects
    the y_prev-seeded vs zero-seeded accumulator; ``w_t`` the (N, K)
    weight layout."""
    if has_yp:
        yp_ref, o_ref, acc_ref = rest
    else:
        o_ref, acc_ref = rest
    i, j, kk = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = yp_ref[...] if has_yp else jnp.zeros_like(acc_ref)

    tile_cls = cls_ref[i, kk]

    @pl.when(tile_cls == 2 if split_low else tile_cls > 0)
    def _accum_full():
        # Δ spans [-254, 254] and does not fit int8, so the tile runs as two
        # int8 x int8 MXU dots: Δ @ W = x_t @ W - x_prev @ W, exact in int32
        w = w_ref[...]
        acc_ref[...] += (_dot_w(xt_ref[...], w, w_t=w_t)
                         - _dot_w(xp_ref[...], w, w_t=w_t))

    if split_low:

        @pl.when(tile_cls == 1)
        def _accum_low():
            # class-1 contract: max|Δ| <= LOW_BIT_MAX, so every lane fits a
            # signed nibble and the pack->unpack round-trip below is exact
            d = xt_ref[...].astype(jnp.int32) - xp_ref[...].astype(jnp.int32)
            packed = pack_int4(d)  # (bm, bk/2) int8 — the int4x2 storage word
            lo, hi = unpack_int4_lanes(packed)  # even/odd K lane planes, int32
            w_even, w_odd = _w_lane_pair(w_ref[...].astype(jnp.int32), w_t=w_t)
            acc_ref[...] += _dot_w(lo, w_even, w_t=w_t) + _dot_w(hi, w_odd, w_t=w_t)

    @pl.when(kk == n_k - 1)
    def _store():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret",
                                             "low_bits", "w_transposed"))
def ditto_diff_matmul(
    x_t: jax.Array,
    x_prev: jax.Array,
    w_q: jax.Array,
    y_prev: jax.Array | None,
    classes: jax.Array,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool | None = None,
    low_bits: int = DEFAULT_LOW_BITS,
    w_transposed: bool = False,
) -> jax.Array:
    """x_*: (M,K) int8; w_q: (K,N) int8 — or (N,K) with ``w_transposed``;
    y_prev: (M,N) int32 or None (zero-seeded, returns the bare diff
    contribution); classes: (M/bm, K/bk) int32 from diff_encode.
    Returns y_t int32.

    low_bits=8 runs low tiles on the int8 dot (one merged class-1/2
    predicate); low_bits=4 routes class-1 tiles through the packed-int4
    branch — bit-identical output either way (the class-1 verdict bounds
    |Δ| inside the exact pack/unpack range).

    interpret=None auto-detects: native lowering on TPU, interpreter
    (bit-identical math) everywhere else."""
    interpret = resolve_interpret(interpret)
    validate_low_bits(low_bits)
    m, k = x_t.shape
    n, k2 = w_q.shape if w_transposed else w_q.shape[::-1]
    assert k == k2 and m % bm == 0 and n % bn == 0 and k % bk == 0
    assert classes.shape == (m // bm, k // bk), (classes.shape, (m // bm, k // bk))
    if low_bits == 4:
        assert bk % 2 == 0, f"low_bits=4 pairs K lanes: bk must be even, got {bk}"
    n_k = k // bk
    grid = (m // bm, n // bn, n_k)
    has_yp = y_prev is not None
    if w_transposed:
        w_spec = pl.BlockSpec((bn, bk), lambda i, j, kk, cls: (j, kk))
    else:
        w_spec = pl.BlockSpec((bk, bn), lambda i, j, kk, cls: (kk, j))
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, kk, cls: (i, kk)),
        pl.BlockSpec((bm, bk), lambda i, j, kk, cls: (i, kk)),
        w_spec,
    ]
    operands = [classes, x_t, x_prev, w_q]
    if has_yp:
        in_specs.append(pl.BlockSpec((bm, bn), lambda i, j, kk, cls: (i, j)))
        operands.append(y_prev)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk, cls: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, n_k=n_k, split_low=low_bits == 4,
                          has_yp=has_yp, w_t=w_transposed),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        interpret=interpret,
    )(*operands)
