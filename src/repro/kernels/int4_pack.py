"""Packed-int4 lane format for low-class difference tiles (paper §IV/§V-B).

The Encoding Unit's class-1 verdict (``diff_encode``: ``max|Δ| <=``
:data:`LOW_BIT_MAX`) guarantees every element of a low tile fits a signed
4-bit lane. This module defines the storage word the int4 execution branch
of ``ditto_diff_matmul`` uses for those tiles: TWO adjacent-K lanes per
int8 byte,

    word = (d[2c+1] << 4) | (d[2c] & 0xF)          (two's-complement nibbles)

i.e. the EVEN K lane lives in bits 0-3 and the ODD K lane in bits 4-7 of
one int8. Unpacking is pure bit arithmetic — arithmetic right shift
recovers the high lane, ``((w & 0xF) ^ 8) - 8`` sign-extends the low lane
— and is EXACT for every value in [-8, 7]; the class-1 contract
(``|Δ| <= LOW_BIT_MAX = 7``) is strictly inside that range, so
``unpack_int4(pack_int4(d)) == d`` bit-for-bit on every low tile. That
round-trip exactness is what makes the int4 branch of the diff matmul
bit-identical to the int8 branch (property-tested in
tests/test_kernel_properties.py).

On an int4-capable backend the packed word feeds two 4-bit multiplier
lanes directly (the Ditto PE of the paper). The kernels that consume it
here (the ``low_bits=4`` branch of ``ditto_diff_matmul`` and
``fused_step``) run in interpret mode only: Mosaic refuses their even/odd
K-lane reshape, so under native TPU lowering ``ops.ditto_linear_step``
refuses them with ``NativeLoweringUnsupported``. The cost model
(``core.ditto.bops`` / ``hwmodel``) prices the 4-bit lanes from the
measured tile-class mix.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .diff_encode import LOW_BIT_MAX

__all__ = ["LOW_BIT_MAX", "pack_int4", "unpack_int4", "unpack_int4_lanes"]


def pack_int4(d: jax.Array) -> jax.Array:
    """(..., K) int Δ with K even -> (..., K/2) int8, two int4 lanes/byte.

    Lossless iff every element is in [-8, 7]; class-1 tiles satisfy the
    stricter ``|Δ| <= LOW_BIT_MAX``.
    """
    k = d.shape[-1]
    assert k % 2 == 0, f"K must be even to pair int4 lanes, got {k}"
    d32 = d.astype(jnp.int32).reshape(d.shape[:-1] + (k // 2, 2))
    lo = d32[..., 0]  # even K lane -> bits 0-3
    hi = d32[..., 1]  # odd  K lane -> bits 4-7
    return ((hi << 4) | (lo & 0xF)).astype(jnp.int8)


def unpack_int4_lanes(p: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(..., K/2) int8 packed words -> (even, odd) int32 lane planes, each
    (..., K/2). Pure bit arithmetic — no strided slicing — so the kernel's
    int4 branch can consume the planes directly."""
    p32 = p.astype(jnp.int32)
    lo = ((p32 & 0xF) ^ 8) - 8  # sign-extend bits 0-3 (even K lane)
    hi = p32 >> 4  # arithmetic shift sign-extends bits 4-7 (odd K lane)
    return lo, hi


def unpack_int4(p: jax.Array) -> jax.Array:
    """(..., K/2) int8 packed words -> (..., K) int32 lanes (exact inverse
    of :func:`pack_int4` for lane values in [-8, 7])."""
    lo, hi = unpack_int4_lanes(p)
    return jnp.stack([lo, hi], axis=-1).reshape(p.shape[:-1] + (p.shape[-1] * 2,))
