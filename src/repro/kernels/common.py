"""Shared kernel-wrapper helpers — single source for the three contracts
every Pallas wrapper in this package repeats:

* :func:`resolve_interpret` — the ``interpret=None`` backend
  auto-detection (native Mosaic lowering on TPU, the bit-identical Pallas
  interpreter everywhere else). ``repro.serve.cache`` uses the same
  function so a cache key built from ``None`` and one built from its
  resolved value can never name two different lowerings.
* :func:`pad2` — zero-padding a 2-D operand up to the tile grid (the
  128-tile padding contract documented in each kernel module).
* :func:`validate_low_bits` — the ``low_bits`` domain check. Raising
  ``ValueError`` at the ops boundary beats an assert deep in a jitted
  kernel: a bad value (say ``low_bits=2``) would otherwise silently take
  the int8 branch or trip an opaque trace-time assert.
* :func:`require_native_kernels` — the one rule for which kernel
  variants have a native Mosaic lowering. It raises the typed
  :class:`NativeLoweringUnsupported` instead of running the interpreter
  or another lowering; the ops boundary and plan acceptance
  (``repro.core.ditto.plan.require_native_lowering``) both call it.
  :class:`ShardedLoweringUnsupported` is the matching refusal of a
  batch-sharded (``dp > 1``) dispatch.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["DEFAULT_LOW_BITS", "NativeLoweringUnsupported", "ShardedLoweringUnsupported",
           "resolve_interpret", "pad2", "require_native_kernels", "validate_low_bits"]

#: The int8-everywhere default; DittoPlan.low_bits and every kernel
#: signature share this one constant so the defaults cannot drift.
DEFAULT_LOW_BITS = 8


def resolve_interpret(interpret: bool | None) -> bool:
    """None -> True unless running on a real TPU (see module docstring)."""
    return jax.default_backend() != "tpu" if interpret is None else bool(interpret)


def pad2(a: jax.Array, br: int, bc: int, fill: int = 0) -> jax.Array:
    """Zero-pad a (R, C) array so R % br == C % bc == 0."""
    r, c = a.shape
    pr, pc = (-r) % br, (-c) % bc
    if pr or pc:
        a = jnp.pad(a, ((0, pr), (0, pc)), constant_values=fill)
    return a


def validate_low_bits(low_bits: int) -> int:
    """Only 4 (packed-int4 low tiles) and 8 (int8 everywhere) exist."""
    if low_bits not in (4, 8):
        raise ValueError(
            f"low_bits must be 4 (packed-int4 low-tile branch) or 8 (int8), "
            f"got {low_bits!r}")
    return low_bits


class NativeLoweringUnsupported(NotImplementedError):
    """The plan selects a kernel variant that only runs in interpret mode."""

    def __init__(self, kernel: str, knob: str):
        self.kernel = kernel
        super().__init__(
            f"{kernel} has no native TPU lowering ({knob}); serve this plan "
            f"with the default two-pass kernels or with interpret=True")


class ShardedLoweringUnsupported(NotImplementedError):
    """A batch-sharded (``dp > 1``) dispatch under native lowering. GSPMD
    cannot partition a Mosaic kernel, and gathering the batch onto every
    chip would not be data parallelism, so such a plan is refused."""


def require_native_kernels(*, fused: bool, low_bits: int) -> None:
    """Raise :class:`NativeLoweringUnsupported` for a diff-step variant
    with no Mosaic lowering: the fused single-pass kernels and the int4
    branch both split K lanes with a reshape Mosaic refuses."""
    if fused:
        raise NativeLoweringUnsupported("diff_encode_fused/ditto_fused_matmul", "fused=True")
    if validate_low_bits(low_bits) == 4:
        raise NativeLoweringUnsupported("ditto_diff_matmul int4 branch", "low_bits=4")
