"""jit'd public wrappers over the Pallas kernels.

``interpret`` auto-detects the backend: real TPU lowers natively; anywhere
else the kernel body executes in interpret mode (bit-identical math, used
for all CPU validation in this repo).

The high-level entry is :func:`ditto_linear_step`: quantized temporal-
difference linear layer — either the two-pass flow (diff_encode ->
ditto_diff_matmul) or, with ``fused=True``, the single-pass fused kernel
(``kernels.fused_step``: one encode+pack pass, then a matmul whose
scalar-prefetched hold maps elide the DMAs of skipped tiles and whose
y_prev lands as an epilogue). Both flows are bit-identical; the two-pass
path is the reference oracle. :func:`attention_delta` composes the
paper's two-sub-op attention identity from the same diff kernel without
materializing transposes or zero y_prev tensors.

``low_bits`` is validated here (ValueError on anything but 4 or 8) so a
bad value fails loudly at the API boundary instead of silently running
the wrong branch inside a jitted kernel.

Every public wrapper accepts ``plan=`` — a ``repro.core.ditto.DittoPlan``
(duck-typed: anything with ``block`` / ``interpret`` / ``low_bits`` /
``fused`` attributes works, which keeps this kernels layer free of a
dependency on ``repro.core``). A plan overrides the per-knob kwargs,
which remain as the micro-API for kernel tests and benchmarks that need
non-square ``bm/bn/bk`` tiles.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import (DEFAULT_LOW_BITS, pad2, require_native_kernels, resolve_interpret,
                     validate_low_bits)
from .diff_encode import diff_encode
from .ditto_diff_matmul import ditto_diff_matmul
from .fused_step import diff_encode_fused, ditto_fused_matmul
from .int8_matmul import int8_matmul


def _plan_knobs(plan, bm, bn, bk, interpret, low_bits, fused):
    """Resolve (plan | per-knob kwargs) to one kernel config; plan wins."""
    if plan is None:
        return bm, bn, bk, interpret, low_bits, fused
    b = plan.block
    return b, b, b, plan.interpret, plan.low_bits, plan.fused


def int8_act_matmul(x_q, w_q, *, plan=None, bm=128, bn=128, bk=128, interpret=None,
                    low_bits=DEFAULT_LOW_BITS, fused=False):
    """(M,K) int8 @ (K,N) int8 -> (M,N) int32, exact (act-mode ITC path).

    Pads both operands to the (bm, bn, bk) tile grid with zeros — padding
    contributes nothing to the int32 accumulation, so the sliced result is
    bit-identical to the unpadded matmul.

    ``low_bits`` and ``fused`` are accepted (validated, then ignored) for
    call-site uniformity with the diff path: the act GEMM has no Δ
    operand, so there is nothing to narrow or skip — the compiled engine
    passes one plan to every mode's op.
    """
    bm, bn, bk, interpret, low_bits, fused = _plan_knobs(
        plan, bm, bn, bk, interpret, low_bits, fused)
    validate_low_bits(low_bits)
    del low_bits, fused
    interpret = resolve_interpret(interpret)
    m, k = x_q.shape
    n = w_q.shape[1]
    xp = pad2(x_q, bm, bk)
    wp = pad2(w_q, bk, bn)
    return int8_matmul(xp, wp, bm=bm, bn=bn, bk=bk, interpret=interpret)[:m, :n]


def quantized_matmul(x_q, w_q, x_scale, w_scale, *, bm=128, bn=128, bk=128, interpret=None):
    """int8 x int8 -> fp32 with scales (baseline act-mode path)."""
    y = int8_act_matmul(x_q, w_q, bm=bm, bn=bn, bk=bk, interpret=interpret)
    return y.astype(jnp.float32) * x_scale * w_scale[None, :]


def encode_classes(x_t_q, x_prev_q, *, bm=128, bk=128, interpret=None):
    interpret = resolve_interpret(interpret)
    xt = pad2(x_t_q, bm, bk)
    xp = pad2(x_prev_q, bm, bk)
    return diff_encode(xt, xp, bm=bm, bk=bk, interpret=interpret)


def ditto_linear_step(
    x_t_q, x_prev_q, w_q, y_prev_i32=None, *, plan=None, bm=128, bn=128, bk=128,
    interpret=None, low_bits=DEFAULT_LOW_BITS, fused=False, w_transposed=False,
):
    """One temporal-difference linear step, tile-skipped.

    Returns (y_t_i32 (M,N), classes (M/bm, K/bk)) — exact int32, equal to
    y_prev + (x_t - x_prev) @ W regardless of how many tiles were skipped.
    ``y_prev_i32=None`` returns the bare diff contribution without ever
    materializing (or moving) a zeros tensor. ``w_transposed`` takes W as
    (N, K) and folds the transpose into the kernel's weight index map —
    no (K, N) copy lands in HBM.

    ``fused=True`` runs the single-pass flow (``kernels.fused_step``):
    class map + encoded Δ stream (int4 nibble plane + class-2 high plane)
    in one encode pass, then one matmul pass that never touches raw
    activations — its prefetched hold maps remap every skipped tile's
    block index to the pipeline-resident block (zero-class tiles DMA
    nothing, low tiles stream the half-width nibble plane instead of
    re-deriving Δ per output column) and y_prev is a fused epilogue add.
    Bit-identical to the two-pass oracle for every mix/low_bits/y_prev
    combination.

    ``low_bits=4`` executes class-1 tiles of the two-pass flow through
    the packed-int4 branch of ``ditto_diff_matmul``; the fused flow
    always executes class-1 tiles from the int4-packed Δ-cache (that is
    its storage format) — bit-identical either way (the class-1 verdict
    bounds |Δ| inside the exact pack/unpack range).

    Both of those variants run in interpret mode only: under native
    lowering they raise ``NativeLoweringUnsupported`` naming the refused
    kernel, and nothing falls back to another lowering.
    """
    bm, bn, bk, interpret, low_bits, fused = _plan_knobs(
        plan, bm, bn, bk, interpret, low_bits, fused)
    validate_low_bits(low_bits)
    interpret = resolve_interpret(interpret)
    if not interpret:
        require_native_kernels(fused=fused, low_bits=low_bits)
    m, k = x_t_q.shape
    n = w_q.shape[0] if w_transposed else w_q.shape[1]
    xt = pad2(x_t_q, bm, bk)
    xp = pad2(x_prev_q, bm, bk)
    wp = pad2(w_q, bn, bk) if w_transposed else pad2(w_q, bk, bn)
    yp = None if y_prev_i32 is None else pad2(y_prev_i32, bm, bn)
    if fused:
        classes, dc, dh = diff_encode_fused(xt, xp, bm=bm, bk=bk, interpret=interpret)
        y = ditto_fused_matmul(wp, dc, dh, classes, bm=bm, bn=bn, bk=bk,
                               interpret=interpret, w_transposed=w_transposed)
        if yp is not None:
            y = y + yp  # epilogue: one fused XLA add, not a kernel operand pass
    else:
        classes = diff_encode(xt, xp, bm=bm, bk=bk, interpret=interpret)
        y = ditto_diff_matmul(xt, xp, wp, yp, classes, bm=bm, bn=bn, bk=bk,
                              interpret=interpret, low_bits=low_bits,
                              w_transposed=w_transposed)
    return y[:m, :n], classes


def attention_delta(q_t, q_prev, k_t, k_prev, s_prev_i32, *, plan=None, interpret=None,
                    **blk):
    """Paper §IV-A attention identity via two diff-matmuls:

        S_t = S_prev + Q_t ΔK^T + ΔQ K_prev^T

    q_*: (M, D) int8; k_*: (N, D) int8; s_prev: (M, N) int32. Exact.
    Returns (S_t, (cls_dk, cls_dq)) — the tile-class maps of BOTH
    sub-operations (ΔK and ΔQ), so callers can histogram every tile the
    kernels actually executed. ``low_bits`` in ``blk`` routes class-1
    tiles of both sub-ops through the packed-int4 branch; ``fused`` runs
    both sub-ops through the single-pass fused kernel.

    Neither sub-op materializes anything extra in HBM: the stationary
    activation (Q_t, K_prev) feeds the kernel in its natural (rows, D)
    layout via ``w_transposed`` — the transpose lives in the weight index
    map — and y_prev is omitted entirely (no zeros tensor, no y_prev
    operand pass); S_prev joins in the epilogue sum below.
    """
    if plan is not None:
        blk = {}
        interpret = plan.interpret
    interpret = resolve_interpret(interpret)
    #   Q_t ΔK^T  = ((k_t - k_prev) @ Q_t^T)^T   — x = K rows, W = Q_t (N,K) layout
    #   ΔQ K_prev^T = (q_t - q_prev) @ K_prev^T  — W = K_prev in (N,K) layout
    y1, cls_dk = ditto_linear_step(k_t, k_prev, q_t, None, plan=plan,
                                   interpret=interpret, w_transposed=True, **blk)
    y2, cls_dq = ditto_linear_step(q_t, q_prev, k_prev, None, plan=plan,
                                   interpret=interpret, w_transposed=True, **blk)
    return s_prev_i32 + y1.T + y2, (cls_dk, cls_dq)
