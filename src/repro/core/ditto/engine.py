"""The Ditto temporal-difference processing engine (paper §IV).

The engine intercepts every linear operation of a denoiser during the
reverse-diffusion loop and executes it in one of three modes:

  act     : direct quantized GEMM  y = W_q · q_t                (step 1, and
            layers Defo decides to keep)
  diff    : temporal differences   y_t = y_{t+1} + W_q · Δq     (steps >= 2)
  spatial : Diffy-style row deltas (Defo+ for act-mode layers)

All difference math is exact in the integer domain (int16 deltas, int32
accumulation), so `diff` is bit-identical to `act` under a shared scale —
property-tested. Per layer and per step the engine records zero/low/full
fractions, BOPs, simulated memory traffic and cycle estimates; Defo uses
the step-1 (act) and step-2 (diff) cycles to fix each layer's mode for the
remaining steps (§IV-B), with 'defo+' additionally allowing spatial mode.

Layers declare ``boundary_in/out`` metadata from the static graph analysis
(defo.py): when False, the diff-domain passes through (difference
calculation / summation bypass), removing the extra x_prev/y_prev traffic
the paper measures in Fig. 8.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from . import bops as bops_mod
from . import classify, quant
from .hwmodel import HwModel, DEFAULT_HW


@dataclasses.dataclass
class LayerMeta:
    name: str
    kind: str = "dense"  # dense | attn_qk | attn_pv
    boundary_in: bool = True  # input produced by a non-linear op
    boundary_out: bool = True  # output consumed by a non-linear op


@dataclasses.dataclass
class _LayerState:
    w: quant.QTensor | None = None
    bias: jax.Array | None = None
    x_scale: jax.Array | None = None
    x_prev: jax.Array | None = None  # int8 of previous step
    y_prev: jax.Array | None = None  # int32 accumulation of previous step
    mode: str = "act"
    # attention state
    a_prev: jax.Array | None = None  # lhs int8 of previous step
    b_prev: jax.Array | None = None  # rhs int8 of previous step
    a_scale: jax.Array | None = None
    b_scale: jax.Array | None = None


class DittoEngine:
    """policy: 'act' | 'diff' | 'spatial' | 'defo' | 'defo+'."""

    def __init__(self, policy: str = "defo", hw: HwModel = DEFAULT_HW, collect_oracle: bool = False):
        assert policy in ("act", "diff", "spatial", "defo", "defo+")
        self.policy = policy
        self.hw = hw
        self.collect_oracle = collect_oracle
        self.layers: dict[str, _LayerState] = {}
        self.meta: dict[str, LayerMeta] = {}
        self.step_idx = 0
        self.records: list[dict] = []  # one per (layer, step)
        self._decided = False
        self._compiled_base = None  # cached (modes, first-record-per-layer)
        self.watchdog_events: list[dict] = []  # re-anchor events (serve watchdog)
        self._reset_counters()

    # ------------------------------------------------------------- weights
    def register_linear(self, meta: LayerMeta, w: jax.Array, bias: jax.Array | None = None):
        st = _LayerState(w=quant.quantize_weight(np.asarray(w)), bias=bias)
        self.layers[meta.name] = st
        self.meta[meta.name] = meta

    def register_attention(self, meta: LayerMeta):
        self.layers[meta.name] = _LayerState()
        self.meta[meta.name] = meta

    # --------------------------------------------------------------- steps
    def begin_sample(self):
        self.step_idx = 0
        self._decided = False
        self._compiled_base = None
        self.records = []
        self.watchdog_events = []
        self._reset_counters()
        for st in self.layers.values():
            st.x_prev = st.y_prev = None
            st.a_prev = st.b_prev = None
            st.x_scale = st.a_scale = st.b_scale = None
            st.mode = "act"

    # ------------------------------------------------------------ counters
    def _reset_counters(self):
        self.host_reads = 0  # blocking device->host reads of this sample
        self.eager_steps = 0
        self.compiled_steps = 0
        self.looped_steps = 0  # compiled steps run inside a segment loop
        # device (layers, 3) int32 (zero, low, full) tile totals of the
        # compiled steps, rows in sorted layer order (make_step_fn)
        self.tile_totals = None

    def host_read(self, v, cast=float):
        """``cast(v)``: one blocking device->host read, counted. The eager
        step reads its class fractions one scalar at a time; a compiled
        step's packed statistics are one read (``cast=jax.device_get``)."""
        self.host_reads += 1
        return cast(v)

    def counters(self) -> dict:
        """This sample's counters. ``tile_hist`` maps each diff-mode layer
        to its (zero, low, full) tile counts summed over the compiled
        steps, fetched with one ``device_get`` (not counted in
        ``host_reads``)."""
        tiles: dict[str, tuple] = {}
        if self.tile_totals is not None:
            modes = self.compiled_modes()
            rows = np.asarray(jax.device_get(self.tile_totals))
            tiles = {name: tuple(int(v) for v in row)
                     for name, row in zip(sorted(self.layers), rows)
                     if modes[name] == "diff"}
        return {"host_reads": self.host_reads, "eager_steps": self.eager_steps,
                "compiled_steps": self.compiled_steps, "looped_steps": self.looped_steps,
                "tile_hist": tiles}

    def end_step(self):
        self.step_idx += 1
        if self.step_idx == 2 and self.policy in ("defo", "defo+") and not self._decided:
            self._defo_decide()
            self._decided = True

    def _defo_decide(self):
        """Fix per-layer modes from step-1 (act) vs step-2 (diff) cycles."""
        by_layer: dict[str, dict[int, dict]] = {}
        for r in self.records:
            by_layer.setdefault(r["layer"], {})[r["step"]] = r
        for name, steps in by_layer.items():
            if 0 not in steps or 1 not in steps:
                continue
            c_act = steps[0]["cycles"]
            c_diff = steps[1]["cycles"]
            st = self.layers[name]
            if self.policy == "defo+":
                c_spatial = steps[0].get("cycles_spatial", np.inf)
                best = min((c_diff, "diff"), (c_act, "act"), (c_spatial, "spatial"))
                st.mode = best[1]
            else:
                st.mode = "diff" if c_diff < c_act else "act"

    # -------------------------------------------------------------- linear
    def linear(self, name: str, x: jax.Array) -> jax.Array:
        """x: (..., K) fp32 -> (..., N) fp32 through the quantized path."""
        st = self.layers[name]
        meta = self.meta[name]
        x2 = x.reshape(-1, x.shape[-1])
        t, k = x2.shape
        n = st.w.q.shape[1]

        if st.x_scale is None:  # first-step calibration, held afterwards
            # per-sample (batch-row) scales: quantized trajectories stay
            # independent of batch composition (see quant.sample_scale)
            st.x_scale = quant.sample_scale(x2, x.shape[0] if x.ndim > 1 else 1)
        q_t = quant.quantize(x2, st.x_scale)

        mode = self._mode_for_step(st)
        rec: dict[str, Any] = {"layer": name, "step": self.step_idx, "mode": mode, "kind": meta.kind,
                               "macs": t * k * n}

        if mode == "act" or st.x_prev is None:
            y_i32 = quant.int_matmul(q_t, st.w.q)
            d_for_stats = None
            mode = "act"
            rec["mode"] = mode  # fallback executed act: keep accounting honest
        elif mode == "spatial":
            d_sp = classify.spatial_diff(q_t, axis=0)  # exact reconstructable
            # y rows: y[0] = W q[0]; y[i] = y[i-1] + W d[i] — mathematically
            # W·q via prefix sums; numerically identical to act:
            y_i32 = quant.int_matmul(q_t, st.w.q)
            d_for_stats = d_sp[1:]  # first row stays full-precision
        else:  # temporal diff
            d = q_t.astype(jnp.int16) - st.x_prev.astype(jnp.int16)
            y_i32 = st.y_prev + quant.int_matmul(d, st.w.q)
            d_for_stats = d

        # ---- statistics / cost model ----
        self._account(rec, t, k, n, q_t, d_for_stats, meta)
        self.records.append(rec)

        st.x_prev = q_t
        st.y_prev = y_i32
        y = y_i32.astype(jnp.float32) * st.x_scale * st.w.scale[None, :]
        if st.bias is not None:
            y = y + st.bias
        return y.reshape(x.shape[:-1] + (n,))

    # ----------------------------------------------------------- attention
    def attention_matmul(self, name: str, a: jax.Array, b: jax.Array) -> jax.Array:
        """Two-operand matmul a @ b^T where BOTH change across steps
        (Q·K^T and P·V). Paper identity:
            A_t B_t^T = A_{t+1}B_{t+1}^T + A_t ΔB^T + ΔA B_{t+1}^T
        a: (..., M, D), b: (..., N, D) -> (..., M, N). Quantized per step
        with held scales; the two sub-operations run on Δ operands.
        """
        st = self.layers[name]
        meta = self.meta[name]
        lead = a.shape[:-2]
        m, d_ = a.shape[-2], a.shape[-1]
        n = b.shape[-2]
        a2 = a.reshape(-1, m, d_)
        b2 = b.reshape(-1, n, d_)

        if st.a_scale is None:
            # per-(sample, head) scales — same batch-composition invariance
            # as the linear path (quant.sample_scale)
            st.a_scale = quant.sample_scale(a2, a2.shape[0])
            st.b_scale = quant.sample_scale(b2, b2.shape[0])
        qa = quant.quantize(a2, st.a_scale)
        qb = quant.quantize(b2, st.b_scale)

        mode = self._mode_for_step(st)
        rec: dict[str, Any] = {"layer": name, "step": self.step_idx, "mode": mode, "kind": meta.kind,
                               "macs": a2.shape[0] * m * n * d_}

        def bmm(x_, y_):
            return jnp.einsum("bmd,bnd->bmn", x_.astype(jnp.int32), y_.astype(jnp.int32))

        if mode in ("act", "spatial") or st.a_prev is None:
            y_i32 = bmm(qa, qb)
            d_for_stats = None
            mode = "act"
            rec["mode"] = mode  # fallback executed act: keep accounting honest
        else:
            da = qa.astype(jnp.int16) - st.a_prev.astype(jnp.int16)
            db = qb.astype(jnp.int16) - st.b_prev.astype(jnp.int16)
            #   A_t ΔB^T + ΔA B_{t+1}^T  (A_t treated as weight, B_prev as weight)
            y_i32 = st.y_prev + bmm(qa, db.astype(jnp.int32)) + bmm(da.astype(jnp.int32), st.b_prev)
            d_for_stats = jnp.concatenate([da.reshape(-1), db.reshape(-1)])

        self._account(rec, a2.shape[0] * m, d_, n, jnp.concatenate([qa.reshape(-1), qb.reshape(-1)]),
                      d_for_stats, meta, attention=True)
        self.records.append(rec)

        st.a_prev, st.b_prev, st.y_prev = qa, qb, y_i32
        y = y_i32.astype(jnp.float32) * st.a_scale * st.b_scale
        return y.reshape(lead + (m, n))

    # ------------------------------------------------------------ internals
    def _mode_for_step(self, st: _LayerState) -> str:
        if self.step_idx == 0:
            return "spatial" if self.policy in ("spatial", "defo+") else "act"
        if self.policy == "act":
            return "act"
        if self.policy == "diff":
            return "diff"
        if self.policy == "spatial":
            return "spatial"
        if self.step_idx == 1:  # defo probes diff on step 2
            return "diff"
        return st.mode

    def _account(self, rec, t, k, n, q_t, d, meta, *, attention=False):
        # --- class fractions, per candidate mode (the simulator re-prices
        # each hardware design from these; see repro.sim) ---
        read = self.host_read
        q_cls = classify.element_classes(q_t)
        cls_act = (read(q_cls["zero"]), 0.0, read(q_cls["low"] + q_cls["full"]))
        cls_diff = None
        if d is not None:
            cls = classify.element_classes(d)
            cls_diff = (read(cls["zero"]), read(cls["low"]), read(cls["full"]))
        self._account_classes(rec, t, k, n, cls_act, cls_diff, meta, attention=attention)
        hw = self.hw
        macs = rec["macs"]
        mem_cycles = rec["mem_cycles"]
        # spatial-mode counterfactual for Defo+ / the simulator
        if (self.step_idx == 0 and self.policy in ("defo+",)) or self.collect_oracle:
            q2 = q_t.reshape(t, k) if not attention else None
            if q2 is not None and t > 1:
                ds = classify.spatial_diff(q2, axis=0)[1:]
                cs = classify.element_classes(ds)
                z2, l2, f2 = read(cs["zero"]), read(cs["low"]), read(cs["full"])
                # the first row stays full precision
                w0 = 1.0 / t
                rec["cls_spatial"] = (z2 * (1 - w0), l2 * (1 - w0), f2 * (1 - w0) + w0)
                eff2 = macs * ((1 - w0) * hw.lanes_mixed(z2, l2, f2) + w0 * hw.lanes_full)
                cc2 = eff2 / (hw.n_pe * hw.mults_per_pe)
                rec["cycles_spatial"] = max(cc2, mem_cycles) + min(cc2, mem_cycles) * hw.overlap_slack
                rec["bops_spatial"] = bops_mod.bops_mixed(macs, *rec["cls_spatial"])

    def _account_classes(self, rec, t, k, n, cls_act, cls_diff, meta, *, attention=False,
                         cls_spatial=None):
        """Price one record from precomputed class fractions.

        This is the fraction-level core of ``_account``: the eager path
        feeds it fractions measured from the materialized Δ tensors, the
        compiled path feeds it fractions reduced on-device inside the jitted
        step (``record_compiled_step``) — both produce the same schema the
        simulator (repro.sim.cycles) prices.

        The executed-mode stats (zero/low/full, bops, cycles) come from
        ``cls_diff`` only when the record's mode actually ran in the diff
        domain; an act record may still CARRY a candidate ``cls_diff`` /
        ``cls_spatial`` so the simulator can re-price other designs'
        mode choices at scaled dimensions.
        """
        hw = self.hw
        macs = rec["macs"]
        rec.update(t=t, k=k, n=n, attention=attention,
                   boundary_in=meta.boundary_in, boundary_out=meta.boundary_out)
        rec["cls_act"] = cls_act
        if cls_diff is not None:
            rec["cls_diff"] = cls_diff
        if cls_spatial is not None:
            rec["cls_spatial"] = cls_spatial
        executed_diff = cls_diff is not None and rec["mode"] in ("diff", "spatial")
        zero, low, full = cls_diff if executed_diff else cls_act
        rec.update(zero=zero, low=low, full=full)
        # --- BOPs ---
        rec["bops_act"] = bops_mod.bops_act(macs)
        rec["bops"] = bops_mod.bops_mixed(macs, zero, low, full) if executed_diff else rec["bops_act"]
        # --- memory traffic (bytes; mirrors repro.sim.cycles._mem_split) ---
        w_bytes = k * n if not attention else 0  # weights stream once
        act_bytes = t * k + t * n  # read x, write y (int8)
        mem = w_bytes + act_bytes
        if rec["mode"] == "diff":
            extra = 4 * t * n  # y_prev read + y_t write (16-bit store)
            if meta.boundary_in:
                extra += 2 * t * k  # x_prev read + x_t write
            mem += extra
        rec["mem_bytes"] = mem
        # --- cycles (Ditto hardware: adder-tree PEs, 4-bit multipliers;
        # hw.lanes_mixed is the shared pricing hook with repro.sim.cycles) ---
        eff_macs = macs * (hw.lanes_mixed(zero, low, full) if executed_diff
                           else hw.lanes_full)
        compute_cycles = eff_macs / (hw.n_pe * hw.mults_per_pe)
        mem_cycles = mem / hw.bytes_per_cycle
        rec["cycles"] = max(compute_cycles, mem_cycles) + min(compute_cycles, mem_cycles) * hw.overlap_slack
        rec["compute_cycles"] = compute_cycles
        rec["mem_cycles"] = mem_cycles

    # ------------------------------------------------- compiled execution
    def ready_for_compiled(self) -> bool:
        """True once everything the compiled pass bakes in statically is
        fixed: activation scales and prev-step state exist (>= 1 eager
        step) and, for Defo policies, the per-layer mode decision has been
        made (after step 2's diff probe)."""
        if self.step_idx < 1:
            return False
        if self.policy in ("defo", "defo+") and not self._decided:
            return False
        return True

    def compiled_modes(self) -> dict[str, str]:
        """Static per-layer execution modes for the compiled pass (the mode
        ``_mode_for_step`` would return for every remaining step).

        Attention layers have no spatial path (the eager engine falls back
        to act there), so 'spatial' maps to 'act' for them.
        """
        modes: dict[str, str] = {}
        for name, st in self.layers.items():
            if self.policy in ("act", "diff", "spatial"):
                m = self.policy
            else:  # defo / defo+ after _defo_decide
                m = st.mode
            if m == "spatial" and self.meta[name].kind in ("attn_qk", "attn_pv"):
                m = "act"
            modes[name] = m
        return modes

    def record_compiled_step(self, stats, *, modes: dict[str, str] | None = None,
                             reanchor: bool = False) -> None:
        """Append records for one compiled step.

        ``stats`` (a ``compiled.PackedStats``) comes out of the jitted step
        function: the step's per-layer aux pytree packed into one float32
        vector of class fractions and one int32 vector of tile counts,
        with the aux pytree's layout as static data. It is fetched with one
        ``jax.device_get`` (one counted host read) and unpacked on the host
        into, per layer, the zero/low/full class fractions reduced
        on-device — 'cls_act' always, 'cls_diff' / 'cls_spatial' where the
        layer has the state to measure them (candidate stats are kept even
        for act-frozen layers so the simulator can re-price other designs'
        mode choices). Diff-mode layers additionally carry 'tile_hist', the
        measured (n_zero, n_low, n_full) tile-class histogram from
        ``diff_encode`` — the tiles the kernel REALLY skipped / routed
        through the packed-int4 branch; it lands on the record together
        with its tile-granular pricing ('bops_tile', 'tile_fracs').
        Layer dimensions are reused from that layer's calibration-step
        record — shapes are static across the denoising loop (same
        latents/batch), which is exactly what lets the step be jitted in
        the first place.
        """
        aux = self.host_read(stats, jax.device_get).unpack()
        self._record_aux(aux, self.step_idx, modes=modes, reanchor=reanchor)

    def record_compiled_steps(self, stats) -> None:
        """Append the records of every step of one segment loop, the
        steps from ``step_idx`` on: ``stats`` is the loop's
        ``compiled.PackedStats`` stacked on a leading step axis, fetched
        with one ``jax.device_get`` (one counted host read). Each step's
        records are those :meth:`record_compiled_step` gives for it."""
        host = self.host_read(stats, jax.device_get)
        for i, one in enumerate(host.unstack()):
            self._record_aux(one.unpack(), self.step_idx + i)

    def _record_aux(self, aux: dict, step: int, *, modes: dict[str, str] | None = None,
                    reanchor: bool = False) -> None:
        """The records of one compiled step from its unpacked aux pytree."""
        if self._compiled_base is None:
            base_by_layer: dict[str, dict] = {}
            for r in self.records:
                base_by_layer.setdefault(r["layer"], r)
            self._compiled_base = (self.compiled_modes(), base_by_layer)
        base_modes, base_by_layer = self._compiled_base
        if modes is None:
            modes = base_modes
        for name, a in aux.items():
            base = base_by_layer[name]
            meta = self.meta[name]
            rec: dict[str, Any] = {"layer": name, "step": step, "mode": modes[name],
                                   "kind": meta.kind, "macs": base["macs"], "compiled": True}
            if reanchor:
                rec["reanchor"] = True
            cls_act = tuple(float(v) for v in a["cls_act"])
            cls_diff = tuple(float(v) for v in a["cls_diff"]) if "cls_diff" in a else None
            cls_sp = tuple(float(v) for v in a["cls_spatial"]) if "cls_spatial" in a else None
            self._account_classes(rec, base["t"], base["k"], base["n"], cls_act, cls_diff, meta,
                                  attention=base["attention"], cls_spatial=cls_sp)
            if "tile_hist" in a:
                hist = tuple(int(v) for v in a["tile_hist"])
                rec["tile_hist"] = hist
                rec["tile_fracs"] = bops_mod.tile_fractions(hist)
                rec["bops_tile"] = bops_mod.bops_tile_mix(rec["macs"], hist)
            self.records.append(rec)

    # -------------------------------------------------------------- summary
    def summary(self) -> dict:
        import collections

        total = collections.defaultdict(float)
        for r in self.records:
            total["macs"] += r["macs"]
            total["bops"] += r["bops"]
            total["bops_act"] += r["bops_act"]
            total["mem_bytes"] += r["mem_bytes"]
            total["cycles"] += r["cycles"]
        steps = max((r["step"] for r in self.records), default=0) + 1
        modes = {name: st.mode for name, st in self.layers.items()}
        return {"steps": steps, **dict(total), "modes": modes}
