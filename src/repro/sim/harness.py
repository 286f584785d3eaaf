"""Design-point harness: ONE engine pass collects per-mode statistics for
an identical trajectory; each design is then priced on its hardware at
(optionally) paper-scale layer dimensions.

Design points (paper Fig. 13): GPU (analytic A100), ITC, Diffy,
Cambricon-D, Ditto, Ditto+.
"""
from __future__ import annotations

import jax

from ..core import diffusion
from ..core.spans import span
from ..core.ditto import CAMBRICON_D, DIFFY, DITTO_HW, ITC, DittoEngine, make_denoise_fn
from ..core.ditto.plan import UNSET, DittoPlan, PlanSchedule, plan_from_kwargs
from ..nn import dit as dit_mod
from ..nn import mmdit as mmdit_mod
from . import cycles

DESIGN_HW = {
    "itc": ITC,
    "diffy": DIFFY,
    "cambricon-d": CAMBRICON_D,
    "ditto": DITTO_HW,
    "ditto+": DITTO_HW,
}

# A100 analytic baseline: 624 TOPS int8 peak; small-batch diffusion
# inference is launch/memory bound — low single-digit sustained
# utilization (the paper's GPU bars sit below the 27-TOPS ITC).
GPU_TOPS = 624e12 * 0.03
GPU_BW = 1.555e12


def collect_records(params, cfg: dit_mod.DiTCfg | mmdit_mod.MMDiTCfg, sched, x_T, labels, *, steps: int,
                    sampler: str = "ddim"):
    """One exact engine pass collecting act/diff/spatial stats per record."""
    eng = DittoEngine(policy="diff", collect_oracle=True)
    fn = make_denoise_fn(params, cfg, eng)
    eng.begin_sample()
    sample = diffusion.SAMPLERS[sampler](sched, fn, x_T, steps=steps, labels=labels)
    return eng.records, sample, eng


def serve_records(params, cfg: dit_mod.DiTCfg | mmdit_mod.MMDiTCfg, sched, x_T, labels=None,
                  plan: DittoPlan | PlanSchedule | None = None, *, runner_cache=None,
                  bucket: int | None = None, mesh=None, steps=UNSET, sampler=UNSET,
                  policy=UNSET, compiled=UNSET, interpret=UNSET, collect_stats=UNSET,
                  block=UNSET, low_bits=UNSET, fused=UNSET):
    """The deployment pass: eager calibration (+ the Defo mode decision
    after step 2), then the remaining steps through the jit-compiled Pallas
    path — act layers on int8_matmul, diff layers on diff_encode ->
    ditto_diff_matmul with on-device tile skipping. Records cover every
    step (compiled steps synthesize records from on-device class fractions
    unless ``plan.collect_stats=False``) and keep candidate-mode stats —
    spatial counterfactuals on the calibration steps (collect_oracle) and
    temporal/spatial fractions on compiled steps even for act-frozen
    layers — so run_designs can still re-price every design point.

    ``plan`` (a :class:`repro.core.ditto.DittoPlan`) is the whole
    configuration: sampling loop (``steps``/``sampler``/``policy``),
    kernel lowering (``block``/``interpret``/``low_bits``/``fused``) and
    serve behavior (``compiled``/``collect_stats``); omitting it means
    ``DittoPlan()`` — the documented defaults (20-step ddim, defo,
    compiled), not an error. The per-knob keywords are a deprecated shim
    that builds the equivalent plan (and therefore the same runner-cache
    key). ``plan`` may also be a :class:`repro.core.ditto.PlanSchedule`:
    the loop-level fields come off its base and the compiled step loop is
    partitioned by segment (one trace per distinct segment sig, temporal
    state carried across boundaries — see ``make_denoise_fn``).

    ``runner_cache`` (a repro.serve.CompiledRunnerCache) makes the compiled
    step persistent across calls: batches whose (cfg, frozen layer modes,
    ``plan.cache_sig()``, bucket) agree replay one shared XLA trace instead
    of recompiling. ``bucket`` pads the batch dim up to that size by row
    replication before the pass and slices the sample back afterwards —
    bit-identical to the unbucketed path (see repro.serve.bucketing) while
    letting ragged batch sizes share a trace. Records are collected at
    bucket scale (the padded rows are replicas, so per-element fractions
    are representative; ``macs`` scale with the bucket).

    ``mesh`` (a concrete ``jax.sharding.Mesh``) commits the padded
    dispatch onto a shard submesh for a mesh-signed plan (batch axis
    split over the plan's ``mesh_axis``; per-sample calibration keeps the
    sharded sample bit-identical — see repro.serve.mesh). ``mesh=None``
    with a sharded plan resolves a default mesh over the leading host
    devices; unsharded plans ignore it entirely."""
    plan = plan_from_kwargs("sim.harness.serve_records", plan, steps=steps,
                            sampler=sampler, policy=policy, compiled=compiled,
                            interpret=interpret, collect_stats=collect_stats,
                            block=block, low_bits=low_bits, fused=fused)
    true_b = x_T.shape[0]
    if bucket is not None and bucket != true_b:
        from ..serve import bucketing  # function-level: repro.serve imports sim.harness

        x_T, labels = bucketing.pad_batch(x_T, labels, bucket)
    if plan.mesh_sig() is not None:
        from ..serve import mesh as mesh_mod  # function-level, as above

        mesh = mesh_mod.resolve_mesh(plan, mesh)
        x_T, labels = mesh_mod.place_dispatch(x_T, labels, mesh, plan.mesh_axis)
    with span("ditto.requantize"):  # every weight pulled and quantized
        eng = DittoEngine(policy=plan.policy, collect_oracle=plan.collect_stats)
        fn = make_denoise_fn(params, cfg, eng, plan, runner_cache=runner_cache,
                             bucket=x_T.shape[0])
    eng.begin_sample()
    sample = diffusion.SAMPLERS[plan.sampler](sched, fn, x_T, steps=plan.steps,
                                              labels=labels)
    return eng.records, sample[:true_b], eng


def run_designs(records, *, t_mult: float = 1.0, d_mult: float = 1.0, seq_mult: float | None = None,
                designs=tuple(DESIGN_HW), **mode_kw) -> dict:
    recs = cycles.scale_records(records, t_mult=t_mult, d_mult=d_mult, seq_mult=seq_mult)
    out = {}
    for name in designs:
        hw = DESIGN_HW[name]
        fn = cycles.mode_fn_for(name, recs, hw, **mode_kw)
        out[name] = cycles.simulate(recs, hw, fn)
    out["gpu-a100"] = gpu_baseline(recs)
    return out


def gpu_baseline(records) -> dict:
    total_macs = sum(r["macs"] for r in records)
    total_bytes = sum(cycles._mem_bytes(r, "act") for r in records)
    t = max(2 * total_macs / GPU_TOPS, total_bytes / GPU_BW)
    return {"hw": "gpu-a100", "time_s": t, "energy_j": t * 300.0, "cycles": t * 1.41e9}


def run_all(params, cfg: dit_mod.DiTCfg | mmdit_mod.MMDiTCfg, sched, x_T, labels, *, steps: int,
            sampler: str = "ddim", t_mult: float = 1.0, d_mult: float = 1.0,
            seq_mult: float | None = None):
    records, sample, eng = collect_records(params, cfg, sched, x_T, labels,
                                           steps=steps, sampler=sampler)
    out = run_designs(records, t_mult=t_mult, d_mult=d_mult, seq_mult=seq_mult)
    for r in out.values():
        r["sample"] = sample
    out["records"] = records
    out["engine"] = eng
    return out
