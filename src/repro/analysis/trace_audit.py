"""Trace-identity audit: prove ``cache_sig()`` ⇔ jaxpr identity, abstractly.

``RunnerKey = (cfg_sig, mode_sig, plan.cache_sig(), bucket)`` — the whole
serving cache hangs on ``cache_sig()`` being exactly the set of plan
fields that select a distinct lowering. Two failure modes, one per
direction:

* **stale trace** — a knob changes the jaxpr but not the sig. Two plans
  collide on one cache entry and the second silently runs the first
  plan's computation (wrong results, no error).
* **trace duplication** — a sig field has no jaxpr effect. Identical
  computations get distinct cache entries and re-pay the multi-second
  trace/compile cost the cache exists to amortize.

This module checks both directions without executing a single kernel:
every step function is built with :func:`make_step_fn` and traced with
``jax.make_jaxpr`` over ``jax.ShapeDtypeStruct`` inputs (weights are
never materialized; the temporal-state pytree is bootstrapped with
``jax.eval_shape``). The canonicalized jaxpr text is hashed into a
fingerprint; within an audit group (same cfg, modes, bucket):

  equal sig, different fingerprint  -> ``trace-stale`` finding
  different sig, equal fingerprint  -> ``trace-dup`` finding, unless an
                                       explicit shared-trace allowlist
                                       entry covers the pair

The allowlist (``# dittolint: shared-trace``) records pairs that are
*known and intended* to share a lowering — today only ``fused=True``
plans differing in ``low_bits``, because the fused kernel always executes
class-1 tiles from its int4-packed Δ-cache, so ``low_bits`` genuinely
does not select a lowering there. Keeping ``low_bits`` in the sig is
still correct (it selects distinct two-pass lowerings); the allowlist
scopes the exception instead of widening the invariant.

The dup direction is only asserted in all-``diff`` mode groups: in an
all-``act`` group every diff-path knob is validated-then-ignored by
design (``int8_act_matmul`` has no Δ operand), so "same jaxpr" there says
nothing about whether the field earns its place in the sig.
"""
from __future__ import annotations

import dataclasses
import hashlib
import re

from .findings import Finding

#: where sig/jaxpr mismatches anchor — the sig definition is the defect site
PLAN_PATH = "src/repro/core/ditto/plan.py"


# ------------------------------------------------------------- fingerprints
def canonical_fingerprint(jaxpr) -> str:
    """Hash of the jaxpr text with memory addresses canonicalized out.

    ``str(jaxpr)`` embeds ``0x...`` ids for callables closed over by
    custom primitives (pallas kernel functions); two traces of the same
    computation differ only there. Everything else — primitive sequence,
    shapes, dtypes, params — is deterministic within a process.
    """
    s = re.sub(r"0x[0-9a-fA-F]+", "0xX", str(jaxpr))
    return hashlib.sha256(s.encode()).hexdigest()[:16]


@dataclasses.dataclass(frozen=True)
class TraceCase:
    """One audited point: a labelled (sig, jaxpr-fingerprint) pair.

    ``plan`` rides along (not compared) so allowlist predicates can ask
    *why* two cases were expected to share a trace.
    """
    label: str
    sig: tuple
    fingerprint: str
    plan: object = None


# -------------------------------------------------------- shared-trace list
def _differing_fields(pa, pb) -> set[str]:
    fields = {f.name for f in dataclasses.fields(pa)} if dataclasses.is_dataclass(pa) \
        else set(vars(pa))
    return {f for f in fields if getattr(pa, f) != getattr(pb, f, object())}


def _fused_low_bits(pa, pb) -> bool:
    """fused=True plans differing only in ``low_bits`` share one lowering:
    the fused kernel's Δ-cache IS int4-packed storage, both settings
    execute class-1 tiles from it identically."""
    if pa is None or pb is None:
        return False
    if not (getattr(pa, "fused", False) and getattr(pb, "fused", False)):
        return False
    return _differing_fields(pa, pb) == {"low_bits"}


#: # dittolint: shared-trace — (name, predicate(plan_a, plan_b)) entries.
#: A pair matching any predicate may share a jaxpr despite distinct sigs.
SHARED_TRACE_ALLOWLIST: tuple = (
    ("fused-low-bits", _fused_low_bits),
)


# ------------------------------------------------------------------- audit
def audit_cases(cases: list[TraceCase], *, group: str = "", check_dup: bool = True,
                allowlist=SHARED_TRACE_ALLOWLIST) -> list[Finding]:
    """Pairwise both-direction check over one audit group."""
    findings = []
    for i, a in enumerate(cases):
        for b in cases[i + 1:]:
            if a.sig == b.sig and a.fingerprint != b.fingerprint:
                findings.append(Finding(
                    "trace-stale", PLAN_PATH, f"{group}:{a.label}~{b.label}",
                    f"[{group}] plans '{a.label}' and '{b.label}' share "
                    f"cache_sig() but lower to different jaxprs — the second "
                    f"to arrive would silently replay the first's trace; some "
                    f"knob distinguishing them is missing from cache_sig()"))
            elif a.sig != b.sig and a.fingerprint == b.fingerprint and check_dup:
                allowed = next((name for name, pred in allowlist
                                if pred(a.plan, b.plan)), None)
                if allowed is None:
                    findings.append(Finding(
                        "trace-dup", PLAN_PATH, f"{group}:{a.label}~{b.label}",
                        f"[{group}] plans '{a.label}' and '{b.label}' have "
                        f"distinct cache_sig() but identical jaxprs — a sig "
                        f"field with no lowering effect duplicates traces and "
                        f"re-pays compilation (add a shared-trace allowlist "
                        f"entry only if the sharing is intended)"))
    return findings


# --------------------------------- abstract DiT / MMDiT inputs (no data)
def _is_mmdit(cfg) -> bool:
    from repro.nn.mmdit import MMDiTCfg

    return isinstance(cfg, MMDiTCfg)


def _mmdit_linear_dims(cfg, batch: int) -> dict:
    """``{call site: (k, n, rows)}`` of every MMDiT engine linear."""
    from repro.nn import mmdit as mmdit_mod

    d, hid = cfg.d_model, cfg.ff
    out = {}
    for i in range(cfg.n_layers):
        for s, tok in (("img", cfg.n_tokens), ("txt", cfg.ctx_tokens)):
            pre_only = s == "txt" and i == cfg.n_layers - 1
            rows = batch * tok
            dims = {"mod": (d, (2 if pre_only else 6) * d, batch), "wq": (d, d, rows),
                    "wk": (d, d, rows), "wv": (d, d, rows), "wo": (d, d, rows),
                    "wi": (d, hid, rows), "wd": (hid, d, rows)}
            names = mmdit_mod.PRE_ONLY_LINEARS if pre_only else mmdit_mod.STREAM_LINEARS
            out.update({f"blk{i}.{s}.{nm}": dims[nm] for nm in names})
    out["final.out"] = (d, cfg.patch_dim, batch * cfg.n_tokens)
    return out


def _layer_names(cfg):
    if _is_mmdit(cfg):
        linear = list(_mmdit_linear_dims(cfg, 1))
    else:
        linear = []
        for i in range(cfg.n_layers):
            b = f"blk{i}"
            linear += [f"{b}.mod", f"{b}.wq", f"{b}.wk", f"{b}.wv", f"{b}.wo",
                       f"{b}.wi", f"{b}.wd"]
        linear.append("final.out")
    attn = [f"blk{i}.{s}" for i in range(cfg.n_layers) for s in ("qk", "pv")]
    return linear, attn


def abstract_inputs(cfg, batch: int):
    """ShapeDtypeStruct pytrees for one step: (dparams, mparams, latents,
    t, labels), for a ``DiTCfg`` or an ``MMDiTCfg``. Weight values never
    exist — ``init`` runs under ``eval_shape`` and the per-layer Ditto
    params are written directly as shape structs mirroring what
    ``DittoEngine.register_*`` produces."""
    import jax
    import jax.numpy as jnp

    from repro.nn import dit as dit_mod
    from repro.nn import mmdit as mmdit_mod

    S = jax.ShapeDtypeStruct
    model = mmdit_mod if _is_mmdit(cfg) else dit_mod
    mparams = jax.eval_shape(lambda k: model.init(k, cfg), jax.random.PRNGKey(0))

    def lin_p(k, n, rows):
        return dict(w_q=S((k, n), jnp.int8), w_scale=S((n,), jnp.float32),
                    bias=S((n,), jnp.float32), x_scale=S((rows, 1), jnp.float32))

    linear, attn = _layer_names(cfg)
    if _is_mmdit(cfg):
        dparams = {nm: lin_p(*dims) for nm, dims in _mmdit_linear_dims(cfg, batch).items()}
    else:
        d, tok, hid = cfg.d_model, cfg.n_tokens, int(cfg.mlp_ratio * cfg.d_model)
        rows_tok = batch * tok
        dims = {"mod": (d, 6 * d, batch), "wq": (d, d, rows_tok), "wk": (d, d, rows_tok),
                "wv": (d, d, rows_tok), "wo": (d, d, rows_tok), "wi": (d, hid, rows_tok),
                "wd": (hid, d, rows_tok), "out": (d, cfg.patch_dim, rows_tok)}
        dparams = {nm: lin_p(*dims[nm.split(".")[-1]]) for nm in linear}
    bh = batch * cfg.n_heads
    for nm in attn:
        dparams[nm] = dict(a_scale=S((bh, 1, 1), jnp.float32),
                           b_scale=S((bh, 1, 1), jnp.float32))
    lat = S((batch, cfg.input_size, cfg.input_size, cfg.in_channels), jnp.float32)
    # int32, matching the samplers' jnp.full(..., t, jnp.int32) exactly —
    # CompiledRunnerCache.warmup lowers AOT executables from these structs,
    # so any dtype drift from the live call would defeat the warmup
    t = S((batch,), jnp.int32)
    labels = S((batch,), jnp.int32)
    return dparams, mparams, lat, t, labels


def uniform_modes(cfg, mode: str) -> dict[str, str]:
    linear, attn = _layer_names(cfg)
    return {nm: mode for nm in linear + attn}


def abstract_state(cfg, batch: int):
    """Bootstrap the temporal-state pytree shape with one ``eval_shape``:
    under all-``act`` modes with ``collect_stats=False`` the step reads no
    layer's state (only the tile totals, which it passes through), so
    empty per-layer dicts trace fine and the returned ``new_state`` IS the
    true state shape tree (the engine writes every field regardless of
    mode)."""
    import jax
    import jax.numpy as jnp

    from repro.core.ditto import dit_runner
    from repro.core.ditto.compiled import TILE_TOTALS
    from repro.core.ditto.plan import DittoPlan

    dparams, mparams, lat, t, labels = abstract_inputs(cfg, batch)
    modes = uniform_modes(cfg, "act")
    step = dit_runner.make_step_fn(cfg, modes, DittoPlan(collect_stats=False))
    dummy = {nm: {} for nm in modes}
    dummy[TILE_TOTALS] = jax.ShapeDtypeStruct((len(modes), 3), jnp.int32)
    _, state_shapes, _ = jax.eval_shape(step, dparams, mparams, dummy, lat, t, labels)
    return state_shapes


def trace_fingerprint(cfg, modes: dict[str, str], plan, batch: int, state=None) -> str:
    """Fingerprint of the step's jaxpr for (cfg, modes, plan, batch) —
    pure ``jax.make_jaxpr`` over shape structs, zero FLOPs."""
    import jax

    from repro.core.ditto import dit_runner

    dparams, mparams, lat, t, labels = abstract_inputs(cfg, batch)
    if state is None:
        state = abstract_state(cfg, batch)
    step = dit_runner.make_step_fn(cfg, modes, plan)
    jpr = jax.make_jaxpr(step)(dparams, mparams, state, lat, t, labels)
    return canonical_fingerprint(jpr)


# ------------------------------------------------------- schedule expansion
def expand_schedule(label: str, schedule, *, normalize: bool = True) -> list:
    """``(label[start:stop), plan)`` audit cases, one per schedule segment.

    The audit's schedule contract is exactly the runtime's: a
    :class:`~repro.core.ditto.PlanSchedule` IS its segment plans (the
    denoise loop partitions by segment and each segment hits the cache as
    a bare plan), so running the sig⇔jaxpr check over this expansion
    covers schedules with zero new tracing machinery. Normalizing first
    (default) audits what actually executes — merged segments appear
    once; a constant schedule expands to exactly its bare plan's case.
    """
    sched = schedule.normalized() if normalize else schedule
    return [(f"{label}[{start}:{stop})", plan)
            for start, stop, plan in sched.segment_plans()]


def default_schedule_matrix() -> list:
    """(label, schedule) variants for the shipped audit: the
    histogram-style int8→int4+fused split, a constant schedule (must
    land on the bare plan's sig AND jaxpr — zero new traces), and a
    redundantly-split spelling that normalization must merge to one
    segment."""
    from repro.core.ditto.plan import DittoPlan, PlanSchedule

    base = DittoPlan(collect_stats=False, steps=12)
    return [
        ("const", PlanSchedule(base, [(0, 6, {}), (6, 12, {})])),
        ("hist", PlanSchedule(base, [(0, 4, {}),
                                     (4, 12, dict(low_bits=4, fused=True))])),
        ("resplit-lb4", PlanSchedule(base, [(0, 2, dict(low_bits=4)),
                                            (2, 12, dict(low_bits=4))])),
    ]


# ------------------------------------------------------- recovery coverage
def audit_recovery_sigs(plans, audited_sigs, *, group: str = "recovery"
                        ) -> list[Finding]:
    """Prove the failure paths never mint surprise traces: every rung of a
    plan's degradation ladder (``fallback_plans()``) and every watchdog
    plan's canonical re-anchor lowering (``fused=False``, default
    ``low_bits`` — what ``make_denoise_fn`` actually builds) must resolve
    to a ``cache_sig()`` the audit matrix already fingerprinted. A rung
    outside the audited set would mean recovery dispatches run a lowering
    the sig⇔jaxpr proof never saw."""
    from repro.kernels.common import DEFAULT_LOW_BITS

    findings: list[Finding] = []
    for label, plan in plans:
        rungs = plan.fallback_plans() if hasattr(plan, "fallback_plans") else ()
        for i, rung in enumerate(rungs):
            if rung.cache_sig() not in audited_sigs:
                findings.append(Finding(
                    "fallback-unaudited", PLAN_PATH, f"{group}:{label}#rung{i}",
                    f"[{group}] plan '{label}' fallback rung {i} resolves to "
                    f"cache_sig()={rung.cache_sig()} which no audit group "
                    f"fingerprinted — a failed dispatch would recover onto an "
                    f"unaudited lowering; add the sig to the plan matrix"))
        if getattr(plan, "watchdog", False):
            # a schedule re-anchors off whichever segment plan is live, so
            # every segment contributes a candidate re-anchor sig
            seg_plans = ([p for _, _, p in plan.segment_plans()]
                         if hasattr(plan, "segment_plans") else [plan])
            rsigs = {p.replace(fused=False,
                               low_bits=DEFAULT_LOW_BITS).cache_sig()
                     for p in seg_plans}
            for rsig in sorted(rsigs - set(audited_sigs)):
                findings.append(Finding(
                    "reanchor-unaudited", PLAN_PATH, f"{group}:{label}#reanchor",
                    f"[{group}] plan '{label}' re-anchors onto "
                    f"cache_sig()={rsig} which no audit group fingerprinted — "
                    f"the watchdog's full-bit-width step would run an "
                    f"unaudited lowering; add the sig to the plan matrix"))
    return findings


def default_recovery_matrix():
    """(label, plan) recovery representatives: the production-shaped
    ladders whose rungs/re-anchor sigs the audit must have covered —
    the kernel-family ladder the example/benches serve (fused→unfused→
    int8→eager) in both stats flavors, plus a scheduled base."""
    from repro.core.ditto.plan import DittoPlan, PlanSchedule

    base = DittoPlan(collect_stats=False)
    ladder = (dict(fused=False), dict(fused=False, low_bits=8),
              dict(compiled=False))
    serving = base.replace(low_bits=4, fused=True, watchdog=True,
                           max_retries=3, retry_backoff_ms=25.0,
                           fallbacks=ladder)
    stats_serving = DittoPlan(fused=True, watchdog=True, max_retries=3,
                              retry_backoff_ms=25.0, reanchor_full_frac=0.97,
                              fallbacks=(dict(fused=False),))
    sched = PlanSchedule(serving.replace(steps=12),
                         [(0, 4, dict(fused=False, low_bits=8)), (4, 12, {})])
    # a mesh-stamped ladder: rungs inherit the mesh fields via replace, so
    # every recovery dispatch (and the watchdog re-anchor) stays on the
    # shard's submesh — their mesh-sig'd rung sigs must be audited too
    mesh_serving = serving.replace(mesh_devices=2)
    return [("serving-ladder", serving),
            ("stats-serving-ladder", stats_serving),
            ("scheduled-ladder", sched),
            ("mesh-serving-ladder", mesh_serving)]


# ----------------------------------------------------------- default matrix
def _tiny_cfgs():
    """Audit configs: a minimal DiT plus a scaled-down echo of the
    registry's dit-xl2 geometry (patch 2, 4 latent channels, mlp_ratio 4,
    class-conditional) — same code paths, trace-sized shapes."""
    from repro.nn import dit as dit_mod

    tiny = dit_mod.DiTCfg(d_model=16, n_layers=1, n_heads=2, patch=2,
                          in_channels=2, input_size=4, n_classes=2)
    xl2_echo = dit_mod.DiTCfg(d_model=32, n_layers=2, n_heads=4, patch=2,
                              in_channels=4, input_size=8, n_classes=10)
    return [("tiny", tiny), ("xl2-echo", xl2_echo)]


def default_plan_matrix():
    """(label, plan) variants spanning every cache_sig field plus every
    deliberately-absent field (the equal-sig probes)."""
    from repro.core.ditto.plan import DittoPlan

    base = DittoPlan(collect_stats=False)
    return [
        # equal-sig probes: must all share one jaxpr with `base`
        ("base", base),
        ("interpret-explicit", base.replace(interpret=True)),
        ("steps-40", base.replace(steps=40)),
        ("sampler-plms", base.replace(sampler="plms")),
        ("policy-diff", base.replace(policy="diff")),
        ("max-batch-8", base.replace(max_batch=8)),
        ("deadline-250", base.replace(deadline_ms=250.0)),
        ("eager", base.replace(compiled=False)),
        ("watchdog", base.replace(watchdog=True)),
        ("retry-ladder", base.replace(
            max_retries=2, retry_backoff_ms=5.0,
            fallbacks=(dict(low_bits=4), dict(compiled=False)))),
        # distinct-sig probes: each must select a distinct jaxpr
        ("stats", base.replace(collect_stats=True)),
        # recovery knobs on top of stats: sig must STAY the stats sig
        ("watchdog-reanchor", base.replace(
            collect_stats=True, watchdog=True, reanchor_full_frac=0.9)),
        ("low-bits-4", base.replace(low_bits=4)),
        ("fused", base.replace(fused=True)),
        ("fused-low-bits-4", base.replace(fused=True, low_bits=4)),  # allowlisted vs fused
        ("block-256", base.replace(block=256)),
        # mesh probes: the sharding constraint is traced over an ABSTRACT
        # (axis: dp) mesh, so the mesh sig is provable on a 1-device host.
        # Each mesh sig must select a distinct jaxpr from base AND from
        # every other mesh width/axis; per-request metadata on a mesh plan
        # must not (the equal-sig deadline probe).
        ("mesh-dp2", base.replace(mesh_devices=2)),
        ("mesh-dp2-deadline", base.replace(mesh_devices=2, deadline_ms=250.0)),
        ("mesh-dp4", base.replace(mesh_devices=4)),
        ("mesh-axis-x", base.replace(mesh_devices=2, mesh_axis="x")),
        # the mesh flavors of the serving ladder's rung sigs (fused=False
        # keeps low_bits=4; the no-retry rung keeps the fused sig) — the
        # recovery audit requires them fingerprinted
        ("mesh-dp2-low-bits-4", base.replace(mesh_devices=2, low_bits=4)),
        ("mesh-dp2-fused-lb4", base.replace(mesh_devices=2, fused=True,
                                            low_bits=4)),
    ]


def run_trace_audit(log=None) -> list[Finding]:
    """The shipped audit matrix (~20 abstract traces, a few seconds on CPU).

    Full plan matrix on (tiny, all-diff, bucket=2) — the group where every
    knob is live — plus the schedule matrix expanded to segments in the
    same geometry; equal-sig stale probes on a second bucket, a second cfg
    and an all-act group (dup checking off there, see module docstring).
    Fingerprints are memoized per (cfg, mode, bucket, plan) so segment
    plans that coincide with matrix plans cost nothing extra.
    """
    say = log or (lambda *_: None)
    findings: list[Finding] = []
    cfgs = dict(_tiny_cfgs())
    fps: dict = {}  # (cfg id, mode, batch, plan) -> fingerprint, across groups
    audited_sigs: set = set()  # every sig any group fingerprinted

    def build(cfg, modes, plans, batch, group, state):
        cases = []
        mode0 = next(iter(modes.values()))
        for label, plan in plans:
            memo = (id(cfg), mode0, batch, plan)
            fp = fps.get(memo)
            if fp is None:
                fp = fps[memo] = trace_fingerprint(cfg, modes, plan, batch, state=state)
            say(f"  traced {group}:{label} sig={plan.cache_sig()} fp={fp}")
            audited_sigs.add(plan.cache_sig())
            cases.append(TraceCase(label, plan.cache_sig(), fp, plan))
        return cases

    plans = default_plan_matrix()
    tiny = cfgs["tiny"]
    state = abstract_state(tiny, 2)
    say("group tiny/diff/b2: full plan matrix, both directions")
    findings += audit_cases(
        build(tiny, uniform_modes(tiny, "diff"), plans, 2, "tiny/diff/b2", state),
        group="tiny/diff/b2")

    # schedules audit as their segment expansion, against the bare base
    # plan in the same group: a constant schedule's one segment must share
    # the base's sig AND jaxpr (zero new traces), multi-segment schedules
    # must split exactly at their distinct sigs
    from repro.core.ditto.plan import DittoPlan

    sched_cases = [("base", DittoPlan(collect_stats=False))]
    for label, schedule in default_schedule_matrix():
        sched_cases += expand_schedule(label, schedule)
    say("group tiny/diff/b2/sched: schedule segment expansion, both directions")
    findings += audit_cases(
        build(tiny, uniform_modes(tiny, "diff"), sched_cases, 2,
              "tiny/diff/b2/sched", state),
        group="tiny/diff/b2/sched")

    stale_probes = [p for p in plans if p[0] in
                    ("base", "interpret-explicit", "steps-40", "watchdog",
                     "stats")]
    say("group tiny/act/b2: stale direction only (diff knobs inert under act)")
    findings += audit_cases(
        build(tiny, uniform_modes(tiny, "act"), stale_probes, 2, "tiny/act/b2", state),
        group="tiny/act/b2", check_dup=False)

    say("group tiny/diff/b4: stale probes at a second bucket")
    findings += audit_cases(
        build(tiny, uniform_modes(tiny, "diff"), stale_probes, 4, "tiny/diff/b4",
              abstract_state(tiny, 4)),
        group="tiny/diff/b4", check_dup=False)

    echo = cfgs["xl2-echo"]
    echo_probes = [p for p in plans if p[0] in ("base", "steps-40", "fused")]
    say("group xl2-echo/diff/b2: registry-geometry spot check")
    findings += audit_cases(
        build(echo, uniform_modes(echo, "diff"), echo_probes, 2, "xl2-echo/diff/b2",
              abstract_state(echo, 2)),
        group="xl2-echo/diff/b2")

    say("group recovery: ladder rungs / re-anchor sigs ⊆ audited sigs")
    findings += audit_recovery_sigs(default_recovery_matrix(), audited_sigs)
    return findings
