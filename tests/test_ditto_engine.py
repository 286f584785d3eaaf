"""Ditto algorithm invariants: exactness, Defo analysis/decisions, stats."""
import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import diffusion
from repro.core.ditto import DittoDiT, DittoEngine, defo, make_denoise_fn, quant
from repro.nn import dit as dit_mod

CFG = dit_mod.DiTCfg(d_model=64, n_layers=2, n_heads=2, patch=2, in_channels=4, input_size=8, n_classes=4)


@pytest.fixture(scope="module")
def setup():
    key = jax.random.PRNGKey(0)
    params = dit_mod.init(key, CFG)
    lat = jax.random.normal(jax.random.fold_in(key, 1), (2, 8, 8, 4))
    labels = jnp.array([0, 1])
    return params, lat, labels


def _run(params, lat, labels, policy, n_steps=3):
    eng = DittoEngine(policy=policy)
    run = DittoDiT(params, CFG, eng)
    eng.begin_sample()
    outs = []
    x = lat
    for i in range(n_steps):
        t = jnp.full((2,), 900.0 - 40 * i)
        outs.append(np.asarray(run(x, t, labels)))
        eng.end_step()
        x = x * 0.98 + 0.01  # drift mimicking a denoise update
    return outs, eng


def test_diff_equals_act_bitexact(setup):
    """The paper's central identity: temporal-difference processing is
    numerically equivalent to direct execution (int domain, shared scale)."""
    params, lat, labels = setup
    ref_outs, _ = _run(params, lat, labels, "act")
    diff_outs, eng = _run(params, lat, labels, "diff")
    for a, b in zip(ref_outs, diff_outs):
        np.testing.assert_array_equal(a, b)
    assert any(r["mode"] == "diff" for r in eng.records)


def test_spatial_and_defo_equal_act(setup):
    params, lat, labels = setup
    ref_outs, _ = _run(params, lat, labels, "act")
    for policy in ("spatial", "defo", "defo+"):
        outs, _ = _run(params, lat, labels, policy)
        for a, b in zip(ref_outs, outs):
            np.testing.assert_array_equal(a, b)


def test_int8_close_to_fp32(setup):
    params, lat, labels = setup
    outs, _ = _run(params, lat, labels, "act", n_steps=1)
    y_fp = np.asarray(dit_mod.apply(params, CFG, lat, jnp.full((2,), 900.0), labels))
    rel = np.linalg.norm(outs[0] - y_fp) / np.linalg.norm(y_fp)
    assert rel < 0.10, rel


def test_defo_decides_and_freezes_modes(setup):
    params, lat, labels = setup
    _, eng = _run(params, lat, labels, "defo", n_steps=4)
    by_layer = collections.defaultdict(dict)
    for r in eng.records:
        by_layer[r["layer"]][r["step"]] = r
    for name, steps in by_layer.items():
        assert steps[0]["mode"] == "act"  # step 1 always full bit-width
        assert steps[1]["mode"] == "diff"  # step 2 probes diff
        # steps >= 3 use the frozen decision
        frozen = eng.layers[name].mode
        for s in (2, 3):
            assert steps[s]["mode"] == frozen or (frozen == "diff" and steps[s]["mode"] == "diff")
        # the decision matches the cycle comparison (paper Fig. 9)
        want = "diff" if steps[1]["cycles"] < steps[0]["cycles"] else "act"
        assert frozen == want


@pytest.mark.parametrize("policy", ["spatial", "defo+"])
def test_step0_fallback_records_labeled_act(setup, policy):
    """Regression: when the act fallback fires (no prev-step state yet) the
    record must say 'act' — a 'diff'/'spatial' label would charge
    diff-mode memory traffic for a step that executed act."""
    params, lat, labels = setup
    _, eng = _run(params, lat, labels, policy, n_steps=2)
    step0 = [r for r in eng.records if r["step"] == 0]
    assert step0 and all(r["mode"] == "act" for r in step0)
    # under policy='diff' the first-ever step falls back to act as well
    _, eng_d = _run(params, lat, labels, "diff", n_steps=1)
    assert all(r["mode"] == "act" for r in eng_d.records)


def test_defo_static_analysis_dit():
    metas = defo.analyze(defo.dit_graph(2))
    # qkv feed the attention matmuls directly -> summation bypass
    assert not metas["blk0.wq"].boundary_out
    assert not metas["blk0.wv"].boundary_out
    # wo's input is the PV matmul (linear) -> difference-calc bypass
    assert not metas["blk0.wo"].boundary_in
    # adaLN mod is fenced on both sides
    assert metas["blk0.mod"].boundary_in and metas["blk0.mod"].boundary_out


def test_defo_static_analysis_conv():
    metas = defo.analyze(defo.ddpm_tiny_graph(2))
    # skip convs read the (linear) block input -> no input boundary
    assert not metas["res0.skip"].boundary_in
    # conv_out follows silu -> fenced
    assert metas["conv_out"].boundary_in


def _linear_sources(graph, node, seen=None):
    """The linear ops that feed ``node`` through diff-transparent ops."""
    seen = set() if seen is None else seen
    out = set()
    for p in defo._producers(graph, node):
        if p.name in seen:
            continue
        seen.add(p.name)
        if p.op in defo.LINEAR_OPS:
            out.add(p.name)
        elif p.op in defo.TRANSPARENT:
            out |= _linear_sources(graph, p, seen)
    return out


def test_defo_static_analysis_mmdit():
    """Both streams carry the DiT block's fences, leaf for leaf, and the
    joint attention is fed by both streams' projections."""
    n = 3
    nodes = defo.mmdit_graph(n)
    metas = defo.analyze(nodes)
    dit = defo.analyze(defo.dit_graph(n))
    linears = {k for k, m in metas.items() if m.kind == "dense"}
    assert len(linears) == (n - 1) * 2 * 7 + 7 + 4 + 1
    for name in linears:
        leaf = "final.out" if name == "final.out" else f"blk0.{name.split('.')[-1]}"
        flags = (metas[name].boundary_in, metas[name].boundary_out)
        assert flags == (dit[leaf].boundary_in, dit[leaf].boundary_out), name
        assert any(flags), name  # a non-linear op on at least one side
    graph = {g.name: g for g in nodes}
    for i in range(n):
        assert _linear_sources(graph, graph[f"blk{i}.qk"]) == {
            f"blk{i}.{s}.w{x}" for s in ("img", "txt") for x in ("q", "k")}
        # the probabilities come through the softmax (a fence), v from both streams
        assert _linear_sources(graph, graph[f"blk{i}.pv"]) == {
            f"blk{i}.img.wv", f"blk{i}.txt.wv"}
    assert "blk2.txt.wo" not in metas and "blk1.txt.wo" in metas


def test_full_sampler_loop_with_engine(setup):
    params, lat, labels = setup
    sched = diffusion.cosine_schedule(100)
    eng = DittoEngine(policy="defo")
    fn = make_denoise_fn(params, CFG, eng)
    eng.begin_sample()
    out = diffusion.ddim_sample(sched, fn, lat, steps=5, labels=labels)
    assert out.shape == lat.shape
    assert not bool(jnp.isnan(out).any())
    s = eng.summary()
    assert s["steps"] == 5
    assert s["bops"] <= s["bops_act"] + 1e-6  # diff processing never costs more BOPs


def test_plms_sampler(setup):
    params, lat, labels = setup
    sched = diffusion.cosine_schedule(100)
    eng = DittoEngine(policy="act")
    fn = make_denoise_fn(params, CFG, eng)
    eng.begin_sample()
    out = diffusion.plms_sample(sched, fn, lat, steps=5, labels=labels)
    assert not bool(jnp.isnan(out).any())


def test_quant_roundtrip_bounds(key):
    x = jax.random.normal(key, (64, 64)) * 3
    qt = quant.quantize_tensor(x)
    err = jnp.max(jnp.abs(qt.dequant() - x))
    assert float(err) <= float(qt.scale) * 0.5 + 1e-6


def test_engine_fp32_structure_matches_dit_apply(setup):
    """DittoDiT (engine act-mode, int8) must track nn.dit.apply closely —
    a structural divergence (e.g. masking) would show up far above
    quantization noise. Guards the dual-implementation equivalence."""
    params, lat, labels = setup
    eng = DittoEngine(policy="act")
    run = DittoDiT(params, CFG, eng)
    eng.begin_sample()
    t = jnp.full((2,), 700.0)
    y_eng = np.asarray(run(lat, t, labels))
    y_ref = np.asarray(dit_mod.apply(params, CFG, lat, t, labels))
    rel = np.linalg.norm(y_eng - y_ref) / np.linalg.norm(y_ref)
    assert rel < 0.05, rel
