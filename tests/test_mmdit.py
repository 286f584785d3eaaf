"""MMDiT (SD3's two-stream joint-attention denoiser) through the Ditto path,
against the plain float32 reference ``repro.nn.mmdit`` at a tiny size:
3 blocks (the last one ``context_pre_only``), 8x8x16 latents (16 image
tokens), 6 context tokens, 4 prompts."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import trace_audit as ta
from repro.core import diffusion
from repro.core.ditto import CompiledDittoDiT, DittoDiT, DittoEngine, DittoPlan, dit_runner
from repro.core.ditto.compiled import CompiledDittoEngine
from repro.nn import mmdit
from repro.serve import ServeScheduler, ServeSession

CFG = mmdit.MMDiTCfg(d_model=64, n_layers=3, n_heads=4, patch=2, in_channels=16,
                     input_size=8, ctx_tokens=6, ctx_dim=32, pooled_dim=16, n_prompts=4)
PLAN = DittoPlan(steps=4, policy="diff", max_batch=2, collect_stats=False)


@pytest.fixture(scope="module")
def setup():
    key = jax.random.PRNGKey(0)
    params = mmdit.init(key, CFG)
    lat = jax.random.normal(jax.random.fold_in(key, 1), (2, 8, 8, 16))
    labels = jnp.array([1, 3])
    return params, lat, labels


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def test_pre_only_block_needs_a_full_block_before_it():
    with pytest.raises(ValueError):
        mmdit.MMDiTCfg(d_model=64, n_layers=1, n_heads=4)


def test_fp32_ops_equal_the_reference(setup):
    """The Ditto forward with float32 ops injected in place of the engine's
    is the reference's forward: the two differ only in the order of float32
    roundings (split projections, per-head matmuls), so 1e-5 relative."""
    params, lat, labels = setup
    weights = {n: (w, b) for n, w, b in dit_runner.mmdit_linear_weights(params, CFG)}
    t = jnp.full((2,), 700, jnp.int32)
    got = dit_runner._mmdit_forward(
        params, CFG, lambda n, x: x @ weights[n][0] + weights[n][1],
        lambda n, a, b: jnp.einsum("bmd,bnd->bmn", a, b), lat, t, labels)
    assert _rel(got, mmdit.apply(params, CFG, lat, t, labels)) < 1e-5


def test_int8_step_tracks_the_reference(setup):
    """One eager act step (int8 weights and activations): quantization
    noise only, under 5% as for DiT (tests/test_ditto_engine.py); a
    structural divergence, such as a stream's wrong modulation order,
    reads far above it."""
    params, lat, labels = setup
    eng = DittoEngine(policy="act")
    run = DittoDiT(params, CFG, eng)
    eng.begin_sample()
    t = jnp.full((2,), 700, jnp.int32)
    assert _rel(run(lat, t, labels), mmdit.apply(params, CFG, lat, t, labels)) < 0.05
    # every call site is registered, the text stream of the last block has no wo/MLP
    assert "blk2.txt.wo" not in eng.layers and "blk1.txt.wo" in eng.layers
    assert len(eng.layers) == 2 * 7 * 2 + 7 + 4 + 1 + 2 * 3


def test_eager_and_compiled_steps_are_bit_identical(setup):
    """After two eager calibration steps, one compiled step (Pallas kernels,
    interpreted) and the third eager step give the same output and the same
    int32 temporal state in every layer."""
    params, lat, labels = setup
    eng = DittoEngine(policy="diff")
    run = DittoDiT(params, CFG, eng)
    eng.begin_sample()
    x = lat
    for i in range(2):
        run(x, jnp.full((2,), 900 - 40 * i, jnp.int32), labels)
        eng.end_step()
        x = x * 0.98 + 0.01
    comp = CompiledDittoDiT(params, CFG, eng, DittoPlan(collect_stats=False))
    t = jnp.full((2,), 820, jnp.int32)
    got = comp(x, t, labels)
    want = run(x, t, labels)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    for name, st in eng.layers.items():
        np.testing.assert_array_equal(np.asarray(comp.state[name]["y_prev"]),
                                      np.asarray(st.y_prev), err_msg=name)


def test_abstract_inputs_match_the_registered_engine(setup):
    """``trace_audit.abstract_inputs`` (the AOT warmup's and the compile
    rehearsal's shapes) describes exactly what the engine registers."""
    params, lat, labels = setup
    eng = DittoEngine(policy="act")
    run = DittoDiT(params, CFG, eng)
    eng.begin_sample()
    run(lat, jnp.full((2,), 900, jnp.int32), labels)
    eng.end_step()
    real = jax.tree.map(lambda a: (a.shape, a.dtype), CompiledDittoEngine(eng).params)
    dparams = ta.abstract_inputs(CFG, 2)[0]
    assert jax.tree.map(lambda s: (s.shape, s.dtype), dparams) == real
    assert set(ta.uniform_modes(CFG, "diff")) == set(eng.layers)


def test_step_ops_carry_the_stream_and_joint_attention_scopes():
    dparams, mparams, lat, t, labels = ta.abstract_inputs(CFG, 1)
    step = dit_runner.make_step_fn(CFG, ta.uniform_modes(CFG, "diff"),
                                   DittoPlan(collect_stats=False))
    text = jax.jit(step).lower(dparams, mparams, ta.abstract_state(CFG, 1), lat, t,
                               labels).as_text(debug_info=True)
    scopes = set(re.findall(r"(blk\d+/(?:img|txt|joint_attn))", text))
    assert scopes == {f"blk{i}/{s}" for i in range(CFG.n_layers)
                      for s in ("img", "txt", "joint_attn")}


@pytest.fixture(scope="module")
def served(setup):
    """Two one-row requests coalesced into one bucket-2 dispatch by the
    scheduler (AOT-warmed), and each served alone by a session."""
    params, lat, labels = setup
    sched = diffusion.cosine_schedule(1000)
    s = ServeScheduler(params, CFG, sched, PLAN)
    s.warmup(buckets=[2])
    tickets = [s.submit(lat[i:i + 1], labels[i:i + 1]) for i in range(2)]
    s.flush()
    sess = ServeSession(params, CFG, sched, PLAN)
    alone = [sess.serve(lat[i:i + 1], labels[i:i + 1]).sample for i in range(2)]
    return s, [t.result() for t in tickets], alone


def test_a_request_coalesced_in_a_bucket_equals_it_served_alone(served):
    s, coalesced, alone = served
    assert s.stats()["dispatches"] == 1
    for got, want in zip(coalesced, alone):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_the_warmed_bucket_serves_without_a_compile(served):
    st = served[0].stats()
    assert st["aot_misses"] == 0 and st["aot_hits"] >= 1


def test_text_stream_tiles_are_counted_apart(served):
    st = served[0].stats()
    assert sum(st["tiles_txt"]) > 0
    assert all(0 <= t <= a for t, a in zip(st["tiles_txt"], st["tiles"]))
    assert sum(st["tiles_txt"]) < sum(st["tiles"])
