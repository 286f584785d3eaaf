"""The ``mmdit`` family (SD3-medium's MMDiT) on the CPU at a tiny size: the
benchmark's reference against the program's, the work count against a hand
count, the harness's pricing of every call site, and whole runs of the
``sd3m-512.batch`` cell through ``harness.run_cell`` (the chip lookup is the
only step skipped).

The limit here is the cell's 0.06: at this size (width 32, 2 blocks, 4
image and 3 text tokens, DDIM 3) sound runs of the program read
0.0044-0.0153 and the 4-bit control 0.082-0.105 on the CPU (5 seeds)."""
import contextlib
import json
import os
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, peaks, work, work_mmdit

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
TINY = dict(hidden_size=32, depth=2, num_heads=2, input_size=4, context_tokens=3,
            context_dim=8, pooled_dim=8, num_classes=4, max_batch=1, check={"rel_l2": 0.06})
CONFIG = os.path.join(os.path.dirname(os.path.abspath(__file__)), "configs",
                      "sd3-medium-512.json")


def config(**over):
    with open(CONFIG) as f:
        return dict(json.load(f), **over)


@contextlib.contextmanager
def tiny_cell():
    with pytest.MonkeyPatch.context() as mp:
        mp.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
        cell = harness.load_cell("sd3m-512.batch")
        cell.config.update(TINY)
        cell.mix = dict(cell.mix, steps=3)
        yield cell


@pytest.fixture(scope="module")
def cell():
    with tiny_cell() as c:
        yield c


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def test_published_widths():
    c = config()
    assert (c["hidden_size"], c["num_heads"], c["patch_size"], c["in_channels"]) == (
        1536, 24, 2, 16)
    assert c["hidden_size"] // c["num_heads"] == c["published"]["attention_head_dim"]
    assert int(c["mlp_ratio"] * c["hidden_size"]) == 6144
    assert (c["context_tokens"], c["context_dim"], c["pooled_dim"]) == (154, 4096, 2048)
    assert set(c["reduced"]) == {"depth", "text_encoders", "guidance", "sampler"}


def test_the_reference_agrees_with_the_program_reference(cell):
    """Two independent writings of the same equations: the family's forward
    and ``repro.nn.mmdit.apply`` on the same weights, float32 at the highest
    matmul precision; they differ only in the order of roundings."""
    from repro.nn import mmdit

    c = dict(cell.config, depth=3)
    weights = cell.family.init_weights(c, jax.random.PRNGKey(3))
    params, cfg = cell.family.program_model(c, weights)
    x = jax.random.normal(jax.random.PRNGKey(4), (2,) + cell.family.latent_shape(c))
    t, labels = jnp.array([900, 35], jnp.int32), jnp.array([0, 3], jnp.int32)
    with jax.default_matmul_precision("highest"):
        want = mmdit.apply(params, cfg, x, t, labels)
        got = cell.family.forward(c, weights, x, t, labels)
    assert _rel(got, want) < 1e-5
    with jax.default_matmul_precision("highest"):
        control = cell.family.forward(c, weights, x, t, labels, bits=4)
    assert _rel(control, want) > 0.01  # the control quantizes what the program does


@pytest.mark.parametrize("depth", [2, 12])
def test_macs_equal_a_hand_count(depth):
    c = config(depth=depth)
    d, ff, ti, tt = 1536, 6144, 1024, 154
    n = ti + tt
    img = 6 * d * d + 4 * ti * d * d + 2 * ti * d * ff
    txt = 6 * d * d + 4 * tt * d * d + 2 * tt * d * ff
    txt_pre_only = 2 * d * d + 3 * tt * d * d
    joint = 2 * n * n * d
    embed = ti * 64 * d + 256 * d + d * d + 2048 * d + d * d + tt * 4096 * d
    head = 2 * d * d + ti * d * 64
    want = (depth - 1) * (img + txt + joint) + img + txt_pre_only + joint + embed + head
    assert work_mmdit.mmdit_macs(c) == want
    if depth == 12:
        assert work_mmdit.mmdit_macs(c) / 1e9 == pytest.approx(449.61, rel=1e-4)


def test_every_call_site_is_priced_by_the_harness(cell):
    """``harness._wrap_session`` prices each diff layer through
    ``work.diff_tile_ops``, which knows layers by their last name segment."""
    from repro.core.ditto import DittoDiT, DittoEngine

    weights = cell.family.init_weights(cell.config, jax.random.PRNGKey(0))
    params, cfg = cell.family.program_model(cell.config, weights)
    eng = DittoEngine()
    DittoDiT(params, cfg, eng)
    assert len(eng.layers) == 2 * 7 + (7 + 4) + 1 + 2 * 2  # full, pre-only, head, attention
    for name in eng.layers:
        for bucket in (1, 2):
            assert work.diff_tile_ops(cell.config, bucket, name) > 0, name


def run(cell, seed):
    return harness.run_cell(cell, seed, 0.3, False, CPU, time.monotonic())


def test_sound_runs_are_correct(cell):
    out = run(cell, 2**33 + 5)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] >= 1
    assert 0 < out["checks"]["rel_l2"]["value"] < 0.06
    assert set(out["metrics"]) == {"images_per_s", "setup_s"}


def test_the_four_bit_control_is_not_correct(cell, monkeypatch):
    weights = []
    init = cell.family.init_weights
    monkeypatch.setattr(cell.family, "init_weights",
                        lambda config, key: weights.append(init(config, key)) or weights[-1])

    def control(params, cfg, sched, x, labels=None, plan=None, **kw):
        s = cell.family.sample(cell.config, weights[0], x, labels, plan.steps, bits=4)
        return [], jnp.asarray(s), None

    monkeypatch.setattr("repro.sim.harness.serve_records", control)
    out = run(cell, 1001)
    assert out["correct"] is False
    assert out["checks"]["rel_l2"]["value"] > out["checks"]["rel_l2"]["limit"]


def _run_record(stats_before, stats_after, **kw):
    return types.SimpleNamespace(stats_before=stats_before, stats_after=stats_after, **kw)


def test_text_zero_tile_share_reads_the_window_and_nothing_without_the_counter(cell):
    read = cell.readers["mmdit.txt_zero_tiles.batch"]
    before = {"tiles_txt": [10, 5, 5]}
    after = {"tiles_txt": [40, 10, 30]}
    assert read(_run_record(before, after)) == pytest.approx(100 * 30 / 60)
    assert read(_run_record({}, {})) is None  # a program without the counter
    assert read(_run_record(before, before)) is None


def test_mfu_counts_every_step_of_the_traced_dispatches(cell):
    read = cell.readers["model.mfu.mmdit.batch"]
    trace = types.SimpleNamespace(window_s=2.0)
    run_ = types.SimpleNamespace(
        trace=trace, traced=[types.SimpleNamespace(rows=1)] * 3, cell=cell,
        plan=types.SimpleNamespace(steps=28), peaks=peaks.PEAKS["TPU v5 lite"])
    want = 100 * 2 * work_mmdit.mmdit_macs(cell.config) * 3 * 28 / (2.0 * 393e12)
    assert read(run_) == pytest.approx(want)
    assert read(types.SimpleNamespace(trace=None, traced=[])) is None
