"""Work counts: DiT-XL/2's multiply-adds against the DiT paper, the kernel
call table against the model's count, and the diff kernel's least work
against what the kernel executes when tiles are skipped."""
import json
import os

import numpy as np
import pytest

from bench import work

CONFIGS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "configs")


def config(name):
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name, paper_gmacs", [("dit-xl2-256", 118.6), ("dit-xl2-512", 524.6)])
@pytest.mark.parametrize("learn_sigma", [False, True])
def test_dit_macs_match_the_paper(name, paper_gmacs, learn_sigma):
    c = dict(config(name), learn_sigma=learn_sigma)
    assert work.dit_macs(c) / 1e9 == pytest.approx(paper_gmacs, rel=0.01)


@pytest.mark.parametrize("bucket", [1, 4])
def test_all_act_kernel_calls_cover_the_quantized_model(bucket):
    c = config("dit-xl2-256")
    g = work.dims(c)
    layers = [f"blk{i}.{op}" for i in range(g.layers)
              for op in ("mod", "wq", "wk", "wv", "qk", "pv", "wo", "wi", "wd")] + ["final.out"]
    calls = work.step_calls(c, bucket, {name: "act" for name in layers})
    assert {k.kernel for k in calls} == {"int8_matmul"}
    # everything but the float32 patch embedding, timestep MLP and final modulation
    unquantized = g.tokens * g.patch_dim * g.d + 256 * g.d + g.d * g.d + g.d * 2 * g.d
    assert sum(k.ops() for k in calls) == 2 * bucket * (work.dit_macs(c) - unquantized)


def test_diff_layer_calls_follow_the_attention_identity():
    c = config("dit-xl2-256")
    qk = work.step_calls(c, 2, {"blk0.qk": "diff"})
    assert [(k.m, k.k, k.n, k.count) for k in qk] == [(256, 72, 256, 32)] * 2
    pv = work.step_calls(c, 2, {"blk0.pv": "diff"})
    assert [(k.m, k.k, k.n) for k in pv] == [(72, 256, 256), (256, 256, 72)]
    (wi,) = work.step_calls(c, 2, {"blk0.wi": "diff"})
    assert (wi.m, wi.k, wi.n, wi.y_prev) == (512, 1152, 4608, True)
    # bytes that every implementation moves: x_t, x_prev in; y_prev in, y out
    assert wi.bytes() == 2 * 512 * 1152 + 2 * 4 * 512 * 4608


@pytest.mark.parametrize("m, k, n", [(256, 1152, 384), (200, 300, 130), (4, 1152, 6912),
                                     (72, 256, 256)])
def test_least_tile_ops_never_exceed_the_dense_count(m, k, n):
    call = work.Call("ditto_diff_matmul", "x", m, k, n)
    tiles = -(-m // work.TILE) * -(-k // work.TILE)
    for nonzero in range(tiles + 1):
        assert nonzero * call.least_tile_ops() <= call.ops()
    if m % work.TILE == 0 and k % work.TILE == 0:
        assert tiles * call.least_tile_ops() == call.ops()


def test_diff_count_stays_under_the_kernel_work_as_tiles_are_skipped():
    """Run the program's diff kernel (interpreted) with a growing share of
    zero tiles: the benchmark's least operations, from the kernel's own
    class map, stay at or below the MXU work the kernel issues (one int8
    dot per low tile and two per full tile, each 2*128*128*N)."""
    from repro.kernels import ops

    rng = np.random.default_rng(0)
    m, k, n, t = 256, 384, 128, work.TILE
    w = rng.integers(-127, 128, (k, n)).astype(np.int8)
    x_prev = rng.integers(-100, 100, (m, k)).astype(np.int8)
    call = work.Call("ditto_diff_matmul", "x", m, k, n, y_prev=True)
    for zero_share in (0.0, 0.5, 1.0):
        x_t = x_prev.copy()
        for i in range(m // t):
            for j in range(k // t):
                if rng.random() >= zero_share:
                    x_t[i * t:(i + 1) * t, j * t:(j + 1) * t] += rng.integers(
                        -20, 20, (t, t)).astype(np.int8)
        _, classes = ops.ditto_linear_step(x_t, x_prev, w, np.zeros((m, n), np.int32))
        classes = np.asarray(classes)
        low, full = int((classes == 1).sum()), int((classes == 2).sum())
        issued = 2.0 * t * t * n * (low + 2 * full)
        assert (low + full) * call.least_tile_ops() <= issued
        assert (low + full) * call.least_tile_ops() <= call.ops()


@pytest.mark.parametrize("kernel", ["int8_matmul", "ditto_diff_matmul"])
def test_kernel_roofline_reads_least_time_over_kernel_time(kernel):
    """A kernel that ran exactly as long as its least time reads 100 %."""
    from types import SimpleNamespace

    from bench import peaks, readers, tracing

    c = config("dit-xl2-256")
    modes = {"blk0.wi": "act", "blk0.qk": "diff", "final.out": "act"}
    calls = [k for k in work.step_calls(c, 4, modes) if k.kernel == kernel]
    p = peaks.PEAKS["TPU v5 lite"]
    steps, tile_ops = 48, 1e9
    ops = tile_ops if kernel == "ditto_diff_matmul" else steps * sum(k.ops() for k in calls)
    least = max(ops / p["int8_ops_s"], steps * sum(k.bytes() for k in calls) / p["hbm_bytes_s"])
    events = steps * sum(k.count for k in calls)
    trace = tracing.TraceSummary(window_s=1.0, busy_s=0.5, devices=1,
                                 ops={kernel: [least, events]}, modules={}, gaps=[])
    dispatch = SimpleNamespace(buckets=[4], modes=modes, compiled_steps=steps,
                               diff_tile_ops=tile_ops, rows=4)
    run = SimpleNamespace(trace=trace, traced=[dispatch], cell=SimpleNamespace(config=c),
                          peaks=p)
    assert readers.kernel_roofline(run, kernel) == pytest.approx(100.0)
    dispatch.compiled_steps = None  # no program records: steps from the event count
    assert readers.kernel_roofline(run, kernel) == pytest.approx(100.0)
    trace.ops = {}
    assert readers.kernel_roofline(run, kernel) is None  # a silent kernel reads nothing
