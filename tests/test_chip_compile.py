"""Compile rehearsal: the main-path Pallas kernels for a described TPU v5e.

Each test lowers and compiles with ``interpret=False`` at DiT-XL/2 widths
(d 1152, d_ff 4608, 256 tokens per sample, head_dim 72) for one chip of a
described ``v5e:2x2`` topology, and asserts the executable holds a Mosaic
kernel (``tpu_custom_call``). Nothing runs: this catches what the chip's
compiler refuses (block layouts, operand dtypes, VMEM) at no chip time.
The topology is described inside a fixture, never at import.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.analysis import trace_audit as ta
from repro.core.ditto import dit_runner
from repro.core.ditto.plan import DittoPlan
from repro.kernels import ops
from repro.nn import dit as dit_mod

D, FF, TOKENS, HEAD_DIM = 1152, 4608, 256, 72
ROWS = 4 * TOKENS  # a bucket-4 dispatch


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compiled_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


I8, I32 = jnp.int8, jnp.int32


@pytest.mark.parametrize("k,n", [(D, FF), (FF, D)], ids=["d_to_ff", "ff_to_d"])
def test_int8_act_matmul_compiles(one_chip, k, n):
    text = _compiled_text(lambda x, w: ops.int8_act_matmul(x, w, interpret=False),
                          one_chip, ((ROWS, k), I8), ((k, n), I8))
    assert "tpu_custom_call" in text


def test_ditto_linear_step_kn_layout_compiles(one_chip):
    text = _compiled_text(
        lambda a, b, w, y: ops.ditto_linear_step(a, b, w, y, interpret=False),
        one_chip, ((ROWS, D), I8), ((ROWS, D), I8), ((D, FF), I8), ((ROWS, FF), I32))
    assert "tpu_custom_call" in text


def test_ditto_linear_step_nk_layout_compiles(one_chip):
    text = _compiled_text(
        lambda a, b, w: ops.ditto_linear_step(a, b, w, None, interpret=False,
                                              w_transposed=True),
        one_chip, ((ROWS, FF), I8), ((ROWS, FF), I8), ((D, FF), I8))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("sub_op", ["qk", "pv"])
def test_attention_act_sub_ops_compile(one_chip, sub_op):
    # qk: (T, hd) @ (hd, T); pv: (T, T) @ (T, hd) — attention_apply's act path
    k = HEAD_DIM if sub_op == "qk" else TOKENS
    n = TOKENS if sub_op == "qk" else HEAD_DIM
    text = _compiled_text(lambda a, b: ops.int8_act_matmul(a, b.T, interpret=False),
                          one_chip, ((TOKENS, k), I8), ((n, k), I8))
    assert "tpu_custom_call" in text


def test_attention_delta_compiles(one_chip):
    q = ((TOKENS, HEAD_DIM), I8)
    text = _compiled_text(
        lambda a, b, c, d, s: ops.attention_delta(a, b, c, d, s, interpret=False),
        one_chip, q, q, q, q, ((TOKENS, TOKENS), I32))
    assert "tpu_custom_call" in text


def _serving_step_text(one_chip, mode: str, bucket: int) -> str:
    """Compiled HLO of the whole jitted serving step (one DiT-XL/2 block at
    full width) at ``bucket`` rows — the program the runner cache compiles."""
    cfg = dit_mod.DiTCfg(d_model=D, n_layers=1, n_heads=16, n_classes=1000)
    plan = DittoPlan(interpret=False, collect_stats=False)
    step = dit_runner.make_step_fn(cfg, ta.uniform_modes(cfg, mode), plan)
    dparams, mparams, lat, t, labels = ta.abstract_inputs(cfg, bucket)
    state = ta.abstract_state(cfg, bucket)
    args = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
                        (dparams, mparams, state, lat, t, labels))
    return jax.jit(step).lower(*args).compile().as_text()


@pytest.mark.parametrize("mode", ["act", "diff"])
def test_one_layer_serving_step_compiles(one_chip, mode):
    assert "tpu_custom_call" in _serving_step_text(one_chip, mode, 4)


def test_one_row_serving_step_keeps_float_matmuls_on_the_mxu(one_chip):
    """XLA:TPU lowers a one-row f32 matmul as a multiply-reduce, which
    rounds unlike the MXU convolution every larger bucket takes; a sample
    must not change with the batch it is served in. So bucket 1 must lower
    exactly as many float matmuls to MXU convolutions as bucket 2 (the
    Ditto GEMMs are Mosaic kernels, not convolutions). Without
    ``dit_runner._cond_dense`` bucket 1 keeps 1 of bucket 2's 4."""
    def convolutions(text):
        return len(re.findall(r"= \S+ convolution\(", text))

    one, two = (convolutions(_serving_step_text(one_chip, "act", b)) for b in (1, 2))
    assert one == two > 0, (one, two)
