"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.diff_encode import diff_encode
from repro.kernels.ditto_diff_matmul import ditto_diff_matmul
from repro.kernels.int8_matmul import int8_matmul


def _rand_i8(key, shape, lo=-127, hi=128):
    return jax.random.randint(key, shape, lo, hi, dtype=jnp.int8)


SHAPES = [(128, 128, 128), (256, 384, 128), (384, 256, 512), (128, 512, 256)]


@pytest.mark.parametrize("m,k,n", SHAPES)
def test_int8_matmul_matches_ref(key, m, k, n):
    x = _rand_i8(key, (m, k))
    w = _rand_i8(jax.random.fold_in(key, 1), (k, n))
    got = int8_matmul(x, w)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref.int8_matmul_ref(x, w)))


@pytest.mark.parametrize("m,k", [(128, 128), (256, 512), (384, 256)])
@pytest.mark.parametrize("tile", [(128, 128)])
def test_diff_encode_matches_ref(key, m, k, tile):
    xp = _rand_i8(key, (m, k))
    # build deltas spanning all three classes
    d = jnp.zeros((m, k), jnp.int8)
    d = d.at[:128, :128].set(_rand_i8(jax.random.fold_in(key, 1), (128, 128), -5, 6))
    if k > 128:
        d = d.at[:128, 128:256].set(_rand_i8(jax.random.fold_in(key, 2), (128, 128), -90, 91))
    xt = jnp.clip(xp.astype(jnp.int16) + d.astype(jnp.int16), -127, 127).astype(jnp.int8)
    got = diff_encode(xt, xp, bm=tile[0], bk=tile[1])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref.diff_encode_ref(xt, xp, tile)))


@pytest.mark.parametrize("m,k,n", SHAPES)
def test_ditto_diff_matmul_exact(key, m, k, n):
    """Tile-skipped diff matmul == y_prev + Δ@W == direct x_t@W (bit-exact)."""
    xp = _rand_i8(key, (m, k))
    d = jnp.zeros((m, k), jnp.int8)
    d = d.at[:128, :128].set(_rand_i8(jax.random.fold_in(key, 1), (128, 128), -3, 4))
    xt = jnp.clip(xp.astype(jnp.int16) + d.astype(jnp.int16), -127, 127).astype(jnp.int8)
    w = _rand_i8(jax.random.fold_in(key, 2), (k, n))
    y_prev = ref.int8_matmul_ref(xp, w)
    y, classes = ops.ditto_linear_step(xt, xp, w, y_prev)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(ref.ditto_diff_matmul_ref(xt, xp, w, y_prev)))
    np.testing.assert_array_equal(np.asarray(y), np.asarray(ref.int8_matmul_ref(xt, w)))
    # most tiles are genuinely zero-class (were skipped)
    assert int(np.sum(np.asarray(classes) == 0)) >= (m // 128) * (k // 128) - 2


def test_all_zero_delta_skips_everything(key):
    x = _rand_i8(key, (256, 256))
    w = _rand_i8(jax.random.fold_in(key, 1), (256, 128))
    y_prev = ref.int8_matmul_ref(x, w)
    y, classes = ops.ditto_linear_step(x, x, w, y_prev)
    assert int(np.asarray(classes).max()) == 0
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_prev))


def test_attention_delta_identity(key):
    d_ = 128
    qp = _rand_i8(key, (128, d_), -60, 61)
    kp = _rand_i8(jax.random.fold_in(key, 1), (256, d_), -60, 61)
    dq = _rand_i8(jax.random.fold_in(key, 2), (128, d_), -2, 3)
    dk = _rand_i8(jax.random.fold_in(key, 3), (256, d_), -2, 3)
    qt = (qp + dq).astype(jnp.int8)
    kt = (kp + dk).astype(jnp.int8)
    s_prev = ref.int8_matmul_ref(qp, jnp.asarray(kp.T))
    s_t, _ = ops.attention_delta(qt, qp, kt, kp, s_prev)
    np.testing.assert_array_equal(
        np.asarray(s_t), np.asarray(ref.int8_matmul_ref(qt, jnp.asarray(kt.T)))
    )


def test_quantized_matmul_scales(key):
    x = jax.random.normal(key, (100, 200))
    w = jax.random.normal(jax.random.fold_in(key, 1), (200, 96)) * 0.1
    from repro.core.ditto import quant

    xq = quant.quantize_tensor(np.asarray(x))
    wq = quant.quantize_weight(np.asarray(w))
    y = ops.quantized_matmul(xq.q, wq.q, xq.scale, wq.scale)
    rel = float(jnp.linalg.norm(y - x @ w) / jnp.linalg.norm(x @ w))
    assert rel < 0.05, rel


@pytest.mark.parametrize("knobs,kernel", [({"fused": True}, "ditto_fused_matmul"),
                                          ({"low_bits": 4}, "int4 branch")],
                         ids=["fused", "int4"])
def test_native_lowering_refuses_interpret_only_variants(knobs, kernel):
    """fused=True and low_bits=4 have no Mosaic lowering: under native
    lowering the ops boundary raises a typed error naming the kernel, via
    knobs and via a plan alike, and never falls back."""
    from repro.core.ditto.plan import DittoPlan
    from repro.kernels import NativeLoweringUnsupported

    x = jnp.zeros((128, 128), jnp.int8)
    with pytest.raises(NativeLoweringUnsupported, match=kernel):
        ops.ditto_linear_step(x, x, x, interpret=False, **knobs)
    with pytest.raises(NativeLoweringUnsupported, match=kernel):
        ops.attention_delta(x, x, x, x, jnp.zeros((128, 128), jnp.int32),
                            plan=DittoPlan(interpret=False, **knobs))
    y, _ = ops.ditto_linear_step(x, x, x, interpret=True, **knobs)  # interpreter: allowed
    assert not np.asarray(y).any()
