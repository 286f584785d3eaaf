"""GPipe pipeline over a mesh axis == sequential layer stack."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed.pipeline import pipeline_apply
from repro.launch.mesh import auto_mesh


@pytest.mark.skipif(len(jax.devices()) != 1, reason="uses all local devices as one stage axis")
def test_pipeline_matches_sequential(key):
    # 1 real device -> stage axis of size 1 degenerates; emulate 2 stages
    # via a 2-device mesh only when available, else the S=1 path.
    n = len(jax.devices())
    mesh = auto_mesh((n,), ("pod",))
    L, B, D = 4, 8, 16
    ws = jax.random.normal(key, (L, D, D)) * 0.3

    def layer_fn(w, x):
        return jnp.tanh(x @ w)

    x = jax.random.normal(jax.random.fold_in(key, 1), (B, D))
    with mesh:
        y = pipeline_apply(layer_fn, ws, x, mesh=mesh, stage_axis="pod", n_microbatches=4)

    def body(h, w):
        return layer_fn(w, h), None

    want, _ = jax.lax.scan(body, x, ws)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_pipeline_multi_stage_subprocess():
    """Real 4-stage pipeline on 4 forced host devices (own process)."""
    import subprocess
    import sys

    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np, sys
sys.path.insert(0, "src")
from repro.distributed.pipeline import pipeline_apply
key = jax.random.PRNGKey(0)
from repro.launch.mesh import auto_mesh
mesh = auto_mesh((4,), ("pod",))
L, B, D = 8, 8, 16
ws = jax.random.normal(key, (L, D, D)) * 0.3
def layer_fn(w, x):
    return jnp.tanh(x @ w)
x = jax.random.normal(jax.random.fold_in(key, 1), (B, D))
with mesh:
    y = pipeline_apply(layer_fn, ws, x, mesh=mesh, stage_axis="pod", n_microbatches=4)
def body(h, w):
    return layer_fn(w, h), None
want, _ = jax.lax.scan(body, x, ws)
np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=2e-5, atol=2e-5)
print("PIPELINE_OK")
"""
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, cwd="/root/repo", timeout=300
    )
    assert "PIPELINE_OK" in out.stdout, out.stderr[-2000:]
