"""Elastic-scaling demo: checkpoint under one device layout, restore under
another, and continue training bit-identically.

On real fleets this is the node-loss path: a 512-chip job falls back to
256 chips by restoring the same sharded checkpoint with new shardings
(CheckpointManager.restore takes a target-sharding tree). On this CPU
container we demonstrate the mechanism across two in-process mesh layouts.

    PYTHONPATH=src python examples/elastic_rescale.py
"""
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

from repro import configs
from repro.compile_cache import use_compile_cache
from repro.checkpoint.manager import CheckpointManager
from repro.data.synthetic import DataCfg, batch_for
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_test_mesh


def main():
    use_compile_cache()
    arch = configs.get("qwen3-0.6b").smoke()
    opt = steps_mod.make_optimizer(arch, total=20)
    dc = DataCfg(seed=0, batch=4, seq_len=32)
    workdir = tempfile.mkdtemp(prefix="repro_elastic_")
    mgr = CheckpointManager(workdir)

    # "big mesh" phase: 10 steps, checkpoint
    state = steps_mod.init_state(arch, jax.random.PRNGKey(0), opt)
    train = jax.jit(steps_mod.make_train_step(arch, opt))
    for step in range(10):
        state, m = train(state, batch_for(arch, dc, step))
    mgr.save(10, state)
    print(f"[mesh A] 10 steps, loss={float(m['loss']):.4f}, checkpointed")

    # "rescaled mesh" phase: restore with explicit (here: fully-replicated)
    # target shardings — the same call accepts any NamedSharding tree
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = make_test_mesh(1)
    shard_tree = jax.tree.map(lambda _: NamedSharding(mesh, P()), state)
    state2 = mgr.restore(10, state, shardings=shard_tree)
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(state2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    print("[mesh B] restored onto a different device layout: bit-identical")

    # continue: data pipeline is seekable -> resumes the exact stream
    with mesh:
        for step in range(10, 15):
            state2, m = train(state2, batch_for(arch, dc, step))
    print(f"[mesh B] continued to step 15, loss={float(m['loss']):.4f}")


if __name__ == "__main__":
    main()
