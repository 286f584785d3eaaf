"""Compile rehearsal: each configuration's compiled step for a described
TPU v5e, with no chip, and its ``memory_analysis()``.

    JAX_PLATFORMS=cpu PYTHONPATH=src python3 bench/rehearse.py dit-xl2-512 --buckets 1 2

Run by hand before a chip call (a whole 28-layer step compiles in about a
minute on the host). Per (configuration, bucket, uniform mode) it prints
one JSON line: argument, output and temporary bytes of the step program,
and ``held_gb``, the step's arguments plus its outputs plus temporaries
plus one more copy of the temporal state. That last copy is the eager
calibration pass's state, which the program keeps alive through a
dispatch, and the step has no donation, so input and output state coexist.
It counts one program, not what else the process holds (the float32
weights are among the step's arguments). The largest power-of-two bucket
whose ``held_gb`` stays under the chip's memory fixes a configuration's
``max_batch``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)


def rehearse(config_name: str, bucket: int, mode: str, one_chip) -> dict:
    import jax

    from bench import harness
    from repro.analysis.trace_audit import abstract_inputs, abstract_state, uniform_modes
    from repro.core.ditto import dit_runner
    from repro.core.ditto.plan import DittoPlan

    config = harness._read_json(os.path.join(ROOT, "bench", "configs", f"{config_name}.json"))
    family = harness._load_module(os.path.join(ROOT, "bench", "families",
                                               f"{config['family']}.py"), "family")
    weights = jax.eval_shape(lambda k: family.init_weights(config, k),
                             jax.ShapeDtypeStruct((2,), jax.numpy.uint32))
    _, cfg = family.program_model(config, weights)
    dparams, mparams, lat, t, labels = abstract_inputs(cfg, bucket)
    state = abstract_state(cfg, bucket)

    def place(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
                            tree)

    step = dit_runner.make_step_fn(cfg, uniform_modes(cfg, mode), DittoPlan(interpret=False))
    args = place((dparams, mparams, state, lat, t, labels))
    mem = jax.jit(step).lower(*args).compile().memory_analysis()
    state_b = sum(s.size * s.dtype.itemsize for s in jax.tree.leaves(state))
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes + state_b)
    return {"config": config_name, "bucket": bucket, "mode": mode,
            "argument_gb": mem.argument_size_in_bytes / 1e9,
            "output_gb": mem.output_size_in_bytes / 1e9,
            "temp_gb": mem.temp_size_in_bytes / 1e9,
            "state_gb": state_b / 1e9, "held_gb": held / 1e9}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("configs", nargs="+")
    ap.add_argument("--buckets", type=int, nargs="+", default=[1, 2, 4])
    ap.add_argument("--modes", nargs="+", default=["diff"])
    args = ap.parse_args(argv)
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    for name in args.configs:
        for b in args.buckets:
            for mode in args.modes:
                print(json.dumps(rehearse(name, b, mode, one_chip)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
