"""The control of a cell's correctness check: the float32 reference, one
precision below the int8 the configuration states (4 bits: every matmul
the program quantizes, fake-quantized), put in the program's place on the
cell's own inputs and sizes. It has to read above the cell's limit.

    python3 bench/control.py --workload xl2-256.batch --seeds 101 102 103

Run by hand on the chip (the benchmark's runs never run it). For each seed
it draws the weights and the window's first requests as a run of that seed
does, runs the reference at float32 and at ``--bits``, and prints one JSON
line with the relative L2 distance the harness would compare.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--bits", type=int, default=4)
    args = ap.parse_args(argv)
    from bench import harness
    from bench.traffic import Traffic, seed_key

    cell = harness.load_cell(args.workload)
    device = harness.device_info(cell.chips)
    harness.configure_jax()
    config, mix = cell.config, cell.mix
    for seed in args.seeds:
        t0 = time.monotonic()
        weights = cell.family.init_weights(config, seed_key(seed, 0))
        traffic = Traffic(mix, max_batch=config["max_batch"],
                          latent_shape=cell.family.latent_shape(config),
                          n_classes=config["num_classes"], seed=seed)
        reqs = traffic.first_requests(mix["check_rows"])
        chosen = [(r.x, r.labels, cell.family.sample(config, weights, r.x, r.labels,
                                                     mix["steps"], bits=args.bits))
                  for r in reqs]
        rel = harness.reference_check(cell, weights, chosen, mix["steps"])
        print(json.dumps({"workload": cell.name, "seed": seed, "bits": args.bits,
                          "rows": sum(r.rows for r in reqs), "rel_l2": rel,
                          "limit": config["check"]["rel_l2"], "wall_s": time.monotonic() - t0,
                          "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
