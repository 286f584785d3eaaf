"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. ``us_per_call`` is the simulated
Ditto-hardware time where meaningful (0 otherwise); ``derived`` is the
figure's headline metric. A final block prints the roofline summary from
the dry-run artifacts (tools/gen_roofline_md.py renders the same JSONs).
"""
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.dirname(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# bench_mesh goes first: it starts a CPU child process, which must happen
# before this process initialises a JAX backend (see bench_mesh.run)
MODULES = [
    "bench_mesh",
    "fig3_similarity",
    "fig4_value_range",
    "fig5_bitwidth",
    "fig6_bops",
    "fig8_memaccess",
    "table2_accuracy",
    "fig13_speedup_energy",
    "fig15_crosstech",
    "fig16_dse",
    "fig17_defo",
    "fig18_ideal",
    "fig19_dynamic",
    "bench_compiled_step",
    "bench_serve_cache",
    "bench_int4_path",
    "bench_fused_step",
    "bench_scheduler",
    "bench_schedule",
    "bench_latency",
    "bench_faults",
]


def roofline_rows():
    """Summaries from the dry-run JSONs (if the sweep has been run)."""
    import glob
    import json

    rows = []
    files = sorted(glob.glob(os.path.join(os.path.dirname(__file__), "..", "experiments", "dryrun", "*.json")))
    n_ok = n_skip = 0
    worst = (None, 1e9)
    for f in files:
        r = json.load(open(f))
        if r["status"] == "skip":
            n_skip += 1
            continue
        if r["status"] != "ok":
            continue
        n_ok += 1
        rl = r["roofline"]
        rows.append(
            (f"roofline/{r['arch']}/{r['shape']}/{r['mesh']}",
             round(max(rl["compute_s"], rl["memory_s"], rl["collective_s"]) * 1e6, 1),
             f"dom={rl['dominant']};frac={rl['roofline_fraction']:.4f}")
        )
    rows.append(("roofline/cells_ok", 0, n_ok))
    rows.append(("roofline/cells_skip", 0, n_skip))
    return rows


def main(argv=None) -> None:
    import argparse
    import importlib

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fused", action="store_true",
                    help="run only bench_fused_step (the single-pass fused "
                         "diff-step kernel vs the two-pass path)")
    args = ap.parse_args(argv)
    modules = ["bench_fused_step"] if args.fused else MODULES
    from repro.compile_cache import use_compile_cache

    use_compile_cache()

    failures = []
    for mod_name in modules:
        t0 = time.monotonic()
        try:
            mod = importlib.import_module(mod_name)
            rows = mod.run()
            for name, us, derived in rows:
                print(f"{name},{us},{derived}", flush=True)
            print(f"# {mod_name} done in {time.monotonic()-t0:.1f}s", file=sys.stderr)
        except Exception as e:
            failures.append((mod_name, e))
            traceback.print_exc()
    for name, us, derived in roofline_rows():
        print(f"{name},{us},{derived}", flush=True)
    if failures:
        print(f"# FAILED: {[m for m, _ in failures]}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
