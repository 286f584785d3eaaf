"""The benchmark harness: one run of one cell of ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix or metric is
found by name: ``bench/configs/<config>.json`` (sizes, serving settings,
the correctness limit), ``bench/families/<family>.py`` (weights, the
program's view of them, the float32 reference), ``bench/mixes/<traffic>.json``
(read by :mod:`bench.traffic`) and ``bench/metrics/<metric>.py`` (a
``read(run) -> float | None`` over the :class:`Run` record). Adding a cell,
a mix or a metric adds files and edits none.

A run: build the weights on the device from the seed; hand them to the
program's ``ServeScheduler`` (async, every plan field the cell does not
name at its default); warm the cell's buckets (the scheduler's AOT warmup,
then one served dispatch per bucket so eager calibration's shapes compile
too); offer the mix for ``--seconds``; read peak memory; free the program;
compare a seeded sample of the served images with the float32 reference;
print the result line.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from typing import Any

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")  # fixed: the path is part of every cache key
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
PERSISTENT_CACHE_MIN_COMPILE_S = 0.0  # cache eager calibration's small compiles too


class NoDevice(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


def log(msg: str, device: dict | None = None) -> None:
    tag = (f"[{device['platform']} {device['kind']} x{device['count']}] "
           if device else "")
    print(f"bench: {tag}{msg}", file=sys.stderr, flush=True)


# ------------------------------------------------------------------- spec
def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict  # the configuration file's contents
    mix: dict
    family: Any  # the module bench/families/<family>.py
    end_to_end: list[dict]
    per_layer: list[dict]
    readers: dict[str, Any]  # metric name -> read(run)


def load_cell(name: str, root: str = ROOT) -> Cell:
    spec = _read_json(os.path.join(root, "BENCHMARK.json"))
    work = {w["name"]: w for w in spec["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r}; known: {sorted(work)}")
    w = work[name]
    (conf,) = [c for c in spec["configs"] if c["name"] == w["config"]]
    config = _read_json(os.path.join(root, conf["file"]))
    bench = os.path.join(root, "bench")
    mix = _read_json(os.path.join(bench, "mixes", f"{w['traffic']}.json"))
    family = _load_module(os.path.join(bench, "families", f"{config['family']}.py"),
                          f"bench_family_{config['family']}")
    e2e = [m for m in spec["end_to_end"] if name in m.get("workloads", [name])]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m else m["moves"] in e2e_names)]
    readers = {m["name"]: _load_module(os.path.join(bench, "metrics", f"{m['name']}.py"),
                                       f"bench_metric_{m['name']}").read
               for m in e2e + per_layer}
    return Cell(name, w["chips"], config, mix, family, e2e, per_layer, readers)


# ----------------------------------------------------------------- device
def device_info(chips: int) -> dict:
    """The devices as JAX reports them; :class:`NoDevice` off the TPU."""
    import jax

    devices = jax.devices()
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
    if info["platform"] != "tpu":
        raise NoDevice(f"the benchmark needs a TPU; JAX found {info['platform']!r}")
    if info["count"] < chips:
        raise NoDevice(f"the cell needs {chips} chips; JAX found {info['count']}")
    return info


def memory_peak_bytes() -> int | None:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


# --------------------------------------------------------------- the run
@dataclasses.dataclass
class Dispatch:
    """One ``ServeSession.serve`` call made by the scheduler (host clock)."""
    t0: float
    t1: float
    rows: int
    buckets: list
    modes: dict | None  # per-layer modes of its compiled steps
    compiled_steps: int | None  # compiled steps, where the program records them
    diff_tile_ops: float  # least operations of its non-zero diff tiles
    tiles: list  # [zero, low, full] diff tiles over its compiled steps


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read."""
    cell: Cell
    seed: int
    seconds: float
    plan: Any
    setup_s: float
    t0: float  # window start (host clock)
    requests: list
    dispatches: list[Dispatch]
    stats_before: dict
    stats_after: dict
    device: dict
    peaks: dict
    trace: Any = None  # tracing.TraceSummary of the traced window
    traced: list[Dispatch] = dataclasses.field(default_factory=list)

    @property
    def served(self) -> list:
        return [r for r in self.requests if r.error is None]

    @property
    def last_done(self) -> float:
        return max(r.done_t for r in self.requests)


def _wrap_session(session, cell: Cell, sink: list) -> None:
    """Record a :class:`Dispatch` (and a ``bench.dispatch`` trace span)
    around every serve call the scheduler makes."""
    import jax

    from bench import work

    serve = session.serve

    def traced_serve(x, labels=None, *, plan=None):
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            result = serve(x, labels, plan=plan)
        t1 = time.monotonic()
        modes, steps, ops, tiles = None, None, 0.0, [0, 0, 0]
        for chunk in result.chunks:
            eng = getattr(chunk, "engine", None)
            if modes is None and hasattr(eng, "compiled_modes"):
                modes = eng.compiled_modes()
            compiled = [r for r in chunk.records if r.get("compiled")]
            if compiled:
                steps = (steps or 0) + len({r["step"] for r in compiled})
            for r in compiled:
                if "tile_hist" in r:
                    z, lo, hi = r["tile_hist"]
                    tiles = [tiles[0] + z, tiles[1] + lo, tiles[2] + hi]
                    ops += (lo + hi) * work.diff_tile_ops(cell.config, chunk.bucket, r["layer"])
        sink.append(Dispatch(t0, t1, int(x.shape[0]), [c.bucket for c in result.chunks],
                             modes, steps, ops, tiles))
        return result

    session.serve = traced_serve


class _Tracer:
    """The profiler over the traced window: from the window's start until the
    first request completes (one whole dispatch), marked by a
    ``bench.window`` span."""

    def __init__(self):
        self._window = None

    def start(self) -> None:
        import jax

        from bench import tracing

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
        self._window = jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN)
        self._window.__enter__()

    def stop(self) -> None:
        if self._window is None:
            return
        import jax

        self._window.__exit__(None, None, None)
        self._window = None
        jax.profiler.stop_trace()


def reference_check(cell: Cell, weights, chosen, steps: int, bits: int = 0) -> float:
    """Worst relative L2 distance, over the chosen requests' rows, between
    the served images and the reference's (``bits`` fake-quantizes the
    reference: the control)."""
    import jax.numpy as jnp

    worst = 0.0
    block = cell.config["max_batch"]
    rows = [(x[i:i + 1], lab[i:i + 1], s[i]) for x, lab, s in chosen for i in range(x.shape[0])]
    for a in range(0, len(rows), block):
        part = rows[a:a + block]
        ref = cell.family.sample(cell.config, weights, jnp.concatenate([p[0] for p in part]),
                                 jnp.concatenate([p[1] for p in part]), steps, bits=bits)
        for (_, _, served), want in zip(part, ref):
            num = np.linalg.norm(np.asarray(served, np.float64) - want)
            rel = num / np.linalg.norm(want)
            worst = max(worst, rel if np.isfinite(rel) else math.inf)
    return float(worst)


def choose_requests(requests, seed: int, check_rows: int) -> list:
    """A seeded sample of the served requests, the largest among them,
    holding at least ``check_rows`` rows (or all of them)."""
    ok = [r for r in requests if r.error is None]
    if not ok:
        return []
    rng = np.random.default_rng(np.random.SeedSequence([seed, 4]))
    largest = max(ok, key=lambda r: (r.rows, -r.index))
    chosen, rows = [largest], largest.rows
    for i in rng.permutation(len(ok)):
        if rows >= check_rows:
            break
        if ok[i] is not largest:
            chosen.append(ok[i])
            rows += ok[i].rows
    return chosen


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, device: dict,
             process_t0: float) -> dict:
    """One run; returns the result line's fields and the compared numbers."""
    from bench import peaks as peaks_mod
    from bench import tracing
    from bench.traffic import Traffic, seed_key
    from repro.core import diffusion
    from repro.core.ditto.plan import DittoPlan
    from repro.serve import ServeScheduler

    peaks = peaks_mod.peaks_for(device["kind"])
    config, mix = cell.config, cell.mix
    weights = cell.family.init_weights(config, seed_key(seed, 0))
    params, model_cfg = cell.family.program_model(config, weights)
    plan = DittoPlan(steps=mix["steps"], sampler=mix["sampler"],
                     max_batch=config["max_batch"], **mix.get("plan", {}))
    traffic = Traffic(mix, max_batch=config["max_batch"],
                      latent_shape=cell.family.latent_shape(config),
                      n_classes=config["num_classes"], seed=seed)
    scheduler = ServeScheduler(params, model_cfg, diffusion.cosine_schedule(1000), plan,
                               async_mode=True, collect_done=True)
    tracer = _Tracer()
    try:
        scheduler.warmup(buckets=traffic.buckets())
        traffic.warm(scheduler)
        dispatches: list[Dispatch] = []
        _wrap_session(scheduler.session, cell, dispatches)
        stats_before = scheduler.stats()
        t0 = time.monotonic()
        setup_s = t0 - process_t0
        log(f"set-up {setup_s:.1f} s; window of {seconds} s", device)
        if trace:
            tracer.start()
        requests = traffic.run(scheduler, t0, seconds, on_first_done=tracer.stop)
        stats_after = scheduler.stats()
        device = dict(device, memory_peak_bytes=memory_peak_bytes())
        chosen = [(r.x, r.labels, np.asarray(r.ticket.result()))
                  for r in choose_requests(requests, seed, mix["check_rows"])]
        for r in requests:
            r.ticket = r.x = r.labels = None
    finally:
        tracer.stop()
        scheduler.close(drain=False)
    del scheduler, params
    gc.collect()
    run = Run(cell, seed, seconds, plan, setup_s, t0, requests, dispatches, stats_before,
              stats_after, device, peaks)
    if trace:
        summary = tracing.summarize(tracing.load_events(TRACE_DIR))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        first_done = min(r.done_t for r in requests)
        run.trace = summary
        run.traced = [d for d in dispatches if d.t1 <= first_done + 1e-3]
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
    checks = {}
    if chosen:
        checks["rel_l2"] = (reference_check(cell, weights, chosen, mix["steps"]),
                            config["check"]["rel_l2"])
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = cell.readers[m["name"]](run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    correct = bool(checks) and all(v <= lim for v, lim in checks.values())
    out = {"correct": correct, "attempted": len(requests),
           "failed": sum(r.error is not None for r in requests),
           "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = run.trace.breakdown()
    out["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
    return out


# ------------------------------------------------------------------- main
def configure_jax() -> None:
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      PERSISTENT_CACHE_MIN_COMPILE_S)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description="Run one cell of BENCHMARK.json on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, process_t0: float | None = None) -> int:
    process_t0 = time.monotonic() if process_t0 is None else process_t0
    args = parse_args(argv)
    if args.seed < 0:
        log("--seed must be >= 0")
        return 2
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        log(f"the system under test is missing ({src}/repro)")
        return 2
    sys.path.insert(0, src)
    cell = load_cell(args.workload)
    try:
        device = device_info(cell.chips)
    except NoDevice as e:
        log(str(e))
        return 3
    configure_jax()
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), device, process_t0)
    for name, c in out["checks"].items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}", device)
    print(json.dumps(out), flush=True)
    return 0
