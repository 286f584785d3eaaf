"""The correctness check against the timed path, on the CPU at a size a test
run holds: a sound run is correct, and a run whose timed path is broken
underneath, or whose program is replaced by the control, is not.

Each run goes through ``harness.run_cell`` (the chip lookup is the only
step skipped): the scheduler, session, compiled step and (interpreted)
Pallas kernels, the traffic, and the comparison with the float32 reference.
The limit here is set for this size: sound runs of the program read
0.0065-0.024 and the 4-bit control 0.10-0.135 on the CPU (DiT with width
32, one layer, 4 tokens, DDIM 3), so it sits at 0.06. The cells' own
limits come from chip runs at their sizes (``PERF.md``). The fault of a
missing exchange between chips does not apply: every cell runs on one chip.
"""
import contextlib
import time

import jax.numpy as jnp
import pytest

from bench import harness, peaks

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
TINY = dict(hidden_size=32, depth=1, num_heads=2, input_size=4, num_classes=10,
            max_batch=2, check={"rel_l2": 0.06})


@contextlib.contextmanager
def tiny_cell(name):
    with pytest.MonkeyPatch.context() as mp:
        mp.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
        cell = harness.load_cell(name)
        cell.config.update(TINY)
        cell.mix = dict(cell.mix, steps=3)
        yield cell


@pytest.fixture(scope="module")
def cell():
    with tiny_cell("xl2-256.batch") as c:
        yield c


def run(cell, seed):
    return harness.run_cell(cell, seed, 0.3, False, CPU, time.monotonic())


def test_sound_runs_are_correct(cell):
    out = run(cell, 2**33 + 7)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] >= 1
    assert 0 < out["checks"]["rel_l2"]["value"] < 0.06
    assert out["metrics"]["images_per_s"]["value"] > 0
    with tiny_cell("xl2-256.single") as single:
        out = run(single, 11)
    assert out["correct"] and set(out["metrics"]) == {"latency_p50_s", "latency_p95_s",
                                                      "setup_s"}
    assert list(out)[-1] == "checks"


def _serve_records_fault(kind, cell, weights):
    from repro.sim import harness as program_harness

    serve = program_harness.serve_records

    def broken(params, cfg, sched, x, labels=None, plan=None, **kw):
        if kind == "half_batch":  # half of the rows left out, the mean of the rest in their place
            h = (x.shape[0] + 1) // 2
            rec, s, eng = serve(params, cfg, sched, x[:h], labels[:h], plan, **kw)
            fill = jnp.broadcast_to(s.mean(axis=0), (x.shape[0] - h,) + s.shape[1:])
            return rec, jnp.concatenate([s, fill]), eng
        if kind == "answer_altered":  # one served image changed where it is produced
            rec, s, eng = serve(params, cfg, sched, x, labels, plan, **kw)
            return rec, s.at[0].add(0.5), eng
        if kind == "control_int4":  # the reference one precision below int8, in the program's place
            s = cell.family.sample(cell.config, weights[0], x, labels, plan.steps, bits=4)
            return [], jnp.asarray(s), None
        raise ValueError(kind)

    return broken


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch", "answer_altered",
                                   "control_int4"])
def test_a_broken_timed_path_is_not_correct(cell, fault, monkeypatch):
    weights = []
    init = cell.family.init_weights
    monkeypatch.setattr(cell.family, "init_weights",
                        lambda config, key: weights.append(init(config, key)) or weights[-1])
    if fault == "state_unchanged":  # every denoising step returns its latents unchanged
        monkeypatch.setattr("repro.core.diffusion.ddim_step",
                            lambda sched, x_t, eps_hat, t, t_prev, **kw: x_t)
    else:
        monkeypatch.setattr("repro.sim.harness.serve_records",
                            _serve_records_fault(fault, cell, weights))
    out = run(cell, 1000 + len(fault))
    assert out["correct"] is False
    assert out["checks"]["rel_l2"]["value"] > out["checks"]["rel_l2"]["limit"]
