"""Packed compiled-step statistics (``compiled.PackedStats``).

The jitted step returns its per-layer aux pytree packed into one float32
vector of class fractions and one int32 vector of tile counts, and the
engine reads a step's statistics with one transfer. These tests serve one
request twice at toy width: once as shipped, and once with the packing
turned off and the records built by a per-scalar reader written here (one
``float``/``int`` read per scalar of the unpacked aux, as the engine read
them before packing; a segment loop's statistics, stacked on a step axis,
are read step by step the same way). Records must agree field for field
and the served images bit for bit, across policies, batch 1 (no
``cls_spatial`` on the one-row ``mod`` layers) and batch 2, a forced
watchdog re-anchor (the per-step path) and a plan schedule's segment swap
(one segment loop per segment).
"""
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import diffusion
from repro.core.ditto import DittoEngine, DittoPlan, PlanSchedule
from repro.core.ditto import bops as bops_mod
from repro.core.ditto import compiled as compiled_mod
from repro.nn import dit as dit_mod
from repro.serve import ServeSession

CFG = dit_mod.DiTCfg(d_model=64, n_layers=2, n_heads=2, patch=2, in_channels=4,
                     input_size=8, n_classes=4)
STEPS = 4


@pytest.fixture(scope="module")
def setup():
    params = dit_mod.init(jax.random.PRNGKey(0), CFG)
    return params, diffusion.cosine_schedule(100)


def _request(b):
    x = jax.random.normal(jax.random.PRNGKey(7), (b, CFG.input_size, CFG.input_size,
                                                  CFG.in_channels))
    return x, jnp.arange(b) % CFG.n_classes


def per_scalar_record(self, aux, *, modes=None, reanchor=False, step=None):
    """Reference reader: one host read per scalar of the unpacked aux."""
    if self._compiled_base is None:
        base_by_layer: dict = {}
        for r in self.records:
            base_by_layer.setdefault(r["layer"], r)
        self._compiled_base = (self.compiled_modes(), base_by_layer)
    base_modes, base_by_layer = self._compiled_base
    if modes is None:
        modes = base_modes
    for name, a in aux.items():
        base = base_by_layer[name]
        meta = self.meta[name]
        rec: dict[str, Any] = {"layer": name, "step": self.step_idx if step is None else step,
                               "mode": modes[name], "kind": meta.kind, "macs": base["macs"],
                               "compiled": True}
        if reanchor:
            rec["reanchor"] = True
        read = self.host_read
        cls_act = tuple(read(v) for v in a["cls_act"])
        cls_diff = tuple(read(v) for v in a["cls_diff"]) if "cls_diff" in a else None
        cls_sp = tuple(read(v) for v in a["cls_spatial"]) if "cls_spatial" in a else None
        self._account_classes(rec, base["t"], base["k"], base["n"], cls_act, cls_diff, meta,
                              attention=base["attention"], cls_spatial=cls_sp)
        if "tile_hist" in a:
            hist = tuple(read(v, int) for v in a["tile_hist"])
            rec["tile_hist"] = hist
            rec["tile_fracs"] = bops_mod.tile_fractions(hist)
            rec["bops_tile"] = bops_mod.bops_tile_mix(rec["macs"], hist)
        self.records.append(rec)


def per_scalar_records(self, aux):
    """Reference reader of a segment loop's unpacked aux, whose leaves are
    stacked on a leading step axis: each step read per scalar."""
    steps = jax.tree_util.tree_leaves(aux)[0].shape[0]
    for i in range(steps):
        per_scalar_record(self, jax.tree_util.tree_map(lambda v: v[i], aux),
                          step=self.step_idx + i)


def _serve(params, sched, plan, b):
    x, labels = _request(b)
    result = ServeSession(params, CFG, sched, plan).serve(x, labels)
    (chunk,) = result.chunks
    return np.asarray(result.sample), result.records, chunk.engine


WATCHDOG = DittoPlan(steps=STEPS, policy="diff", watchdog=True, reanchor_full_frac=0.01)
CASES = [
    *[(f"{policy}-b{b}", DittoPlan(steps=STEPS, policy=policy), b)
      for policy in ("act", "diff", "defo", "defo+") for b in (1, 2)],
    ("watchdog-reanchor", WATCHDOG, 2),
    ("schedule-swap", PlanSchedule(DittoPlan(steps=STEPS, policy="diff"),
                                   [(0, 2, {}), (2, STEPS, dict(low_bits=4))]), 2),
]


@pytest.mark.parametrize("label,plan,b", CASES, ids=[c[0] for c in CASES])
def test_packed_records_equal_the_per_scalar_reader(setup, monkeypatch, label, plan, b):
    params, sched = setup
    image, records, eng = _serve(params, sched, plan, b)
    with monkeypatch.context() as m:
        m.setattr(compiled_mod, "pack_stats", lambda aux: aux)
        m.setattr(DittoEngine, "record_compiled_step", per_scalar_record)
        m.setattr(DittoEngine, "record_compiled_steps", per_scalar_records)
        ref_image, ref_records, ref_eng = _serve(params, sched, plan, b)
    np.testing.assert_array_equal(image, ref_image)
    assert any(r.get("compiled") for r in records)
    assert len(records) == len(ref_records)
    for got, want in zip(records, ref_records):
        assert got.keys() == want.keys()
        for k in want:
            assert got[k] == want[k], (got["layer"], got["step"], k)
            assert type(got[k]) is type(want[k]), (got["layer"], got["step"], k)
    assert eng.watchdog_events == ref_eng.watchdog_events
    if plan is WATCHDOG:
        assert [e["trigger"] for e in eng.watchdog_events] == ["saturation"]
        assert any(r.get("reanchor") for r in records)
    if b == 1:
        assert not any("cls_spatial" in r for r in records
                       if r.get("compiled") and r["layer"].endswith(".mod"))
