"""The compiled segment loop (``denoise_fn.segment_loop``,
``dit_runner.make_loop_fn``) against the per-step path it replaces under
DDIM, at the tiny DiT and MMDiT sizes of the other tests, seeded.

The per-step path runs the DDIM update op by op; the loop runs it fused
into one XLA program with the step, where XLA may contract a multiply-add
or turn a division by a square root into a multiply by its reciprocal
square root. So the images agree to float32 rounding (a few units in the
last place per step), not bit for bit, and are compared to a relative and
absolute 1e-5. The records (mode, class fractions, ``tile_hist``,
``step``), the tile totals and every counter but ``host_reads`` and
``looped_steps`` must be equal.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from repro.analysis import trace_audit as ta
from repro.core import diffusion
from repro.core.ditto import DittoEngine, DittoPlan, PlanSchedule, dit_runner
from repro.nn import dit as dit_mod
from repro.nn import mmdit
from repro.serve import CompiledRunnerCache, ServeScheduler, ServeSession

DIT = dit_mod.DiTCfg(d_model=64, n_layers=2, n_heads=2, patch=2, in_channels=4,
                     input_size=8, n_classes=4)
MMDIT = mmdit.MMDiTCfg(d_model=64, n_layers=3, n_heads=4, patch=2, in_channels=16,
                       input_size=8, ctx_tokens=6, ctx_dim=32, pooled_dim=16, n_prompts=4)
SCHED = diffusion.cosine_schedule(100)
LOOP_ONLY = ("host_reads", "looped_steps")  # the counters the loop changes

CASES = {
    "dit-act": ("dit", DittoPlan(steps=5, policy="act")),
    "dit-defo": ("dit", DittoPlan(steps=5, policy="defo")),
    "dit-two-segments": ("dit", PlanSchedule(DittoPlan(steps=6, policy="diff"),
                                             [(0, 3, {}), (3, 6, dict(low_bits=4))])),
    "mmdit-act": ("mmdit", DittoPlan(steps=4, policy="act")),
    "mmdit-defo": ("mmdit", DittoPlan(steps=4, policy="defo")),
}


@pytest.fixture(scope="module")
def models():
    """``{arch: (params, cfg)}``."""
    key = jax.random.PRNGKey(0)
    return {"dit": (dit_mod.init(key, DIT), DIT), "mmdit": (mmdit.init(key, MMDIT), MMDIT)}


def _request(cfg, b=2, seed=1):
    x = jax.random.normal(jax.random.PRNGKey(seed),
                          (b, cfg.input_size, cfg.input_size, cfg.in_channels))
    n = cfg.n_prompts if cfg is MMDIT else cfg.n_classes
    return x, jnp.arange(b) % n


def _sample(model, plan, *, loop=True, sampler="ddim", callback=None):
    """One seeded sample; ``loop=False`` hides ``segment_loop`` from the
    sampler, which then runs every step through the per-step path."""
    params, cfg = model
    x, labels = _request(cfg)
    eng = DittoEngine(policy=plan.policy, collect_oracle=True)
    fn = dit_runner.make_denoise_fn(params, cfg, eng, plan, bucket=x.shape[0])
    denoise = fn if loop else (lambda *a: fn(*a))
    eng.begin_sample()
    out = diffusion.SAMPLERS[sampler](SCHED, denoise, x, steps=plan.steps, labels=labels,
                                      callback=callback)
    return np.asarray(out), eng


@pytest.fixture(scope="module", params=list(CASES))
def pair(request, models):
    arch, plan = CASES[request.param]
    return {side: _sample(models[arch], plan, loop=side == "loop")
            for side in ("loop", "step")}, plan


def test_loop_images_match_the_per_step_path(pair):
    (loop, _), (step, _) = pair[0]["loop"], pair[0]["step"]
    np.testing.assert_allclose(loop, step, rtol=1e-5, atol=1e-5)


def test_loop_records_equal_the_per_step_records(pair):
    (_, eng), (_, ref) = pair[0]["loop"], pair[0]["step"]
    assert any(r.get("compiled") for r in eng.records)
    assert len(eng.records) == len(ref.records)
    for got, want in zip(eng.records, ref.records):
        assert got.keys() == want.keys()
        for k in want:
            assert got[k] == want[k], (got["layer"], got["step"], k)
            assert type(got[k]) is type(want[k]), (got["layer"], got["step"], k)
    np.testing.assert_array_equal(np.asarray(eng.tile_totals), np.asarray(ref.tile_totals))


def test_loop_counters_equal_the_per_step_counters_but_reads(pair):
    (_, eng), (_, ref) = pair[0]["loop"], pair[0]["step"]
    got, want = eng.counters(), ref.counters()
    assert {k: v for k, v in got.items() if k not in LOOP_ONLY} == {
        k: v for k, v in want.items() if k not in LOOP_ONLY}
    assert got["looped_steps"] == got["compiled_steps"] > 0
    assert want["looped_steps"] == 0


def test_one_host_read_per_segment_loop(pair):
    """The per-step path reads each compiled step's statistics once; the
    loop reads each segment's once (two segments hold compiled steps in
    the two-segment schedule, one otherwise)."""
    (_, eng), (_, ref) = pair[0]["loop"], pair[0]["step"]
    plan = pair[1]
    loops = len(plan.segment_plans()) if isinstance(plan, PlanSchedule) else 1
    steps = eng.counters()["compiled_steps"]
    assert ref.host_reads - eng.host_reads == steps - loops


@pytest.mark.parametrize("bypass", ["watchdog", "plms", "callback"])
def test_the_per_step_path_runs_where_the_loop_cannot(models, bypass):
    """The watchdog guards every step, PLMS carries its multistep history
    on the host and a callback needs every step's ``x``: each keeps the
    per-step path, so no step is looped."""
    plan = DittoPlan(steps=4, policy="diff", watchdog=bypass == "watchdog")
    seen = []
    _, eng = _sample(models["dit"], plan, sampler="plms" if bypass == "plms" else "ddim",
                     callback=(lambda **kw: seen.append(kw["step_index"]))
                     if bypass == "callback" else None)
    counters = eng.counters()
    assert counters["compiled_steps"] == 3 and counters["looped_steps"] == 0
    if bypass == "callback":
        assert seen == [0, 1, 2, 3]


def test_one_trace_per_key_sampler_and_length(models):
    """Two dispatches of one bucket share the loop's trace; a plan with
    more steps runs a longer loop, keyed and traced apart."""
    plan = DittoPlan(steps=4, policy="diff", max_batch=2, collect_stats=False)
    cache = CompiledRunnerCache()
    sess = ServeSession(models["dit"][0], DIT, SCHED, plan, cache=cache)
    x, labels = _request(DIT)
    first = sess.serve(x, labels)
    again = sess.serve(x, labels)
    assert [c.traces_delta for c in first.chunks + again.chunks] == [1, 0]
    (key,) = cache.trace_counts
    assert key.loop == ("repro.core.diffusion.ddim_step", 3)  # one eager step
    np.testing.assert_array_equal(np.asarray(first.sample), np.asarray(again.sample))
    sess.serve(x, labels, plan=plan.replace(steps=6))
    assert sorted(k.loop[1] for k in cache.trace_counts) == [3, 5]
    assert cache.n_traces == 2
    assert sess.stats()["looped_steps"] == sess.stats()["compiled_steps"] == 3 + 3 + 5


def test_warmup_compiles_the_loop_the_first_dispatch_runs(models):
    plan = DittoPlan(steps=5, policy="defo", max_batch=2)
    s = ServeScheduler(models["dit"][0], DIT, SCHED, plan)
    r = s.warmup(buckets=[2])
    assert r["aot_compiled"] == 1
    (key,) = s.session.cache.executables()
    assert key.loop == ("repro.core.diffusion.ddim_step", 3)  # after two eager steps
    before = s.stats()
    ticket = s.submit(*_request(DIT))
    s.flush()
    ticket.result()
    after = s.stats()
    assert after["traces"] == before["traces"]
    assert after["aot_misses"] == 0 and after["aot_hits"] == before["aot_hits"] + 1
    assert after["looped_steps"] == after["compiled_steps"] == 3


def test_the_loop_module_is_not_named_a_step():
    """``step.device_ms.*`` reads the XLA modules whose name holds
    ``step``: a whole segment loop must not be read as one step."""
    cache = CompiledRunnerCache()
    modes = ta.uniform_modes(DIT, "diff")
    update = diffusion.loop_update("ddim", SCHED)
    fn = cache.loop_for(DIT, modes, DittoPlan(collect_stats=False), update=update, length=3)
    dparams, mparams, lat, _, labels = ta.abstract_inputs(DIT, 2)
    ts = jax.ShapeDtypeStruct((4,), jnp.int32)
    text = fn.jitted.lower(dparams, mparams, ta.abstract_state(DIT, 2), lat, ts, labels,
                           update).as_text()
    module = text.split("\n", 1)[0]
    assert "denoise_loop" in module and "step" not in module
    assert diffusion.loop_update("plms", SCHED) is None


def _reader(name):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return harness._load_module(os.path.join(root, "bench", "metrics", f"{name}.py"),
                                f"test_metric_{name}").read


def test_looped_step_share_reads_the_window_change_of_the_counters():
    read = _reader("host.looped_step_frac.batch")

    def run(before, after):
        return type("Run", (), {"stats_before": before, "stats_after": after})()

    before = {"compiled_steps": 48, "looped_steps": 0}
    assert read(run(before, {"compiled_steps": 144, "looped_steps": 96})) == 1.0
    assert read(run(before, {"compiled_steps": 96, "looped_steps": 24})) == 0.5
    assert read(run(before, dict(before))) is None  # no compiled step in the window
    assert read(run({"compiled_steps": 0}, {"compiled_steps": 48})) is None  # a parent
