"""Spans and counters of the serving path (docs/architecture.md,
"Observability").

  * spans: a toy-width serve under ``jax.profiler.trace`` writes the
    ``serve.*``/``ditto.*``/``diffusion.*`` host spans, one per phase and
    step, each carrying the dispatch index;
  * counters: ``host_reads`` (one per segment loop's packed statistics),
    ``eager_steps``, ``compiled_steps``, ``looped_steps`` and the per-layer
    ``tile_hist``
    (accumulated on the device, read once per dispatch) with
    ``collect_stats`` on and off, summed into
    ``ServeSession.stats()`` and ``ServeScheduler.stats()``;
  * tickets stamp ``dispatch_t``; the scheduler sums the queue wait;
  * the jitted step's device ops carry their block's named scope.
"""
import collections
import glob
import re

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import trace_audit as ta
from repro.core import diffusion
from repro.core.ditto import DittoPlan, dit_runner
from repro.nn import dit as dit_mod
from repro.serve import ServeScheduler, ServeSession

CFG = dit_mod.DiTCfg(d_model=64, n_layers=2, n_heads=2, patch=2, in_channels=4,
                     input_size=8, n_classes=4)
STEPS = 5
PREFIXES = ("serve.", "ditto.", "diffusion.")


@pytest.fixture(scope="module")
def setup():
    params = dit_mod.init(jax.random.PRNGKey(0), CFG)
    return params, diffusion.cosine_schedule(100)


def _request(b, seed=0):
    x = jax.random.normal(jax.random.PRNGKey(100 + seed),
                          (b, CFG.input_size, CFG.input_size, CFG.in_channels))
    return x, jnp.arange(b) % CFG.n_classes


def _records_tiles(records) -> dict:
    out: dict = {}
    for r in records:
        if "tile_hist" in r:
            out[r["layer"]] = tuple(a + b for a, b in zip(out.get(r["layer"], (0, 0, 0)),
                                                           r["tile_hist"]))
    return out


@pytest.fixture(scope="module")
def served(setup):
    """One 2-row request through a sync scheduler per collect_stats setting
    (policy diff, so diff tiles exist at toy widths)."""
    params, sched = setup
    out = {}
    for stats in (True, False):
        plan = DittoPlan(steps=STEPS, policy="diff", max_batch=4, collect_stats=stats)
        s = ServeScheduler(params, CFG, sched, plan, retain=True)
        x, labels = _request(2)
        ticket = s.submit(x, labels)
        ticket.result()
        out[stats] = (s, ticket, s.dispatches[0])
    return out


@pytest.mark.parametrize("stats", [True, False])
def test_tile_counter_is_kept_with_and_without_collect_stats(served, stats):
    _, _, result = served[stats]
    tiles = result.counters["tile_hist"]
    assert tiles and len(tiles) == 2 * 9 + 1  # every layer runs diff under policy diff
    assert all(len(h) == 3 and sum(h) > 0 for h in tiles.values())
    if stats:
        assert tiles == _records_tiles(result.records)
    else:
        assert not _records_tiles(result.records)
    # the same tiles either way: collect_stats only adds the host records
    assert tiles == served[not stats][2].counters["tile_hist"]


@pytest.mark.parametrize("stats", [True, False])
def test_step_counters_cover_every_step(served, stats):
    _, _, result = served[stats]
    c = result.counters
    assert c["eager_steps"] == 1  # policy diff calibrates on one eager step
    assert c["eager_steps"] + c["compiled_steps"] == STEPS
    assert c["host_reads"] > 0  # the eager step's class fractions are read


def test_host_reads_are_one_per_compiled_step_and_fall_with_stats_off(served):
    on, off = (served[s][2].counters["host_reads"] for s in (True, False))
    assert on > off
    records = served[True][2].records
    counters = served[True][2].counters
    compiled_steps = counters["compiled_steps"]
    assert compiled_steps == len({r["step"] for r in records if r.get("compiled")})
    # the compiled steps of a bare plan run as one segment loop, whose
    # statistics are one packed fetch; the eager step adds its spatial
    # oracle, three scalars per record that carries one
    assert counters["looped_steps"] == compiled_steps
    assert on - off == 1 + 3 * sum(
        1 for r in records if "cls_spatial" in r and not r.get("compiled"))
    # the jitted step returns its statistics as at most two arrays
    dparams, mparams, lat, t, labels = ta.abstract_inputs(CFG, 2)
    step = dit_runner.make_step_fn(CFG, ta.uniform_modes(CFG, "diff"),
                                   DittoPlan(collect_stats=True))
    _, _, stats = jax.eval_shape(step, dparams, mparams, ta.abstract_state(CFG, 2),
                                 lat, t, labels)
    assert len(jax.tree_util.tree_leaves(stats)) <= 2


@pytest.mark.parametrize("stats", [True, False])
def test_counters_reach_session_and_scheduler_stats(served, stats):
    s, _, result = served[stats]
    got = s.stats()
    for k in ("host_reads", "eager_steps", "compiled_steps"):
        assert got[k] == result.counters[k]
    assert got["tiles"] == [sum(h[i] for h in result.counters["tile_hist"].values())
                            for i in range(3)]
    assert got["tiles_txt"] == [0, 0, 0]  # a DiT has no text stream
    assert s.session.stats()["compiled_steps"] == result.counters["compiled_steps"]


def test_ticket_stamps_its_dispatch_time(served):
    s, ticket, _ = served[True]
    assert ticket.submit_t <= ticket.dispatch_t <= ticket.done_t
    got = s.stats()
    assert got["tickets_dispatched"] == 1
    assert got["queue_wait_s"] == pytest.approx(ticket.dispatch_t - ticket.submit_t)


def test_session_counters_accumulate_across_dispatches(setup):
    params, sched = setup
    plan = DittoPlan(steps=3, policy="diff", max_batch=2, collect_stats=False)
    sess = ServeSession(params, CFG, sched, plan)
    x, labels = _request(3)  # two chunks: buckets 2 and 1
    result = sess.serve(x, labels)
    assert len(result.chunks) == 2
    assert result.counters["compiled_steps"] == 2 * 2
    before = sess.stats()
    assert before["eager_steps"] == 2
    assert before["host_reads"] == result.counters["host_reads"] > 0
    again = sess.serve(x[:1], labels[:1])
    after = sess.stats()
    for k in ("host_reads", "eager_steps", "compiled_steps"):
        assert after[k] == before[k] + again.counters[k]
    assert after["compiled_steps"] == 3 * 2


def _span_events(log_dir):
    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIXES):
                    out.append((ev.name, dict(ev.stats)))
    return out


def test_a_traced_serve_writes_one_span_per_phase_and_step(setup, tmp_path):
    params, sched = setup
    plan = DittoPlan(steps=STEPS, max_batch=4)  # defo: two eager calibration steps
    sess = ServeSession(params, CFG, sched, plan)
    x, labels = _request(2)
    sess.serve(x, labels)  # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        sess.serve(x, labels)
    events = _span_events(tmp_path)
    counts = collections.Counter(name for name, _ in events)
    assert counts["serve.dispatch"] == 1
    assert counts["serve.block"] == 1
    assert counts["ditto.requantize"] == 1
    # the eager steps are sampler steps of their own; the compiled ones run
    # as one segment loop, whose statistics are recorded once
    assert counts["diffusion.step"] == 2
    assert counts["ditto.eager_step"] == 2
    assert counts["ditto.compiled_step"] == 0
    assert counts["ditto.loop"] == 1
    assert counts["ditto.record_step"] == 1
    assert counts["ditto.runner_build"] == 1
    # every span of the dispatch carries its index (the second serve: 1)
    assert {args.get("dispatch") for _, args in events} == {1}
    (args,) = [a for n, a in events if n == "serve.dispatch"]
    assert args["rows"] == 2 and str(args["buckets"]) == "2"
    (loop,) = [a for n, a in events if n == "ditto.loop"]
    assert (loop["step"], loop["steps"]) == (2, STEPS - 2)


def test_step_ops_carry_the_block_scopes():
    dparams, mparams, lat, t, labels = ta.abstract_inputs(CFG, 2)
    modes = ta.uniform_modes(CFG, "diff")
    step = dit_runner.make_step_fn(CFG, modes, DittoPlan(collect_stats=False))
    state = ta.abstract_state(CFG, 2)
    text = jax.jit(step).lower(dparams, mparams, state, lat, t, labels).as_text(
        debug_info=True)
    scopes = set(re.findall(r"(blk\d+/(?:attn|mlp))", text))
    assert scopes == {f"blk{i}/{s}" for i in range(CFG.n_layers) for s in ("attn", "mlp")}


def test_tile_totals_do_not_add_a_compile_on_a_committed_submesh(setup):
    """A session on a submesh commits its params, so the temporal state is
    committed; the tile-total leaf starts placed as the step returns it, so
    the step compiles once, not once for the first call and again after."""
    import numpy as np

    params, sched = setup
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    plan = DittoPlan(steps=4, policy="diff", max_batch=2, collect_stats=False)
    sess = ServeSession(params, CFG, sched, plan, mesh=mesh)
    x, labels = _request(2)
    assert sess.serve(x, labels).counters["compiled_steps"] == 3
    (runner,) = sess.cache._steps.values()
    assert runner.jitted._cache_size() == 1
