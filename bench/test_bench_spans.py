"""The program-span reduction (bench/spans.py) on a small synthetic trace,
and the readers of the program's counters on a synthetic run."""
import os
import types

import pytest

from bench import harness, spans, tracing
from bench.tracing import Event

DEV, HOST = "/device:TPU:0", "/host:CPU"


def op(name, start, end):
    return Event(DEV, tracing.OPS_LINE, name, float(start), float(end - start))


def span(name, start, end, line="python"):
    return Event(HOST, line, name, float(start), float(end - start))


# one dispatch: set-up, an eager step, a compiled step whose stats readback
# follows the device work; the window ends inside the dispatch's block
TRACE = [
    span("bench.window", 0, 100),
    span("bench.dispatch", 0, 120),
    span("serve.dispatch", 2, 118),
    span("ditto.requantize", 4, 30),
    span("diffusion.step", 30, 60),
    span("ditto.eager_step", 31, 58),
    span("diffusion.step", 60, 95),
    span("ditto.compiled_step", 61, 94),
    span("ditto.record_step", 70, 94),
    span("serve.block", 95, 118),
    span("ditto.eager_step", 0, 50, line="another thread"),  # not nested in the dispatch's
    op("fusion.1", 40, 45),
    op("int8_matmul.2", 62, 70),
    op("fusion.3", 96, 99),
]
WINDOW = (0.0, 100.0)
ONE_THREAD = [e for e in TRACE if e.line != "another thread"]


def test_a_gap_is_labelled_by_the_innermost_program_span():
    gaps = dict(spans.label_gaps(ONE_THREAD, WINDOW))
    assert gaps == {
        "ditto.requantize before fusion": pytest.approx(40e-9),  # 0..40, mid 20
        "ditto.eager_step before int8_matmul": pytest.approx(17e-9),  # 45..62
        "ditto.record_step before fusion": pytest.approx(26e-9),  # 70..96, mid 83
        "serve.block before window end": pytest.approx(1e-9),  # 99..100
    }


def test_a_gap_outside_program_spans_keeps_the_bench_label():
    trace = [span("bench.window", 0, 10), span("bench.dispatch", 0, 10),
             span("serve.dispatch", 6, 10), op("fusion.1", 2, 3)]
    assert spans.label_gaps(trace, (0.0, 10.0)) == [
        ("serve.dispatch before window end", pytest.approx(7e-9)),
        ("bench.dispatch before fusion", pytest.approx(2e-9))]


def test_span_totals_are_clipped_to_the_window():
    tot = spans.totals(spans.program_spans(TRACE), WINDOW)
    assert tot["serve.dispatch"] == [pytest.approx(98e-9), 1]  # 2..118 clipped at 100
    assert tot["serve.block"] == [pytest.approx(5e-9), 1]
    assert tot["diffusion.step"] == [pytest.approx(65e-9), 2]
    assert tot["ditto.eager_step"] == [pytest.approx(77e-9), 2]  # both threads
    assert "bench.dispatch" not in tot
    outside = spans.totals([span("ditto.requantize", 200, 300)], WINDOW)
    assert outside == {}


def test_self_time_is_time_less_the_child_spans_on_the_same_thread():
    own = spans.self_times(spans.program_spans(TRACE), WINDOW)
    assert own["serve.dispatch"] == pytest.approx((98 - 26 - 30 - 35 - 5) * 1e-9)
    assert own["diffusion.step"] == pytest.approx((65 - 27 - 33) * 1e-9)
    assert own["ditto.compiled_step"] == pytest.approx((33 - 24) * 1e-9)
    assert own["ditto.record_step"] == pytest.approx(24e-9)
    assert own["ditto.eager_step"] == pytest.approx((27 + 50) * 1e-9)
    total = sum(v[0] for v in spans.totals(spans.program_spans(TRACE), WINDOW).values())
    assert sum(own.values()) == pytest.approx(total - (26 + 30 + 35 + 5 + 27 + 33 + 24) * 1e-9)


def test_split_names_the_dispatch_and_the_idle_time():
    out = spans.split(ONE_THREAD)
    assert out["window_s"] == pytest.approx(100e-9)
    assert out["busy_s"] == pytest.approx(16e-9)
    assert out["dispatch_s"] == pytest.approx(98e-9)
    assert out["named_share"] == pytest.approx(96 / 98)  # 2..4 is the dispatch's own
    # idle: 0..40, 45..62, 70..96, 99..100; only 0..4 lies in no span below serve.dispatch
    assert out["idle_s"] == pytest.approx(84e-9)
    assert out["idle_named_share"] == pytest.approx(80 / 84)
    assert out["idle_gaps"][0] == ("ditto.requantize before fusion", pytest.approx(40e-9))


def test_split_without_a_window_span_uses_the_dispatch():
    out = spans.split([e for e in ONE_THREAD if e.name != "bench.window"])
    assert out["window_s"] == pytest.approx(116e-9)
    with pytest.raises(ValueError, match="serve.dispatch"):
        spans.split([op("fusion.1", 0, 1)])


# ------------------------------------------------------- counter readers
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reader(name):
    return harness._load_module(os.path.join(ROOT, "bench", "metrics", f"{name}.py"),
                                f"test_metric_{name}").read


def _run(before, after):
    return types.SimpleNamespace(stats_before=before, stats_after=after)


def test_reads_per_step_reads_the_window_change_of_the_program_counters():
    read = _reader("host.reads_per_step.batch")
    before = {"host_reads": 1000, "eager_steps": 2, "compiled_steps": 48}
    after = {"host_reads": 1000 + 2 * 800 + 48 * 2700, "eager_steps": 4, "compiled_steps": 96}
    assert read(_run(before, after)) == pytest.approx((1600 + 48 * 2700) / 50)
    assert read(_run(before, dict(before))) is None  # no step in the window
    assert read(_run({"dispatches": 0}, {"dispatches": 1})) is None  # a program without them


def test_queue_wait_reads_the_mean_wait_of_the_window_tickets():
    read = _reader("sched.queue_wait_s.single")
    before = {"queue_wait_s": 1.5, "tickets_dispatched": 3}
    after = {"queue_wait_s": 1.5 + 0.25 + 0.75, "tickets_dispatched": 5}
    assert read(_run(before, after)) == pytest.approx(0.5)
    assert read(_run(before, dict(before))) is None
    assert read(_run({}, {})) is None


@pytest.mark.parametrize("name,cells", [
    ("host.reads_per_step.batch", ["xl2-256.batch", "xl2-512.batch"]),
    ("sched.queue_wait_s.single", ["xl2-256.single"]),
])
def test_the_new_metrics_are_found_by_name_in_their_cells(name, cells):
    for cell in ["xl2-256.batch", "xl2-256.single", "xl2-512.batch"]:
        loaded = harness.load_cell(cell)
        assert (name in loaded.readers) == (cell in cells)
