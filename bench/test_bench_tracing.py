"""The trace reduction on a small synthetic trace: busy union, per-kernel
sums, window clipping and labelled idle gaps."""
import pytest

from bench import tracing
from bench.tracing import Event

DEV0, DEV1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"


def op(name, start, end, plane=DEV0, line=tracing.OPS_LINE):
    return Event(plane, line, name, float(start), float(end - start))


def span(name, start, end):
    return Event(HOST, "python", name, float(start), float(end - start))


TRACE = [
    span("bench.window", 0, 100),
    span("bench.dispatch", 5, 70),
    op("int8_matmul.1", 10, 20),
    op("int8_matmul.2", 15, 30),  # overlaps the first: counted once in busy time
    op("ditto_diff_matmul.3", 40, 50),
    op("fusion.7", 60, 61),
    op("fusion.8", 95, 120),  # runs past the window's end: clipped
    op("jit_counting_step(1)", 10, 61, line=tracing.MODULES_LINE),
    span("unrelated", 0, 1),  # not a bench span: never a label
]


def test_busy_time_is_the_union_clipped_to_the_window():
    s = tracing.summarize(TRACE)
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx((20 + 10 + 1 + 5) * 1e-9)
    assert s.idle_frac == pytest.approx(1 - 36 / 100)
    assert s.devices == 1


def test_per_kernel_sums_strip_the_instruction_suffix():
    s = tracing.summarize(TRACE)
    assert s.ops["int8_matmul"] == [pytest.approx(25e-9), 2]
    assert s.op_seconds("ditto_diff_matmul") == pytest.approx(10e-9)
    assert s.op_seconds("fusion") == pytest.approx(6e-9)
    assert s.op_seconds("absent") == 0.0
    assert s.modules["jit_counting_step(1)"] == [pytest.approx(51e-9), 1]
    top = s.breakdown()["device_ops"]
    assert top[0][0] == "int8_matmul" and len(top) == 3


def test_idle_gaps_are_labelled_by_host_span_and_next_op():
    s = tracing.summarize(TRACE)
    gaps = dict(s.gaps)
    assert [g for _, g in s.gaps] == sorted((g for _, g in s.gaps), reverse=True)
    assert gaps["no span before fusion"] == pytest.approx(34e-9)  # 61..95, after the dispatch
    assert gaps["bench.dispatch before fusion"] == pytest.approx(10e-9)  # 50..60
    assert gaps["bench.dispatch before int8_matmul"] == pytest.approx(10e-9)  # 0..10
    assert gaps["bench.dispatch before ditto_diff_matmul"] == pytest.approx(10e-9)
    assert sum(g for _, g in s.gaps) == pytest.approx(s.window_s - s.busy_s)


def test_busy_time_is_averaged_over_devices_and_window_may_be_given():
    two = TRACE + [op("int8_matmul.9", 0, 50, plane=DEV1)]
    s = tracing.summarize(two, window=(0.0, 50.0))
    assert s.devices == 2
    assert s.busy_s == pytest.approx(((20 + 10) + 50) / 2 * 1e-9)


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError, match="bench.window"):
        tracing.summarize([e for e in TRACE if e.name != "bench.window"])


def test_a_tpu_trace_op_named_by_its_hlo_line_sums_under_its_kernel():
    line = ("%ditto_diff_matmul.316 = s32[1024,4608]{1,0:T(8,128)} custom-call("
            "s32[8,9]{1,0} %diff_encode.316), custom_call_target=\"tpu_custom_call\"")
    assert tracing.base_name(line) == "ditto_diff_matmul"
    assert tracing.base_name("int8_matmul.7") == "int8_matmul"
