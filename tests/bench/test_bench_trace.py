"""The reduction from trace events to busy/idle time, kernel time by
name pattern and idle gaps attributed to host spans: on hand-made
events with known answers, and on a 120 ms slice of a trace recorded
on a TPU v5e (`fit_batches` at H=784, D=8192, 2048-image steps)."""

from pathlib import Path

import pytest

from bench_tiny import ROOT  # noqa: F401  (puts the repository on the path)

DEV, HOST = "/device:TPU:0", "/host:CPU"
FIT_OP = ('%_partial_fit_donated.1 = s32[16,8192]{1,0} custom-call(s32[2048,896]{1,0} %p), '
          'custom_call_target="tpu_custom_call"')
RECORDED = Path(__file__).with_name("fit_trace_v5e.json.gz")


def _events():
    from bench.trace_reduce import Event

    return [
        Event(HOST, "python", "bench.window", 1.0, 1.0),
        Event(DEV, "XLA Modules", "jit__partial_fit_donated(123)", 0.95, 0.40),
        Event(DEV, "XLA Ops", FIT_OP, 0.95, 0.25),          # clipped to [1.0, 1.2]
        Event(DEV, "XLA Ops", "%copy.1 = f32[8]{0} copy(f32[8]{0} %x)", 1.1, 0.2),
        Event(DEV, "XLA Modules", "jit_other(9)", 1.45, 0.2),
        Event(DEV, "XLA Ops", FIT_OP, 1.5, 0.1),            # not in a fit program
        Event(HOST, "python", "bench.call", 1.3, 0.2),       # covers the gap 1.3-1.5
        Event(HOST, "main", "PJRT_Client_Compile", 1.6, 0.4),
        Event(HOST, "main", "TransferToDevice", 1.65, 0.15),  # partial: loses
        Event(DEV, "XLA Ops", FIT_OP, 2.5, 0.1),            # outside the window
    ]


def test_busy_is_the_union_of_ops_clipped_to_the_window():
    from bench.trace_reduce import Reduction

    red = Reduction(_events())
    assert red.window_s == pytest.approx(1.0)
    assert red.busy_intervals(0) == [pytest.approx((1.0, 1.3)), pytest.approx((1.5, 1.6))]
    assert red.busy_s(0) == pytest.approx(0.4)
    assert red.window_s - red.busy_s(0) == pytest.approx(0.6)


def test_kernel_time_by_pattern_and_program():
    from bench.trace_reduce import Reduction

    red = Reduction(_events())
    pat = r'custom_call_target="tpu_custom_call"'
    assert red.kernel_s(pat) == pytest.approx(0.3)
    assert red.kernel_s(pat, r"^jit__partial_fit") == pytest.approx(0.2)
    assert len(red.kernel_events(pat, r"^jit__partial_fit")) == 1
    assert len(red.kernel_events(pat, r"^jit_other")) == 1
    top = red.top_ops(3)
    assert top[0][0] == "jit__partial_fit_donated/_partial_fit_donated.1 [tpu_custom_call]"


def test_idle_gaps_go_to_the_most_specific_covering_host_span():
    from bench.trace_reduce import Reduction

    gaps = dict(Reduction(_events()).idle_gaps([0]))
    assert gaps == {"PJRT_Client_Compile": pytest.approx(0.4),
                    "bench.call": pytest.approx(0.2)}


def test_events_round_trip(tmp_path):
    from bench import trace_reduce

    path = tmp_path / "ev.json.gz"
    trace_reduce.save_events(_events(), path)
    assert trace_reduce.load_events(path) == _events()


def test_recorded_v5e_trace():
    from bench import harness
    from bench.trace_reduce import Reduction, load_events

    red = Reduction(load_events(RECORDED))
    assert red.devices() == [0] and red.window_s == pytest.approx(0.12)
    assert 0.9 * red.window_s < red.busy_s(0) <= red.window_s
    reader = harness.load_module(harness.metric_file(harness.BENCH, "fit.kernel_roofline"),
                                 "fit_roofline")
    kernel = red.kernel_s(reader.OP_PATTERN, reader.MODULE_PATTERN)
    assert 0.9 * red.busy_s(0) < kernel <= red.busy_s(0)
    assert len(red.kernel_events(reader.OP_PATTERN, reader.MODULE_PATTERN)) >= 3  # 23 ms a step
    assert red.top_ops(1)[0][0] == ("jit__partial_fit_donated/_partial_fit_donated.1"
                                    " [tpu_custom_call]")
    gaps = red.idle_gaps([0])
    assert sum(v for _, v in gaps) == pytest.approx(red.window_s - red.busy_s(0))
