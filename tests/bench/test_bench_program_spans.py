"""The readers of the program's own names: `fit.host_step_ms` (the
``hdc.fit.step`` host spans) and `search.encode_ms` (the device ops whose
custom call carries ``hdc_kernel`` ``encode_bundle_dynamic``).  On
hand-made events with known answers, and on slices of traces recorded on
a TPU v5e with the spans and kernel names in place: 120 ms of
`fit_batches` (H=784, D=8192, 2048-image steps) and 150 ms of the
`search_packed` stream (64 queries, top-10 over 2^20 rows)."""

from pathlib import Path

import pytest

from bench_tiny import ROOT  # noqa: F401  (puts the repository on the path)

DEV, HOST = "/device:TPU:0", "/host:CPU"
FIT_RECORDED = Path(__file__).with_name("fit_spans_trace_v5e.json.gz")
SEARCH_RECORDED = Path(__file__).with_name("search_trace_v5e.json.gz")
ENCODE_OP = ('%encode_bundle_dynamic.1 = s32[64,8192]{1,0} custom-call(s32[64,896]{1,0} %p, '
             'u32[896,32]{1,0} %d), custom_call_target="tpu_custom_call", '
             'frontend_attributes={kernel_metadata={\n"hdc_kernel":"encode_bundle_dynamic"\n}}')
ENCODE_NO_NAME = ENCODE_OP.replace('\n"hdc_kernel":"encode_bundle_dynamic"\n', "")


def _metric(name):
    from bench import harness

    return harness.load_module(harness.metric_file(harness.BENCH, name), f"m_{name}")


def _run(events, **work):
    from bench.trace_reduce import Reduction

    class Run:
        reduction = Reduction(events)

    Run.work = work
    return Run


def _window(t0=0.0, dur=1.0):
    from bench.trace_reduce import Event

    return Event(HOST, "python", "bench.window", t0, dur)


def test_host_step_is_the_median_span_clipped_to_the_window():
    from bench.trace_reduce import Event

    steps = [(-0.05, 0.08), (0.1, 0.02), (0.2, 0.03), (0.3, 0.04), (0.95, 0.1)]
    events = [_window()] + [Event(HOST, "python", "hdc.fit.step", a, d) for a, d in steps]
    events.append(Event(HOST, "python", "hdc.fit.reset", 0.5, 0.5))  # another span
    events.append(Event(HOST, "python", "hdc.fit.step", 1.5, 0.2))   # outside
    # clipped: 0.03, 0.02, 0.03, 0.04, 0.05 -> median 0.03
    assert _metric("fit.host_step_ms").read(_run(events)) == pytest.approx(30.0)


def test_encode_time_per_call_from_the_named_kernel():
    from bench.trace_reduce import Event

    events = [_window()]
    for i in range(4):
        t = 0.1 + 0.2 * i
        events.append(Event(DEV, "XLA Modules", "jit_search_packed(3)", t, 0.06))
        events.append(Event(DEV, "XLA Ops", ENCODE_OP, t, 0.002))
        events.append(Event(DEV, "XLA Ops", ENCODE_NO_NAME, t + 0.002, 0.05))  # not it
    events.append(Event(DEV, "XLA Modules", "jit_other(4)", 0.9, 0.05))
    events.append(Event(DEV, "XLA Ops", ENCODE_OP, 0.9, 0.004))  # not in a search step
    assert _metric("search.encode_ms").read(_run(events, traced_calls=4)) == pytest.approx(2.0)


def test_the_name_pattern_tells_the_kernels_apart():
    import re

    m = _metric("search.encode_ms")
    assert re.search(m.OP_PATTERN, ENCODE_OP)
    assert not re.search(m.OP_PATTERN, ENCODE_OP.replace("encode_bundle_dynamic", "encode_bundle"))
    assert re.search(m.OP_PATTERN, ENCODE_OP.replace('"hdc_kernel":"', '\\"hdc_kernel\\": \\"'))


@pytest.mark.parametrize("name", ["fit.host_step_ms", "search.encode_ms"])
def test_readers_give_nothing_without_their_spans(name):
    from bench.trace_reduce import Event

    class Bare:
        reduction = None
        work = {"traced_calls": 4}

    m = _metric(name)
    assert m.read(Bare()) is None
    # a trace of the parent program: no spans, no kernel names
    events = [_window(), Event(HOST, "python", "PjitFunction(_partial_fit_donated)", 0.1, 0.2),
              Event(DEV, "XLA Modules", "jit_search_packed(3)", 0.1, 0.06),
              Event(DEV, "XLA Ops", ENCODE_NO_NAME, 0.1, 0.002)]
    assert m.read(_run(events, traced_calls=1)) is None


def test_recorded_fit_trace_with_spans():
    from bench.trace_reduce import Reduction, load_events

    events = load_events(FIT_RECORDED)
    red = Reduction(events)
    assert red.devices() == [0] and red.window_s == pytest.approx(0.12)
    steps = [e for e in red.host if e.name == "hdc.fit.step"]
    assert len(steps) >= 4  # 23 ms a step
    value = _metric("fit.host_step_ms").read(_run(events))
    assert 0.5 < value < 30.0
    # the named kernel still matches the pattern the roofline reader keys on
    roof = _metric("fit.kernel_roofline")
    kernels = red.kernel_events(roof.OP_PATTERN, roof.MODULE_PATTERN)
    assert len(kernels) >= 3
    assert all('"hdc_kernel":"fit_bundle"' in e.name for _, e in kernels)


def test_recorded_search_trace_with_kernel_names():
    from bench.trace_reduce import Reduction, load_events

    events = load_events(SEARCH_RECORDED)
    red = Reduction(events)
    enc = _metric("search.encode_ms")
    calls = len(red.kernel_events(enc.OP_PATTERN, enc.MODULE_PATTERN))
    assert calls >= 2  # about 49 ms a call
    value = enc.read(_run(events, traced_calls=calls))
    assert 1.0 < value < 3.0  # the query encode, about 1.6 ms a call
    assert value * calls == pytest.approx(1e3 * red.kernel_s(enc.OP_PATTERN, enc.MODULE_PATTERN))
    # the named top-k kernel still matches the roofline reader's anchored pattern
    topk = _metric("search.topk_roofline")
    scans = red.kernel_events(topk.OP_PATTERN, topk.MODULE_PATTERN)
    assert scans and all('"hdc_kernel":"hamming_topk"' in e.name for _, e in scans)
