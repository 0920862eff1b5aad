"""The operation and byte counts the roofline readers divide by, at the
cells' own shapes."""

import json

import pytest

from bench_tiny import ROOT


def _metric(name):
    from bench import harness

    return harness.load_module(harness.metric_file(harness.BENCH, name), f"m_{name}")


def _cfg(name):
    return json.loads((ROOT / f"bench/configs/{name}.json").read_text())


def test_fit_counts_at_60k_in_2048_steps():
    m = _metric("fit.kernel_roofline")
    cfg = _cfg("uhd_mnist_d8192")
    assert m.ops_per_step(cfg, 2048) == 2 * 784 * 8192 * 2048 == 26_306_674_688
    # inputs + labels + the int8 table once + class sums read and written
    want = 4 * 2048 * 784 + 4 * 2048 + 784 * 8192 + 2 * 4 * 10 * 8192
    assert m.bytes_per_step(cfg, 2048) == want == 13_508_608
    dyn = _cfg("uhd_dynamic_mnist_d8192")
    assert m.bytes_per_step(dyn, 2048) == 4 * 2048 * 784 + 4 * 2048 + 784 * 32 + 655_360


def test_fit_roofline_is_bound_by_operations_on_v5e():
    from bench import harness

    m = _metric("fit.kernel_roofline")
    peaks = harness.load_peaks("TPU v5 lite")
    cfg = _cfg("uhd_mnist_d8192")
    t_ops = m.ops_per_step(cfg, 2048) / peaks["int8_ops_per_s"]
    t_bytes = m.bytes_per_step(cfg, 2048) / peaks["hbm_bytes_per_s"]
    assert t_ops == pytest.approx(66.94e-6, rel=1e-3) and t_bytes < t_ops


def test_topk_counts_at_2p20_rows():
    m = _metric("search.topk_roofline")
    rows, words, b, k = 1 << 20, 8192 // 32, 64, 10
    assert m.bytes_per_call(b, rows, words, k) == 4 * (rows * words + b * words + 2 * b * k)
    assert m.bytes_per_call(b, rows, words, k) == 1_073_812_480  # the store once: 1 GiB
    assert m.ops_per_call(b, rows, words) == 64 * 2**20 * 256


def test_readers_give_nothing_without_a_trace():
    class Bare:
        reduction = None
        peaks = None
        spans = {}
        counters = {}
        work = {}
        n_chips = 1

    for name in ("fit.kernel_roofline", "fit_mfu", "fit.device_idle",
                 "search.topk_roofline", "search_mfu", "search.device_idle",
                 "http.write_p99_ms", "http.queue_p99_ms", "http.batch_fill",
                 "http.device_span_ms", "http.device_idle", "pool.dispatch_spread",
                 "pool.device_idle", "pool.queue_p99_ms", "pool.device_span_ms",
                 "pool.gc_share"):
        assert _metric(name).read(Bare()) is None, name


@pytest.mark.parametrize("blocks, spread", [
    ([40, 40, 40, 40], 1.0),
    ([70, 30, 30, 30], 70 / 40),
    ([0, 0, 0, 8], 4.0),
    ([5], 1.0),
    ([0, 0, 0, 0], None),
])
def test_pool_dispatch_spread_on_hand_made_counters(blocks, spread):
    class Run:
        counters = {"n_dispatched": blocks}

    got = _metric("pool.dispatch_spread").read(Run())
    assert got == (None if spread is None else pytest.approx(spread))


@pytest.mark.parametrize("gc_spans, share", [
    ([(0.10, 0.05), (0.50, 0.02)], 7.0),
    ([(-0.05, 0.10), (0.95, 0.10), (1.20, 0.10)], 10.0),  # clipped to the window
    ([(1.20, 0.10)], 0.0),  # collections, none inside the window
    ([], None),  # no hook, nothing to read
])
def test_pool_gc_share_on_hand_made_spans(gc_spans, share):
    from bench.trace_reduce import Event, Reduction

    events = [Event("/host:CPU", "python", "bench.window", 0.0, 1.0),
              Event("/host:CPU", "python", "hdc.batcher.assemble", 0.1, 0.3)]
    events += [Event("/host:CPU", "python", "python.gc", t, d) for t, d in gc_spans]

    class Run:
        reduction = Reduction(events)

    got = _metric("pool.gc_share").read(Run())
    assert got == (None if share is None else pytest.approx(share))


def test_fit_epoch_steps_come_from_the_traffic():
    m = _metric("fit.kernel_roofline")
    assert m.step_sizes(60_000, 2048) == [2048] * 29 + [608]
    assert m.step_sizes(4096, 2048) == [2048, 2048]


@pytest.mark.parametrize("kernels_per_step", [1, 2, 3])
def test_fit_roofline_does_not_depend_on_how_a_step_is_split(kernels_per_step):
    """The same device time over the same epochs reads the same share,
    whether each step runs one Pallas kernel or several."""
    from bench import harness
    from bench.trace_reduce import Event, Reduction

    m = _metric("fit.kernel_roofline")
    cfg, peaks = _cfg("uhd_mnist_d8192"), harness.load_peaks("TPU v5 lite")
    traffic = {"n_train": 2 * 2048 + 608, "batch": 2048}
    op = 'custom-call(s32[1]{0} %p), custom_call_target="tpu_custom_call"'
    events = [Event("/host:CPU", "python", "bench.window", 0.0, 1.0)]
    t, step_s = 0.01, 0.02
    for epoch in range(2):
        for _ in m.step_sizes(traffic["n_train"], traffic["batch"]):
            events.append(Event("/device:TPU:0", "XLA Modules", "jit__partial_fit_donated(7)",
                                t, step_s))
            part = step_s / kernels_per_step
            for j in range(kernels_per_step):
                events.append(Event("/device:TPU:0", "XLA Ops", f"%k{j} = {op}",
                                    t + j * part, part))
            t += step_s + 0.001

    class Run:
        reduction = Reduction(events)
        work = {"traced_images": 2 * traffic["n_train"]}

    Run.cfg, Run.traffic, Run.peaks = cfg, traffic, peaks
    least = 2 * m.least_s_per_epoch(cfg, traffic, peaks)
    assert m.read(Run) == pytest.approx(100 * least / (2 * 3 * step_s))


def test_topk_roofline_reads_its_calls_from_the_driver():
    from bench import harness
    from bench.trace_reduce import Event, Reduction

    m = _metric("search.topk_roofline")
    op = ("%topk.1 = (s32[64,10]{1,0}, s32[64,10]{1,0}) custom-call(u32[64,256]{1,0} %q, "
          'u32[1048576,256]{1,0} %r), custom_call_target="tpu_custom_call"')
    events = [Event("/host:CPU", "python", "bench.window", 0.0, 1.0)]
    for i in range(4):
        events.append(Event("/device:TPU:0", "XLA Modules", "jit_search_packed(3)",
                            0.1 + 0.2 * i, 0.06))
        events.append(Event("/device:TPU:0", "XLA Ops", op, 0.1 + 0.2 * i, 0.05))

    class Run:
        reduction = Reduction(events)
        peaks = harness.load_peaks("TPU v5 lite")
        cfg = _cfg("uhd_dynamic_mnist_d8192")
        traffic = {"batch": 64, "store_rows": 1 << 20, "k": 10}
        work = {"traced_calls": 4}

    least = m.bytes_per_call(64, 1 << 20, 256, 10) / Run.peaks["hbm_bytes_per_s"]
    assert m.read(Run) == pytest.approx(100 * least * 4 / 0.2)
