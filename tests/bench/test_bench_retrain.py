"""The retraining cell, `retrain_dyn_mnist60k`: whole runs at a tiny size
on the CPU (correct, its control and two planted faults not), the
refusal of a program without `HDCModel.retrain_batches`, its data
generator, its five per-layer readers on hand-made events with known
answers, and the operation and byte counts they divide by."""

import json

import numpy as np
import pytest

from bench_tiny import ROOT, TINY

CELL = "retrain_dyn_mnist60k"
DEV, HOST = "/device:TPU:0", "/host:CPU"
ENCODE_OP = ('%encode_bundle_dynamic.1 = s32[2048,8192]{1,0} custom-call(s32[2048,896]{1,0} '
             '%p, u32[896,32]{1,0} %d), custom_call_target="tpu_custom_call", '
             'frontend_attributes={kernel_metadata={\n"hdc_kernel":"encode_bundle_dynamic"\n}}')
TOPK_OP = ('%hamming_topk.1 = (s32[2048,1]{1,0}, s32[2048,1]{1,0}) custom-call(), '
           'custom_call_target="tpu_custom_call", '
           'frontend_attributes={kernel_metadata={\n"hdc_kernel":"hamming_topk"\n}}')


def _metric(name):
    from bench import harness

    return harness.load_module(harness.metric_file(harness.BENCH, name), f"m_{name}")


def _cfg():
    return json.loads((ROOT / "bench/configs/uhd_dynamic_retrain_mnist_d8192.json").read_text())


def _traffic():
    return json.loads((ROOT / "bench/traffic/retrain_60k_b2048.json").read_text())


# ---------------------------------------------------------------------------
# whole runs at the tiny size
# ---------------------------------------------------------------------------


def test_traced_tiny_run_is_correct_and_reads_the_program(run_tiny):
    out = run_tiny(CELL, trace=True)
    assert out["correct"], out["checks"]
    assert set(out["checks"]) == {"class_sum_entries_differing",
                                  "step_wrong_counts_differing", "examples_miscounted"}
    metrics = out["metrics"]
    assert metrics["retrain.host_step_ms"]["value"] > 0
    assert 0 < metrics["retrain.wrong_share"]["value"] < 100
    # no TPU plane on the CPU: the kernel readers find nothing to read
    assert "retrain.encode_roofline" not in metrics


def test_control_misses_the_sums_and_the_counts(run_tiny):
    out = run_tiny(CELL, control=True)
    assert not out["correct"]
    checks = out["checks"]
    assert checks["class_sum_entries_differing"]["value"] > 0
    assert checks["step_wrong_counts_differing"]["value"] > 0
    assert checks["examples_miscounted"]["value"] == 0


def test_a_step_that_folds_nothing_is_caught(run_tiny, monkeypatch):
    import jax
    import jax.numpy as jnp

    from repro.core import hdc_model

    real = hdc_model._retrain_step_donated

    @jax.jit
    def lazy(stateless, sums, words, images, labels):
        _, wrong = real(stateless, jnp.copy(sums), words, images, labels)
        return sums, wrong

    monkeypatch.setattr(hdc_model, "_retrain_step_donated", lazy)
    out = run_tiny(CELL)
    assert not out["correct"]
    assert out["checks"]["class_sum_entries_differing"]["value"] > 0


def test_a_step_that_miscounts_is_caught(run_tiny, monkeypatch):
    import jax

    from repro.core import hdc_model

    real = hdc_model._retrain_step_donated

    @jax.jit
    def off_by_one(stateless, sums, words, images, labels):
        new, wrong = real(stateless, sums, words, images, labels)
        return new, wrong + 1

    monkeypatch.setattr(hdc_model, "_retrain_step_donated", off_by_one)
    out = run_tiny(CELL)
    assert not out["correct"]
    assert out["checks"]["class_sum_entries_differing"]["value"] == 0
    steps = -(-TINY[CELL]["traffic"]["n_train"] // TINY[CELL]["traffic"]["batch"])
    assert out["checks"]["step_wrong_counts_differing"]["value"] == steps * out["attempted"] \
        // TINY[CELL]["traffic"]["n_train"]


def test_a_program_without_retraining_is_refused_before_any_work(monkeypatch):
    from bench import harness
    from repro.core import HDCModel

    import tiny_cell

    monkeypatch.setattr(harness, "enable_compile_cache", lambda root: "off")
    monkeypatch.delattr(HDCModel, "retrain_batches")
    with pytest.raises(harness.Refused, match="retrain_batches"):
        tiny_cell.run(CELL, seed=3, overrides=TINY[CELL])


# ---------------------------------------------------------------------------
# the data generator
# ---------------------------------------------------------------------------


def test_class_conditioned_images_come_from_the_seed():
    from bench import harness

    drv = harness.load_module(harness.driver_file(harness.BENCH, "retrain"), "d_retrain")
    seed = 2**40 + 7
    x, y = drv.class_conditioned(seed, 500, 64, 10, 384.0)
    x2, y2 = drv.class_conditioned(seed, 500, 64, 10, 384.0)
    x3, _ = drv.class_conditioned(seed + 1, 500, 64, 10, 384.0)
    x, y = np.asarray(x), np.asarray(y)
    np.testing.assert_array_equal(x, np.asarray(x2))
    np.testing.assert_array_equal(y, np.asarray(y2))
    assert not np.array_equal(x, np.asarray(x3))
    assert x.dtype == np.float32 and x.shape == (500, 64)
    assert (x == np.round(x)).all() and x.min() >= 0 and x.max() <= 255
    assert set(np.unique(y)) <= set(range(10))
    # images of one class lie nearer their own class mean than another's
    means = np.stack([x[y == k].mean(0) for k in range(10)])
    own = np.abs(x - means[y]).mean()
    other = np.abs(x - means[(y + 1) % 10]).mean()
    assert own < other
    # no noise: every image of a class is its prototype
    xq, yq = (np.asarray(a) for a in drv.class_conditioned(seed, 200, 64, 10, 0.0))
    for k in np.unique(yq):
        assert (xq[yq == k] == xq[yq == k][0]).all()


# ---------------------------------------------------------------------------
# the readers, on hand-made events
# ---------------------------------------------------------------------------


def _run(events, *, cfg=None, traffic=None, peaks=True, **work):
    from bench import harness
    from bench.trace_reduce import Reduction

    class Run:
        reduction = Reduction(events)
        counters = {}
        n_chips = 1

    Run.work = work
    Run.cfg = cfg or _cfg()
    Run.traffic = traffic or _traffic()
    Run.peaks = harness.load_peaks("TPU v5 lite") if peaks else None
    return Run


def _window(dur=1.0):
    from bench.trace_reduce import Event

    return Event(HOST, "python", "bench.window", 0.0, dur)


def test_host_step_is_the_median_retrain_span_clipped_to_the_window():
    from bench.trace_reduce import Event

    steps = [(-0.05, 0.07), (0.1, 0.01), (0.2, 0.03), (0.3, 0.04), (0.97, 0.1)]
    events = [_window()] + [Event(HOST, "python", "hdc.retrain.step", a, d) for a, d in steps]
    events.append(Event(HOST, "python", "hdc.retrain.reset", 0.5, 0.4))  # another span
    events.append(Event(HOST, "python", "hdc.fit.step", 0.6, 0.3))  # the fit's own
    # clipped: 0.02, 0.01, 0.03, 0.04, 0.03 -> median 0.03
    assert _metric("retrain.host_step_ms").read(_run(events)) == pytest.approx(30.0)


def _epochs_events(traffic, *, encode_s, other_s, gap_s, epochs=2):
    """Device events of whole epochs: per step a retrain program holding
    the encode kernel and a top-k op, then an idle gap."""
    from bench.trace_reduce import Event

    m = _metric("retrain.encode_roofline")
    events, t = [], 0.01
    for _ in range(epochs):
        for _ in m.step_sizes(traffic["n_train"], traffic["batch"]):
            events.append(Event(DEV, "XLA Modules", "jit__retrain_step_donated(5)",
                                t, encode_s + other_s))
            events.append(Event(DEV, "XLA Ops", ENCODE_OP, t, encode_s))
            events.append(Event(DEV, "XLA Ops", TOPK_OP, t + encode_s, other_s))
            t += encode_s + other_s + gap_s
    events.append(Event(DEV, "XLA Modules", "jit_search_packed(3)", t, 0.01))
    events.append(Event(DEV, "XLA Ops", ENCODE_OP, t, 0.01))  # not in a retrain step
    return [_window(t + 0.02)] + events, t + 0.02


def test_encode_roofline_from_the_named_kernel_in_retrain_steps():
    traffic = {"n_train": 2 * 2048 + 608, "batch": 2048}
    events, _ = _epochs_events(traffic, encode_s=0.02, other_s=0.002, gap_s=0.001)
    run = _run(events, traffic=traffic, traced_images=2 * traffic["n_train"])
    m = _metric("retrain.encode_roofline")
    least = 2 * m.least_s_per_epoch(run.cfg, traffic, run.peaks)
    assert m.read(run) == pytest.approx(100 * least / (2 * 3 * 0.02))


def test_mfu_and_idle_over_the_traced_window():
    traffic = {"n_train": 2 * 2048 + 608, "batch": 2048}
    events, window_s = _epochs_events(traffic, encode_s=0.02, other_s=0.002, gap_s=0.001)
    run = _run(events, traffic=traffic, traced_images=2 * traffic["n_train"])
    mfu = _metric("retrain_mfu")
    ops = mfu.ops_per_step(run.cfg, 2 * traffic["n_train"])
    assert mfu.read(run) == pytest.approx(100 * ops / window_s / 393e12)
    busy = 6 * 0.022 + 0.01
    assert _metric("retrain.device_idle").read(run) == pytest.approx(
        100 * (1 - busy / window_s))


def test_wrong_share_sums_the_programs_step_counts():
    class Run:
        counters = {"step_mispredicts": [np.array([3, 1, 0]), np.array([3, 1, 0])]}
        work = {"images": 2 * 200}

    assert _metric("retrain.wrong_share").read(Run) == pytest.approx(100 * 8 / 400)
    Run.counters = {"step_mispredicts": [np.array([0, 0, 0])]}
    Run.work = {"images": 200}
    assert _metric("retrain.wrong_share").read(Run) == 0.0


@pytest.mark.parametrize("name", ["retrain.host_step_ms", "retrain.device_idle",
                                  "retrain.encode_roofline", "retrain_mfu",
                                  "retrain.wrong_share"])
def test_readers_give_nothing_without_their_sources(name):
    from bench.trace_reduce import Event

    class Bare:
        reduction = None
        peaks = None
        counters = {}
        work = {}
        n_chips = 1

    m = _metric(name)
    assert m.read(Bare()) is None
    if name in ("retrain.device_idle", "retrain_mfu", "retrain.wrong_share"):
        return  # read the window, the images or the counters, not the program's names
    # a trace of the parent program: no retrain spans, no retrain programs
    events = [_window(), Event(HOST, "python", "hdc.fit.step", 0.1, 0.2),
              Event(DEV, "XLA Modules", "jit__partial_fit_donated(3)", 0.1, 0.06),
              Event(DEV, "XLA Ops", ENCODE_OP, 0.1, 0.002)]
    assert m.read(_run(events, traced_images=4096)) is None


# ---------------------------------------------------------------------------
# the counts
# ---------------------------------------------------------------------------


def test_step_sizes_of_an_epoch_come_from_the_traffic():
    m = _metric("retrain.encode_roofline")
    t = _traffic()
    assert m.step_sizes(t["n_train"], t["batch"]) == [2048] * 29 + [608]


def test_encode_counts_at_the_cells_shapes():
    m = _metric("retrain.encode_roofline")
    cfg = _cfg()
    assert m.encode_ops_per_step(cfg, 2048) == 2 * 2048 * 784 * 8192 == 26_306_674_688
    # quantized inputs + the (H, 32) direction matrix once + the hypervectors written
    want = 4 * 2048 * 784 + 32 * 784 + 4 * 2048 * 8192
    assert m.encode_bytes_per_step(cfg, 2048) == want == 73_556_480
    assert m.encode_ops_per_step(cfg, 608) == 2 * 608 * 784 * 8192


def test_encode_roofline_is_bound_by_the_hypervectors_written_on_v5e():
    from bench import harness

    m = _metric("retrain.encode_roofline")
    peaks, cfg = harness.load_peaks("TPU v5 lite"), _cfg()
    t_ops = m.encode_ops_per_step(cfg, 2048) / peaks["int8_ops_per_s"]
    t_bytes = m.encode_bytes_per_step(cfg, 2048) / peaks["hbm_bytes_per_s"]
    assert t_ops == pytest.approx(66.94e-6, rel=1e-3)
    assert t_bytes == pytest.approx(89.82e-6, rel=1e-3) and t_bytes > t_ops
    least = m.least_s_per_epoch(cfg, _traffic(), peaks)
    assert least == pytest.approx(60_000 / 2048 * t_bytes, rel=1e-3)


def test_whole_step_counts_at_the_cells_shapes():
    m = _metric("retrain_mfu")
    cfg = _cfg()
    encode = 2 * 2048 * 784 * 8192
    distance = 2 * 2048 * 10 * (8192 // 32)
    update = 2 * 10 * 2048 * 8192
    assert m.ops_per_step(cfg, 2048) == encode + distance + update == 26_652_704_768
    assert m.ops_per_step(cfg, 60_000) == pytest.approx(60_000 / 2048 * m.ops_per_step(cfg, 2048))
