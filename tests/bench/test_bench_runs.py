"""Whole runs of every cell at a tiny size on the CPU: correct, with the
whole result line, the end-to-end metrics without a trace and the
per-layer metrics with one."""

import json

import pytest

from bench_tiny import ROOT, TINY, root_with_unlisted


@pytest.fixture(scope="module")
def bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("trace", [False, True], ids=["e2e", "traced"])
@pytest.mark.parametrize("workload", sorted(TINY))
def test_sound_run(run_tiny, bench, workload, trace, tmp_path):
    from bench import harness

    root = ROOT
    if workload not in {w["name"] for w in bench["workloads"]}:
        root = root_with_unlisted(tmp_path)
        bench = harness.load_benchmark(root)
    out = run_tiny(workload, trace=trace, root=root)
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(out)[-1] == "checks"
    assert out["correct"] is True and out["attempted"] > 0 and out["failed"] == 0
    assert all(c["value"] <= c["limit"] for c in out["checks"].values())
    cell = harness.find_cell(bench, workload)
    if trace:
        assert {"busy_s", "window_s"} <= set(out["device"])
        assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
        allowed = {m["name"] for m in harness.per_layer_metrics(bench, cell)}
        assert set(out["metrics"]) <= allowed
    else:
        want = {m["name"] for m in harness.e2e_metrics(bench, cell)}
        assert set(out["metrics"]) == want
        assert all(m["value"] > 0 for m in out["metrics"].values())
    json.dumps(out, allow_nan=False)


@pytest.mark.parametrize("trace", [False, True], ids=["e2e", "traced"])
@pytest.mark.parametrize("in_flight", [1, 8])
def test_search_window_reads_every_call_sent(run_tiny, monkeypatch, in_flight, trace):
    """Calls dispatched ahead are all waited for and counted, in order."""
    from repro.core import hdc_model

    from bench_tiny import TINY

    real, sent = hdc_model.search_packed, []

    class Counting:
        def __call__(self, *args, **kwargs):
            sent.append(1)
            return real(*args, **kwargs)

        def lower(self, *args, **kwargs):
            return real.lower(*args, **kwargs)

    monkeypatch.setattr(hdc_model, "search_packed", Counting())
    tiny = TINY["search_dyn_store1m"]
    overrides = {"config": tiny["config"],
                 "traffic": dict(tiny["traffic"], in_flight=in_flight)}
    out = run_tiny("search_dyn_store1m", trace=trace, overrides=overrides)
    assert out["correct"] is True
    batch = tiny["traffic"]["batch"]
    assert out["attempted"] == (len(sent) - 1) * batch  # less the warm-up call
