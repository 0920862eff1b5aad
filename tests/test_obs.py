"""repro.obs: histograms, traces, Prometheus exposition, perf gate.

The acceptance contract (ISSUE 7): `GET /metrics` negotiates valid
Prometheus text exposition while the JSON form stays backward-compatible
and strict-valid (no NaN); every HTTP request leaves a trace whose
queue/assembly/device/write spans sum to at most the end-to-end
latency; and `check_regression` demonstrably fails on a synthetic
regressed artifact.
"""

import json
import os
import subprocess
import sys
import threading
import time
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import HDCConfig, HDCModel
from repro.obs import (
    LatencyHistogram,
    RequestTrace,
    TraceBuffer,
    new_request_id,
    render_prometheus,
    span,
)
from repro.obs.histogram import log_bounds
from repro.serving import MicroBatcher, ModelRegistry, ServingEngine
from repro.serving.metrics import ServingMetrics
from repro.transport import HdcClient, HdcHttpServer, TransportError

RNG = np.random.default_rng(71)
REPO = Path(__file__).resolve().parent.parent


def _cfg(**kw):
    base = dict(n_features=24, n_classes=4, d=128, levels=16,
                similarity="hamming")
    base.update(kw)
    return HDCConfig(**base)


def _trained(cfg, n=32):
    x = jnp.asarray(RNG.uniform(0, 255, (n, cfg.n_features)), jnp.float32)
    y = jnp.asarray(RNG.integers(0, cfg.n_classes, (n,)), jnp.int32)
    return HDCModel.create(cfg).fit(x, y)


@pytest.fixture
def stack(request):
    """(registry, server, client) around one registered model; torn down
    server-first (the production stop order)."""
    registries, servers, clients = [], [], []

    def build(model, name="m", *, batch_size=8, start=True, **server_kw):
        registry = ModelRegistry()
        registry.register(name, ServingEngine(model, batch_size=batch_size),
                          start=start, max_delay_ms=1.0)
        server = HdcHttpServer(registry, **server_kw).start()
        client = HdcClient(*server.address)
        registries.append(registry)
        servers.append(server)
        clients.append(client)
        return registry, server, client

    yield build
    for client in clients:
        client.close()
    for server in servers:
        server.stop()
    for registry in registries:
        registry.shutdown()


# ---------------------------------------------------------------------------
# histograms: exact counts, merge = union, percentile accuracy
# ---------------------------------------------------------------------------


def test_histogram_exact_counts_and_bounds():
    h = LatencyHistogram()
    values = RNG.uniform(1e-5, 1.0, 500)
    for v in values:
        h.observe(v)
    assert h.count == 500
    assert h.sum_s == pytest.approx(values.sum())
    assert sum(h.bucket_counts()) == 500
    snap = h.snapshot()
    assert snap["count"] == 500
    assert snap["min_ms"] == pytest.approx(values.min() * 1e3)
    assert snap["max_ms"] == pytest.approx(values.max() * 1e3)
    # negative observations clamp to zero instead of corrupting a bucket
    h.observe(-1.0)
    assert h.count == 501 and h.bucket_counts()[0] >= 1


def test_histogram_empty_is_none_never_nan():
    h = LatencyHistogram()
    snap = h.snapshot()
    for key in ("mean_ms", "min_ms", "max_ms", "p50_ms", "p90_ms", "p99_ms"):
        assert snap[key] is None, key
    assert h.percentile(50.0) is None
    # strict JSON by construction
    assert json.loads(json.dumps(snap, allow_nan=False)) == snap


def test_histogram_percentiles_track_numpy_within_bucket_width():
    # relative bucket width is 10^(1/16) - 1 ~ 15.5%; with min/max
    # clamping and interpolation the estimate must stay within one
    # bucket's relative width of the exact numpy percentile
    values = RNG.lognormal(mean=-5.0, sigma=1.0, size=4000)
    h = LatencyHistogram()
    for v in values:
        h.observe(v)
    growth = 10 ** (1 / 16)
    for p in (1, 25, 50, 90, 99):
        exact = float(np.percentile(values, p))
        est = h.percentile(p)
        assert exact / growth <= est <= exact * growth, (p, exact, est)
    # estimates never leave the observed range
    assert h.percentile(0) >= values.min()
    assert h.percentile(100) == pytest.approx(values.max())


def test_histogram_merge_equals_union():
    a, b, union = LatencyHistogram(), LatencyHistogram(), LatencyHistogram()
    va = RNG.uniform(1e-4, 0.05, 300)
    vb = RNG.uniform(0.01, 2.0, 200)
    for v in va:
        a.observe(v)
        union.observe(v)
    for v in vb:
        b.observe(v)
        union.observe(v)
    m = a.merge(b)
    assert m.count == 500
    assert m.sum_s == pytest.approx(union.sum_s)
    assert m.bucket_counts() == union.bucket_counts()
    # the satellite pin: merged percentiles == percentiles of the
    # concatenated observation stream's histogram, exactly
    for p in (50, 90, 99):
        assert m.percentile(p) == union.percentile(p), p
    with pytest.raises(ValueError, match="different bucket bounds"):
        a.merge(LatencyHistogram(log_bounds(1e-3, 1.0, 4)))


def test_histogram_cumulative_is_prometheus_series():
    h = LatencyHistogram()
    for v in (1e-5, 1e-3, 0.1, 100.0):  # 100s overflows the 64s top edge
        h.observe(v)
    series = h.cumulative()
    bounds = [b for b, _ in series]
    cums = [c for _, c in series]
    assert bounds[-1] == np.inf and cums[-1] == 4
    assert all(x <= y for x, y in zip(cums, cums[1:]))  # monotone
    assert cums[-2] == 3  # the 100s observation only lands in +Inf


def test_metrics_thread_hammer_exact_totals():
    """Satellite pin: N threads hammering one ServingMetrics lose no
    observation — counter totals and histogram mass are exact."""
    m = ServingMetrics()
    n_threads, per_thread = 8, 500

    def hammer(tid):
        for i in range(per_thread):
            m.enqueued()
            m.observe_batch(1, 2)
            m.observe_request(1e-4 * (tid + 1))
            m.observe_stage("device", 1e-5)

    threads = [threading.Thread(target=hammer, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    total = n_threads * per_thread
    snap = m.snapshot()
    assert snap["n_requests"] == total
    assert snap["n_batches"] == total
    assert snap["queue_depth"] == 0
    assert m.latency.count == total
    assert m.stage["device"].count == total
    assert m.latency.sum_s == pytest.approx(
        per_thread * 1e-4 * sum(range(1, n_threads + 1))
    )


def test_metrics_merge_combines_counters_and_histograms():
    a, b = ServingMetrics(), ServingMetrics()
    for v in (0.001, 0.002):
        a.observe_request(v)
    b.observe_request(0.004)
    a.shed(2)
    b.observe_batch(3, 4)
    m = a.merge(b)
    snap = m.snapshot()
    assert snap["n_requests"] == 3 and snap["n_shed"] == 2
    assert snap["n_batches"] == 1
    assert m.latency.count == 3
    assert m.latency.sum_s == pytest.approx(0.007)


# ---------------------------------------------------------------------------
# traces: span model + ring behavior
# ---------------------------------------------------------------------------


def test_request_ids_are_unique():
    ids = {new_request_id() for _ in range(1000)}
    assert len(ids) == 1000


def test_trace_finalize_spans_sum_to_e2e():
    t = RequestTrace("r1", model="m")
    base = t.t_submit
    t.t_dequeue = base + 0.010
    t.t_device_start = base + 0.012
    t.t_device_end = base + 0.020
    t.t_resolve = base + 0.021
    t.t_write_start = base + 0.022
    t.t_write_end = base + 0.025
    entry = t.finalize()
    spans = entry["spans"]
    assert spans["queue_ms"] == pytest.approx(10.0)
    assert spans["assembly_ms"] == pytest.approx(2.0)
    assert spans["device_ms"] == pytest.approx(8.0)
    assert spans["write_ms"] == pytest.approx(3.0)
    assert sum(spans.values()) <= entry["e2e_ms"] + 1e-9
    assert t.finalize() is None  # idempotent: first call wins


def test_trace_finalize_collapses_missing_marks():
    t = RequestTrace("r2")
    entry = t.finalize(error=True)
    assert entry["error"] is True
    assert all(v == 0.0 for v in entry["spans"].values())
    assert entry["e2e_ms"] == 0.0


def test_trace_buffer_events_survive_request_floods():
    buf = TraceBuffer(capacity=8, event_capacity=4)
    buf.record_event("promotion", model="m", step=1)
    for i in range(100):
        buf.append(RequestTrace(f"r{i}").finalize())
    entries = buf.snapshot()
    assert [e for e in entries if e["kind"] == "event"]  # not evicted
    assert len([e for e in entries if e["kind"] == "request"]) == 8
    # filters + last-n
    assert len(buf.snapshot(3, kind="request")) == 3
    assert buf.snapshot(kind="event")[0]["event"] == "promotion"
    # seq preserves global append order across the two rings
    seqs = [e["seq"] for e in entries]
    assert seqs == sorted(seqs)


def test_trace_buffer_jsonl_export(tmp_path):
    live = tmp_path / "live.jsonl"
    buf = TraceBuffer(capacity=16, jsonl_path=live, jsonl_sample=2)
    for i in range(10):
        buf.append(RequestTrace(f"r{i}").finalize())
    buf.close()
    lines = [json.loads(l) for l in live.read_text().splitlines()]
    assert len(lines) == 5  # every 2nd entry sampled
    out = tmp_path / "export.jsonl"
    assert buf.export_jsonl(out) == 10
    assert len(out.read_text().splitlines()) == 10


def test_direct_batcher_traces_without_transport():
    """Direct `submit` callers get batcher-owned traces: finalized at
    resolve time with a zero write span."""
    cfg = _cfg()
    engine = ServingEngine(_trained(cfg), batch_size=4)
    traces = TraceBuffer(64)
    batcher = MicroBatcher(engine, name="m", traces=traces)
    futs = [batcher.submit(img)
            for img in RNG.uniform(0, 255, (6, cfg.n_features))]
    batcher.flush()
    for f in futs:
        f.result(timeout=10.0)
    entries = traces.snapshot(kind="request")
    assert len(entries) == 6
    assert len({e["id"] for e in entries}) == 6
    for e in entries:
        assert e["model"] == "m" and e["step"] is None  # no checkpoint step
        assert e["spans"]["write_ms"] == 0.0
        assert sum(e["spans"].values()) <= e["e2e_ms"] + 1e-6
    # per-stage histograms fed from the same marks
    snap = batcher.metrics.snapshot()
    assert snap["stages"]["queue"]["count"] == 6
    assert snap["stages"]["device"]["count"] == 6


def _traced(tmp_path, work):
    """Events of a CPU `jax.profiler` trace around `work()`:
    name -> [((plane, line index), start ns, end ns)]."""
    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        work()
    finally:
        jax.profiler.stop_trace()
    events = {}
    for path in tmp_path.rglob("*.xplane.pb"):
        for plane in ProfileData.from_file(str(path)).planes:
            # a line per thread; threads may share a line name
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    events.setdefault(e.name, []).append(
                        ((plane.name, i), e.start_ns, e.end_ns))
    return events


def test_span_measures_and_lands_on_the_profiler_clock(tmp_path):
    """`obs.span` keeps its wall time and writes a trace event of the
    same name; the serving path's spans appear, and the engine's
    host-to-device copy and result read nest inside its step."""
    cfg = _cfg()
    batcher = MicroBatcher(ServingEngine(_trained(cfg), batch_size=4))
    out = {}

    def work():
        with span("hdc.test.block") as sp:
            time.sleep(0.01)
        out["elapsed"] = sp.elapsed_s
        futs = [batcher.submit(img)
                for img in RNG.uniform(0, 255, (3, cfg.n_features))]
        batcher.step()
        out["labels"] = [f.result(timeout=10.0) for f in futs]

    events = _traced(tmp_path, work)
    assert out["elapsed"] >= 0.01 and len(out["labels"]) == 3
    (_, t0, t1), = events["hdc.test.block"]
    assert (t1 - t0) * 1e-9 == pytest.approx(out["elapsed"], abs=2e-3)
    (line, s0, s1), = events["hdc.engine.step"]
    for inner in ("hdc.engine.put", "hdc.engine.read"):
        (iline, a, b), = events[inner]
        assert iline == line and s0 <= a <= b <= s1, inner
    assert len(events["hdc.batcher.assemble"]) == 1


def test_span_names_every_layer_of_a_served_request(stack, tmp_path):
    """Through HTTP: decode and write on the loop thread, wait and
    assemble on the drain thread, the engine step between them; and a
    forced collection under the running server is a `python.gc` span."""
    import gc

    cfg = _cfg()
    registry, server, client = stack(_trained(cfg))
    image = RNG.uniform(0, 255, cfg.n_features)

    def work():
        for _ in range(2):  # the drain thread's wait between them is traced whole
            client.predict("m", image)
        gc.collect()

    events = _traced(tmp_path, work)
    for name in ("hdc.http.decode", "hdc.batcher.wait", "hdc.batcher.assemble",
                 "hdc.engine.step", "hdc.engine.put", "hdc.engine.read",
                 "hdc.http.write", "python.gc"):
        assert name in events, name
    (decode_line, _, decoded), _ = events["hdc.http.decode"]
    (write_line, written, _), _ = events["hdc.http.write"]
    (step_line, stepped, _), _ = events["hdc.engine.step"]
    assert decode_line == write_line != step_line
    assert decoded <= stepped <= written


def test_gc_span_hook_lives_while_a_server_runs(stack):
    import gc

    from repro.obs import profiler

    hook = profiler._gc_span
    assert hook not in gc.callbacks
    _, server, _ = stack(_trained(_cfg()))
    _, other, _ = stack(_trained(_cfg()))
    assert gc.callbacks.count(hook) == 1
    server.stop()
    assert gc.callbacks.count(hook) == 1  # the other server still runs
    other.stop()
    other.stop()  # idempotent: no second removal
    assert hook not in gc.callbacks


# ---------------------------------------------------------------------------
# strict JSON + Prometheus over HTTP
# ---------------------------------------------------------------------------


def _strict_loads(payload: bytes):
    def refuse(token):
        raise AssertionError(f"non-strict JSON token {token!r} in payload")

    return json.loads(payload, parse_constant=refuse)


def test_fresh_server_metrics_and_health_are_strict_json(stack):
    """Satellite pin: a traffic-free server's /metrics and /healthz are
    valid strict JSON — the old reservoir emitted literal NaN."""
    cfg = _cfg()
    registry, server, client = stack(_trained(cfg))
    host, port = server.address
    import http.client as hc

    for route in ("/metrics", "/healthz"):
        conn = hc.HTTPConnection(host, port, timeout=10.0)
        try:
            conn.request("GET", route)
            resp = conn.getresponse()
            payload = resp.read()
        finally:
            conn.close()
        assert resp.status == 200
        obj = _strict_loads(payload)  # raises on NaN/Infinity
        assert obj == json.loads(json.dumps(obj, allow_nan=False))
    snap = client.metrics()["m"]
    assert snap["n_requests"] == 0 and snap["p99_ms"] is None


def _parse_prometheus(text: str):
    """-> (types, samples): family types and [(name, labels, value)]."""
    types, samples = {}, []
    for line in text.strip().splitlines():
        if line.startswith("# TYPE "):
            _, _, fam, mtype = line.split(None, 3)
            assert fam not in types, f"duplicate TYPE for {fam}"
            types[fam] = mtype
            continue
        if line.startswith("#"):
            continue
        metric, value = line.rsplit(None, 1)
        name, _, rest = metric.partition("{")
        labels = {}
        if rest:
            for pair in rest.rstrip("}").split('",'):
                k, _, v = pair.partition("=")
                labels[k.strip()] = v.strip('"')
        samples.append((name, labels, value))
    return types, samples


def test_prometheus_exposition_over_http(stack):
    cfg = _cfg()
    registry, server, client = stack(_trained(cfg))
    q = RNG.uniform(0, 255, (9, cfg.n_features)).astype(np.float32)
    client.predict_batch("m", q)
    # the write span is observed after the response bytes are flushed;
    # wait for it so the scrape below sees all four stages populated
    deadline = time.time() + 5.0
    while time.time() < deadline:
        if client.metrics()["m"]["stages"]["write"]["count"] >= 9:
            break
        time.sleep(0.01)
    text = client.metrics(prometheus=True)
    assert isinstance(text, str) and text.endswith("\n")
    types, samples = _parse_prometheus(text)
    assert types["uhd_requests_total"] == "counter"
    assert types["uhd_request_latency_seconds"] == "histogram"
    by_name = {}
    for name, labels, value in samples:
        by_name.setdefault(name, []).append((labels, value))
    # counter value matches the JSON snapshot
    [(labels, value)] = by_name["uhd_requests_total"]
    assert labels == {"model": "m"} and int(value) == 9
    # histogram: cumulative buckets are monotone, end at +Inf == _count
    buckets = [
        (l["le"], int(v))
        for l, v in by_name["uhd_request_latency_seconds_bucket"]
    ]
    cums = [c for _, c in buckets]
    assert all(x <= y for x, y in zip(cums, cums[1:]))
    assert buckets[-1][0] == "+Inf" and buckets[-1][1] == 9
    [(_, count)] = by_name["uhd_request_latency_seconds_count"]
    assert int(count) == 9
    # per-stage series carry the stage label
    stage_labels = {
        l["stage"] for l, _ in by_name["uhd_stage_latency_seconds_bucket"]
    }
    assert stage_labels >= {"queue", "assembly", "device", "write"}
    # JSON default is untouched by the negotiation
    assert client.metrics()["m"]["n_requests"] == 9


def test_traces_over_http_span_invariants(stack):
    cfg = _cfg()
    registry, server, client = stack(_trained(cfg))
    q = RNG.uniform(0, 255, (12, cfg.n_features)).astype(np.float32)
    client.predict_batch("m", q)
    client.predict("m", q[0])
    # transport-owned traces land in the ring after the response flush
    deadline = time.time() + 5.0
    while time.time() < deadline:
        entries = client.traces(kind="request")
        if len(entries) >= 13:
            break
        time.sleep(0.01)
    assert len(entries) == 13
    assert len({e["id"] for e in entries}) == 13
    for e in entries:
        assert e["model"] == "m" and e["error"] is False
        spans = e["spans"]
        assert set(spans) == {"queue_ms", "assembly_ms", "device_ms",
                              "write_ms"}
        assert all(v >= 0.0 for v in spans.values()), spans
        assert spans["write_ms"] > 0.0  # transport owns the flush
        assert sum(spans.values()) <= e["e2e_ms"] + 1e-6, e
    # filters
    assert client.traces(n=5, kind="request") == entries[-5:]
    assert client.traces(model="nope") == []
    with pytest.raises(TransportError) as err:
        client.traces(kind="bogus")
    assert err.value.status == 400


# ---------------------------------------------------------------------------
# profile capture route
# ---------------------------------------------------------------------------


def test_profile_route_forbidden_by_default(stack):
    cfg = _cfg()
    registry, server, client = stack(_trained(cfg))
    status, _, payload = client._request("POST", "/v1/debug/profile?ms=5")
    assert status == 403
    assert "disabled" in json.loads(payload)["error"]


def test_profile_route_capture(stack, tmp_path, monkeypatch):
    cfg = _cfg()
    from repro.obs import profiler as profiler_mod

    captured = {}

    def fake_capture(out_dir, ms):
        captured["dir"], captured["ms"] = out_dir, ms
        return str(out_dir)

    monkeypatch.setattr(profiler_mod, "profile_capture", fake_capture)
    registry, server, client = stack(
        _trained(cfg), enable_profiling=True, profile_dir=str(tmp_path)
    )
    out = client._json("POST", "/v1/debug/profile?ms=7")
    assert out["ms"] == 7.0
    assert captured["ms"] == 7.0
    assert captured["dir"].startswith(str(tmp_path))
    # bad / out-of-range windows are 400
    for q in ("ms=zero", "ms=-1", "ms=999999"):
        status, _, _ = client._request("POST", f"/v1/debug/profile?{q}")
        assert status == 400, q


def test_profile_capture_real_jax_trace(tmp_path):
    """The unstubbed capture writes an actual jax.profiler trace."""
    from repro.obs.profiler import profile_capture

    try:
        out = profile_capture(str(tmp_path), 30)
    except Exception as e:  # profiler backend unavailable in this env
        pytest.skip(f"jax.profiler capture unavailable: {e}")
    produced = list(Path(out).rglob("*"))
    assert any(p.is_file() for p in produced), produced


# ---------------------------------------------------------------------------
# perf regression gate
# ---------------------------------------------------------------------------


def _run_gate(*argv):
    env = dict(os.environ, PYTHONPATH="src:.")
    return subprocess.run(
        [sys.executable, "-m", "benchmarks.check_regression", *argv],
        cwd=REPO, env=env, capture_output=True, text=True,
    )


def _write_artifacts(d: Path, transport: dict):
    d.mkdir(parents=True, exist_ok=True)
    (d / "BENCH_transport.json").write_text(json.dumps(transport))


def _tiny_baseline(d: Path) -> Path:
    base = d / "baselines.json"
    base.write_text(json.dumps({
        "BENCH_transport": [
            {"path": "achieved_rps", "direction": "higher",
             "tol": 0.25, "baseline": 1000.0},
            {"path": "p99_ms", "direction": "lower",
             "tol": 0.50, "baseline": 10.0},
        ],
    }))
    return base


def test_check_regression_passes_within_tolerance(tmp_path):
    art = tmp_path / "bench"
    _write_artifacts(art, {"achieved_rps": 900.0, "p99_ms": 13.0})
    out = _run_gate("--artifacts", str(art),
                    "--baseline", str(_tiny_baseline(tmp_path)))
    assert out.returncode == 0, out.stdout + out.stderr
    assert "perf gate ok" in out.stdout


def test_check_regression_fails_on_synthetic_regression(tmp_path):
    """Acceptance negative test: a regressed artifact fails the build."""
    art = tmp_path / "bench"
    _write_artifacts(art, {"achieved_rps": 500.0, "p99_ms": 40.0})
    out = _run_gate("--artifacts", str(art),
                    "--baseline", str(_tiny_baseline(tmp_path)))
    assert out.returncode == 1, out.stdout + out.stderr
    assert "PERF REGRESSION" in out.stdout
    assert "achieved_rps" in out.stdout and "p99_ms" in out.stdout


def test_check_regression_fails_on_missing_metric_or_artifact(tmp_path):
    baseline = _tiny_baseline(tmp_path)
    # missing artifact directory entirely
    out = _run_gate("--artifacts", str(tmp_path / "nope"),
                    "--baseline", str(baseline))
    assert out.returncode == 1
    # artifact present but the gated metric is null
    art = tmp_path / "bench"
    _write_artifacts(art, {"achieved_rps": None, "p99_ms": 5.0})
    out = _run_gate("--artifacts", str(art), "--baseline", str(baseline))
    assert out.returncode == 1
    assert "missing or non-finite" in out.stdout


def test_check_regression_update_baseline_roundtrip(tmp_path):
    art = tmp_path / "bench"
    art.mkdir()
    # synthesize every gated artifact with just the gated paths present
    payloads = {
        "BENCH_train": {"summary": {"fused_img_per_s": 100.0, "speedup": 2.0}},
        "BENCH_serve": {"encoders": {
            "uhd": {"batcher": {"img_per_s": 50.0, "p99_ms": 10.0}},
            "uhd_dynamic": {"batcher": {"img_per_s": 60.0, "p99_ms": 9.0}},
        }},
        "BENCH_encode_dynamic": {"summary": {
            "bytes_ratio_min": 256.0,
            "per_levels": {"16": {"dynamic_img_per_s": 1000.0}},
        }},
        "BENCH_transport": {
            "achieved_rps": 800.0, "p99_ms": 20.0,
            "replicas": {"4": {"achieved_rps": 2800.0, "p99_ms": 18.0,
                               "shed_rate": 0.05}},
        },
        "BENCH_online": {"ingest_eps": 5000.0, "publish_to_promote_ms": 50.0,
                         "predict_p99_ms_active": 30.0},
        "BENCH_obs": {"scrape_cycle": {"p50_ms": 15.0},
                      "merge": {"p50_ms": 1.0},
                      "staleness_detect_ms": 250.0},
        "BENCH_search": {"summary": {"queries_per_s": 120.0,
                                     "p99_ms": 15.0}},
    }
    for name, payload in payloads.items():
        (art / f"{name}.json").write_text(json.dumps(payload))
    baseline = tmp_path / "baselines.json"
    out = _run_gate("--artifacts", str(art), "--baseline", str(baseline),
                    "--update-baseline")
    assert out.returncode == 0, out.stdout + out.stderr
    written = json.loads(baseline.read_text())
    assert set(written) == set(payloads)
    # and the freshly-written baseline passes against the same artifacts
    out = _run_gate("--artifacts", str(art), "--baseline", str(baseline))
    assert out.returncode == 0, out.stdout + out.stderr


def test_committed_baseline_matches_spec_paths():
    """The committed baselines.json gates exactly the SPECS metrics —
    a drive-by edit to one without the other fails here, not in CI."""
    from benchmarks.check_regression import SPECS

    committed = json.loads((REPO / "benchmarks" / "baselines.json").read_text())
    assert set(committed) == set(SPECS)
    for name, checks in SPECS.items():
        have = {(e["path"], e["direction"]) for e in committed[name]}
        want = {(path, direction) for path, direction, _ in checks}
        assert have == want, name
        for entry in committed[name]:
            assert isinstance(entry["baseline"], (int, float))
            assert entry["baseline"] == entry["baseline"]  # not NaN


# ---------------------------------------------------------------------------
# render_prometheus unit coverage (no HTTP)
# ---------------------------------------------------------------------------


def test_render_prometheus_escapes_label_values():
    cfg = _cfg()
    registry = ModelRegistry()
    registry.register('we"ird\nname', ServingEngine(_trained(cfg),
                                                    batch_size=4))
    try:
        text = render_prometheus(registry)
    finally:
        registry.shutdown()
    assert 'model="we\\"ird\\nname"' in text
    assert "\n# TYPE uhd_queue_depth gauge\n" in text


def test_help_and_type_emitted_once_per_family_under_replica_split():
    """A pool entry and a single entry share every uhd_* family; the
    Writer must group samples so HELP/TYPE appear exactly once per
    family no matter how many models/replicas contribute (ISSUE 9
    satellite — duplicate headers are rejected by real scrapers)."""
    from repro.obs.prometheus import parse_exposition

    cfg = _cfg()
    model = _trained(cfg)
    registry = ModelRegistry()
    registry.register_pool(
        "pooled", [ServingEngine(model, batch_size=4) for _ in range(2)]
    )
    registry.register("solo", ServingEngine(model, batch_size=4))
    try:
        text = render_prometheus(registry)
    finally:
        registry.shutdown()
    # parse_exposition raises on any duplicated HELP/TYPE; also pin the
    # literal line counts so the audit cannot rot
    types, helps, samples = parse_exposition(text)
    for family in ("uhd_requests_total", "uhd_queue_depth",
                   "uhd_request_latency_seconds"):
        assert text.count(f"# TYPE {family} ") == 1
        assert text.count(f"# HELP {family} ") == 1
        assert family in types and family in helps
    # both models sampled into the shared families
    models = {ls["model"] for n, ls, _ in samples if n == "uhd_queue_depth"}
    assert models == {"pooled", "solo"}


def test_exposition_roundtrip_with_hostile_model_name():
    r"""Backslash, quote, and newline in a label value must escape on
    the way out and unescape to the exact original on the way back —
    the full 0.0.4 escaping triple, not just quotes."""
    from repro.obs.prometheus import Writer, parse_exposition

    hostile = 'evil\\model"with\nall three'
    w = Writer()
    w.sample("uhd_queue_depth", {"model": hostile}, 3,
             help='queued\nnow "really"')
    text = w.render()
    assert 'model="evil\\\\model\\"with\\nall three"' in text
    types, helps, samples = parse_exposition(text)
    [(name, labels, value)] = samples
    assert labels == {"model": hostile} and value == 3.0
    # HELP escapes backslash+newline only; quotes stay literal
    assert helps["uhd_queue_depth"] == 'queued\nnow "really"'


def test_parse_exposition_rejects_duplicates_and_malformed():
    from repro.obs.prometheus import parse_exposition

    with pytest.raises(ValueError, match="duplicate TYPE"):
        parse_exposition("# TYPE a counter\n# TYPE a gauge\na 1\n")
    with pytest.raises(ValueError, match="duplicate HELP"):
        parse_exposition("# HELP a x\n# HELP a y\na 1\n")
    with pytest.raises(ValueError, match="value"):
        parse_exposition("a notanumber\n")
    with pytest.raises(ValueError, match="label"):
        parse_exposition('a{model="unterminated} 1\n')
