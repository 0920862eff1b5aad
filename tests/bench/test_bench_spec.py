"""BENCHMARK.json against its format rules, and every file it names found by
name; a new cell, traffic mix and metric are added by adding files."""

import json
import re
import shutil
from pathlib import Path

import pytest

from bench_tiny import ROOT, TINY, load_tiny, with_unlisted

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_top_level_keys_and_command(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "bench/run.py"]
    assert 1 <= len(bench["paths"]) <= 16
    for p in bench["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and (ROOT / p).is_dir()
    assert isinstance(bench["run_seconds"], int) and 1 <= bench["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_configs(bench):
    from bench import harness

    names = [c["name"] for c in bench["configs"]]
    assert len(names) == len(set(names))
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert c["file"] == f"bench/configs/{c['name']}.json"
        data = harness.load_json(harness.config_file(harness.BENCH, c["name"]))
        assert data["name"] == c["name"] and data["reduced"] == c["reduced"]
        assert len(c["reduced"]) <= 16 and 1 <= len(c["why"]) <= 200


def test_workloads_find_their_files(bench):
    from bench import harness

    seen = set()
    four = 0
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert (w["config"], w["traffic"]) not in seen
        seen.add((w["config"], w["traffic"]))
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        four += w["chips"] == 4
        traffic = harness.load_json(harness.traffic_file(harness.BENCH, w["traffic"]))
        assert harness.driver_file(harness.BENCH, traffic["driver"]).is_file()
        assert harness.find_cell(bench, w["name"]) is w
        assert w["name"] in TINY
    assert four <= max(1, len(bench["workloads"]) // 2)


def test_metrics(bench):
    from bench import harness

    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m.get("workloads", cells)) <= cells
    layers = {}
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["source"] in SOURCES and m["moves"] in e2e
        assert harness.metric_file(harness.BENCH, m["name"]).is_file()
        layers.setdefault(m["layer"], m["layer"])
        for cell in m["workloads"]:
            reported = harness.e2e_metrics(bench, harness.find_cell(bench, cell))
            assert m["moves"] in {r["name"] for r in reported}
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    unlisted = with_unlisted(bench)
    for key in ("workloads", "end_to_end", "per_layer"):
        names = [m["name"] for m in unlisted[key]]
        assert len(names) == len(set(names)), key
    for w in bench["workloads"]:
        reported = harness.e2e_metrics(bench, w)
        assert "setup_s" in {r["name"] for r in reported} and len(reported) >= 2
        assert harness.per_layer_metrics(bench, w)


def test_every_metric_reader_loads(bench):
    from bench import harness

    for m in bench["per_layer"]:
        mod = harness.load_module(harness.metric_file(harness.BENCH, m["name"]), "m")
        assert callable(mod.read)


def test_peaks_table_refuses_an_unknown_device():
    from bench import harness

    assert harness.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.Refused):
        harness.load_peaks("TPU v9 imaginary")


def _sources(root):
    return {p.relative_to(root): p.read_bytes()
            for d in ("bench", "tests/bench") for p in sorted((root / d).rglob("*.py"))}


def test_a_new_cell_is_only_new_files(tmp_path, monkeypatch):
    """A low-rate traffic mix, its cell, its tiny size and a new per-layer
    metric are added to a copy of the benchmark and its tests as files and
    entries alone: the copy's tiny sizes find the new cell, the harness
    runs it and reports the new metric, its control comes out not
    correct, and no `.py` file of `bench/` or `tests/bench/` changed."""
    from bench import control, harness

    skip = shutil.ignore_patterns("__pycache__")
    shutil.copytree(ROOT / "bench", tmp_path / "bench", ignore=skip)
    shutil.copytree(ROOT / "tests/bench", tmp_path / "tests/bench", ignore=skip)
    (tmp_path / "src").symlink_to(ROOT / "src")
    before = _sources(tmp_path)

    bench = with_unlisted(json.loads((ROOT / "BENCHMARK.json").read_text()))
    traffic = json.loads((ROOT / "bench/traffic/http_poisson_1img.json").read_text())
    traffic["rate_per_s"] = traffic["rate_per_s"] / 20
    (tmp_path / "bench/traffic/http_poisson_1img_low.json").write_text(json.dumps(traffic))
    tiny = TINY["predict_dyn_http_poisson"]
    (tmp_path / "tests/bench/tiny/predict_dyn_http_lowrate.json").write_text(json.dumps(
        {"config": tiny["config"], "traffic": {**tiny["traffic"], "rate_per_s": 20}}))
    bench["workloads"].append({"name": "predict_dyn_http_lowrate",
                               "config": "uhd_dynamic_mnist_d8192",
                               "traffic": "http_poisson_1img_low", "chips": 1,
                               "why": "every step pads"})
    for m in bench["end_to_end"]:
        if m["name"] == "predict_p99_ms":
            m["workloads"].append("predict_dyn_http_lowrate")
    bench["per_layer"].append({"name": "http.assembly_p99_ms", "unit": "ms",
                               "better": "lower", "source": "program_span",
                               "layer": "batcher", "moves": "predict_p99_ms",
                               "workloads": ["predict_dyn_http_lowrate"]})
    (tmp_path / "bench/metrics/http.assembly_p99_ms.py").write_text(
        "from bench.readers import span_ms\n\n\ndef read(run):\n"
        "    return span_ms(run, 'assembly', 99)\n")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    sizes = load_tiny(tmp_path / "tests/bench/tiny")
    assert {w["name"] for w in bench["workloads"]} <= set(sizes)
    monkeypatch.setattr(harness, "enable_compile_cache", lambda root: "off")
    kw = dict(seed=5, seconds=1.0, require_tpu=False, root=tmp_path,
              bench=tmp_path / "bench", overrides=sizes["predict_dyn_http_lowrate"])
    out = harness.run_cell(workload="predict_dyn_http_lowrate", trace=True, **kw)
    assert out["correct"], out["checks"]
    assert "http.assembly_p99_ms" in out["metrics"]
    assert "http.write_p99_ms" not in out["metrics"]  # listed for its own cell only
    with control.swapped("predict_dyn_http_lowrate", 5, root=tmp_path,
                         bench=tmp_path / "bench", overrides=kw["overrides"]):
        low = harness.run_cell(workload="predict_dyn_http_lowrate", trace=False, **kw)
    assert not low["correct"]
    after = _sources(tmp_path)
    assert {k: after[k] for k in before} == before  # no existing file edited
    assert set(after) - set(before) == {Path("bench/metrics/http.assembly_p99_ms.py")}
