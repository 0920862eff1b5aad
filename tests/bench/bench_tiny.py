"""Tiny sizes of every cell, for the benchmark's CPU tests, and the
entries of the HTTP cell, which `BENCHMARK.json` does not hold until its
rate is set from a sweep on the chip (its files are in `bench/`: the
tests add the entries to a copy)."""

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

TINY = {
    "fit_uhd_mnist60k": {"config": {"d": 256},
                         "traffic": {"n_train": 512, "batch": 128, "trace_seconds": 0.3}},
    "search_dyn_store1m": {"config": {"d": 256},
                           "traffic": {"store_rows": 4096, "batch": 16, "query_blocks": 4,
                                       "planted": 4, "check_blocks": 2,
                                       "trace_seconds": 0.3}},
    "predict_dyn_http_poisson": {"config": {"d": 256},
                                 "traffic": {"rate_per_s": 100, "image_pool": 256,
                                             "n_train": 512, "fit_batch": 256,
                                             "batch_size": 16, "connections": 8,
                                             "trace_seconds": 0.3}},
}

HTTP = {
    "workload": {
        "name": "predict_dyn_http_poisson",
        "config": "uhd_dynamic_mnist_d8192",
        "traffic": "http_poisson_1img",
        "chips": 1,
        "why": "Poisson single-image raw-binary :predict at 4/5 of the knee: independent users; transport and batcher host path bound it, not kernels"
    },
    "end_to_end": [
        {
            "name": "predict_p99_ms",
            "unit": "ms",
            "better": "lower",
            "bound": 0.2,
            "source": "host_clock",
            "workloads": [
                "predict_dyn_http_poisson"
            ]
        }
    ],
    "per_layer": [
        {
            "name": "http.write_p99_ms",
            "unit": "ms",
            "better": "lower",
            "source": "program_span",
            "layer": "transport",
            "moves": "predict_p99_ms",
            "workloads": [
                "predict_dyn_http_poisson"
            ]
        },
        {
            "name": "http.queue_p99_ms",
            "unit": "ms",
            "better": "lower",
            "source": "program_span",
            "layer": "batcher",
            "moves": "predict_p99_ms",
            "workloads": [
                "predict_dyn_http_poisson"
            ]
        },
        {
            "name": "http.batch_fill",
            "unit": "%",
            "better": "higher",
            "source": "program_counter",
            "layer": "batcher",
            "moves": "predict_p99_ms",
            "workloads": [
                "predict_dyn_http_poisson"
            ]
        },
        {
            "name": "http.device_span_ms",
            "unit": "ms",
            "better": "lower",
            "source": "program_span",
            "layer": "engine",
            "moves": "predict_p99_ms",
            "workloads": [
                "predict_dyn_http_poisson"
            ]
        },
        {
            "name": "http.device_idle",
            "unit": "%",
            "better": "lower",
            "source": "device_trace",
            "layer": "device",
            "moves": "predict_p99_ms",
            "workloads": [
                "predict_dyn_http_poisson"
            ]
        }
    ]
}


def with_http(bench: dict) -> dict:
    """BENCHMARK.json's entries with the HTTP cell's added."""
    bench = json.loads(json.dumps(bench))
    if HTTP["workload"]["name"] not in {w["name"] for w in bench["workloads"]}:
        bench["workloads"].append(HTTP["workload"])
        bench["end_to_end"].extend(HTTP["end_to_end"])
        bench["per_layer"].extend(HTTP["per_layer"])
    return bench


def root_with_http(tmp_path):
    """A checkout root whose BENCHMARK.json also holds the HTTP cell."""
    bench = with_http(json.loads((ROOT / "BENCHMARK.json").read_text()))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "src").symlink_to(ROOT / "src")
    return tmp_path
