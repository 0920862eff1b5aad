"""The benchmark harness: one run of one cell.

Everything particular to a configuration, a traffic mix or a per-layer
metric lives in a file of its own, found by the name `BENCHMARK.json`
gives it:

  * ``bench/configs/<config>.json``   the configuration's sizes;
  * ``bench/traffic/<traffic>.json``  the traffic mix, naming its driver;
  * ``bench/drivers/<driver>.py``     set-up, window and check of a kind
    of traffic (fit, search, http);
  * ``bench/metrics/<metric>.py``     the reader of one per-layer metric.

A run: refuse without the chips the cell asks for; set up (inputs and
weights from the seed, every shape of the cell warmed); measure for
``--seconds`` (with ``--trace 1``, under the profiler for the first
``trace_seconds`` of it); read the memory peak; free the program's
state; compare what the window produced with the plain reference
(`bench/reference.py`); print the result line.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
WINDOW_SPAN = "bench.window"


class Refused(RuntimeError):
    """The run cannot be made here (no chip, too few chips, no program)."""


@dataclasses.dataclass
class Check:
    """One number compared with the reference, beside its limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


# ---------------------------------------------------------------------------
# the benchmark's files
# ---------------------------------------------------------------------------


def load_json(path: Path) -> Any:
    return json.loads(Path(path).read_text())


def load_benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def find_cell(benchmark: dict, name: str) -> dict:
    for cell in benchmark["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[c['name'] for c in benchmark['workloads']]}")


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def config_file(bench: Path, name: str) -> Path:
    return bench / "configs" / f"{name}.json"


def traffic_file(bench: Path, name: str) -> Path:
    return bench / "traffic" / f"{name}.json"


def driver_file(bench: Path, kind: str) -> Path:
    return bench / "drivers" / f"{kind}.py"


def metric_file(bench: Path, name: str) -> Path:
    return bench / "metrics" / f"{name}.py"


def resolve(workload: str, root: Path = ROOT, bench: Path = BENCH,
            overrides: dict | None = None) -> tuple[dict, dict, dict, dict]:
    """BENCHMARK.json, the cell, its configuration and its traffic mix.
    ``overrides`` (tests only) replaces config and traffic entries to
    shrink a cell."""
    benchmark = load_benchmark(root)
    cell = find_cell(benchmark, workload)
    cfg = load_json(config_file(bench, cell["config"]))
    traffic = load_json(traffic_file(bench, cell["traffic"]))
    if overrides:
        cfg.update(overrides.get("config", {}))
        traffic.update(overrides.get("traffic", {}))
    return benchmark, cell, cfg, traffic


def e2e_metrics(benchmark: dict, cell: dict) -> list[dict]:
    """End-to-end metrics the cell reports (every cell reports setup_s)."""
    return [m for m in benchmark["end_to_end"]
            if "workloads" not in m or cell["name"] in m["workloads"]]


def per_layer_metrics(benchmark: dict, cell: dict) -> list[dict]:
    moved = {m["name"] for m in e2e_metrics(benchmark, cell)}
    return [m for m in benchmark["per_layer"]
            if (cell["name"] in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]


def load_peaks(device_kind: str, bench: Path = BENCH) -> dict:
    table = load_json(bench / "peaks.json")
    if device_kind not in table["devices"]:
        raise Refused(f"device kind {device_kind!r} is not in bench/peaks.json "
                      f"(have {sorted(table['devices'])})")
    return table["devices"][device_kind]


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


class Run:
    """State of one run, handed to the driver module and the metric readers."""

    def __init__(self, *, benchmark: dict, cell: dict, cfg: dict, traffic: dict,
                 seed: int, seconds: float, trace: bool, bench: Path = BENCH,
                 t_start: float | None = None):
        self.benchmark, self.cell, self.cfg, self.traffic = benchmark, cell, cfg, traffic
        self.seed, self.seconds, self.trace = int(seed), float(seconds), bool(trace)
        self.bench = bench
        self.n_chips = int(cell["chips"])
        self.t_start = time.perf_counter() if t_start is None else t_start
        self.marks: dict[str, float] = {}
        self._last_mark = self.t_start
        self.state: dict[str, Any] = {}
        self.e2e: dict[str, float] = {}
        self.attempted = 0
        self.failed = 0
        self.work: dict[str, Any] = {}  # facts of the window for the metrics
        self.counters: dict[str, Any] = {}  # program counters, window deltas
        self.spans: dict[str, Any] = {}  # program span histograms, window deltas
        self.reduction = None  # trace_reduce.Reduction of the traced window
        self.peaks: dict | None = None
        self.device: dict = {}
        self.compiles = {"backend_compiles": 0, "cache_loads": 0}
        self.counting = False  # compilations are counted inside the window
        self.window: Window | None = None

    # -- set-up split --------------------------------------------------

    def mark(self, phase: str) -> None:
        """Close set-up phase `phase` (time since the previous mark)."""
        now = time.perf_counter()
        self.marks[phase] = self.marks.get(phase, 0.0) + now - self._last_mark
        self._last_mark = now

    @staticmethod
    def note(line: str) -> None:
        print(line, flush=True)

    def measure(self) -> "Window":
        self.window = Window(self)
        return self.window

    def hdc_config(self):
        """The configuration as the program's `HDCConfig`; the Sobol
        direction numbers' seeded initial values use the run's seed."""
        from repro.core import HDCConfig

        c = self.cfg
        return HDCConfig(n_features=c["n_features"], n_classes=c["n_classes"], d=c["d"],
                         levels=c["levels"], encoder=c["encoder"], seed=self.seed,
                         sobol_skip=c["sobol_skip"], backend=c["backend"],
                         max_intensity=c["max_intensity"])

    @property
    def on_tpu(self) -> bool:
        return self.device.get("platform") == "tpu"

    def devices(self):
        import jax

        return jax.devices()[: self.n_chips]


class Window:
    """The measured window.  With tracing on, the profiler runs from
    just before the window opens until `trace_seconds` of it passed (or
    it closes), and the host span ``bench.window`` marks the traced
    part on the profiler's clock."""

    def __init__(self, run: Run):
        self.run = run
        self.trace_dir: str | None = None
        self.trace_s = float(run.traffic.get("trace_seconds", run.seconds))
        self._ann = None
        self.t0 = self.t1 = None
        self.traced_work = None

    def __enter__(self) -> "Window":
        run = self.run
        if run.trace:
            import jax

            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            self.trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self._ann = jax.profiler.TraceAnnotation(WINDOW_SPAN)
            self._ann.__enter__()
        run.mark("trace_start" if run.trace else "idle")
        run.counting = True
        self.t0 = time.perf_counter()
        return self

    def elapsed(self) -> float:
        return time.perf_counter() - self.t0

    @property
    def tracing(self) -> bool:
        return self._ann is not None

    def tick(self, work=None) -> None:
        """Called by the driver module between units of work: ends the trace
        once `trace_seconds` of the window have passed."""
        if self.tracing and self.elapsed() >= self.trace_s:
            self.stop_trace(work)

    def stop_trace(self, work=None) -> None:
        import jax

        self._ann.__exit__(None, None, None)
        self._ann = None
        self.traced_work = work
        jax.profiler.stop_trace()

    def __exit__(self, *exc) -> None:
        self.t1 = time.perf_counter()
        if self.tracing:
            self.stop_trace(None)
        self.run.counting = False

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


class CompileCounter:
    """Counts backend compilations and persistent-cache loads made
    while the run's window is open (`jax.monitoring` listeners)."""

    def __init__(self, run: Run):
        self.run = run

    def on_duration(self, event: str, duration: float, **kw) -> None:
        if self.run.counting and event.endswith("backend_compile_duration"):
            self.run.compiles["backend_compiles"] += 1

    def on_event(self, event: str, **kw) -> None:
        if self.run.counting and event.endswith("cache_hits"):
            self.run.compiles["cache_loads"] += 1

    def __enter__(self) -> "CompileCounter":
        import jax

        jax.monitoring.register_event_duration_secs_listener(self.on_duration)
        jax.monitoring.register_event_listener(self.on_event)
        return self

    def __exit__(self, *exc) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self.on_duration)
        jax.monitoring.unregister_event_listener(self.on_event)


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache: `JAX_COMPILATION_CACHE_DIR`
    when set, else a fixed directory in the checkout.  Every program is
    written to it, however fast it compiled, so a cell's second run
    finds them all."""
    import jax

    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache


def describe_device(run: Run, require_tpu: bool) -> None:
    import jax

    devices = jax.devices()
    dev = devices[0]
    run.device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(devices)}
    if require_tpu:
        if dev.platform != "tpu":
            raise Refused(f"no TPU: JAX runs on {dev.platform!r}")
        if len(devices) < run.n_chips:
            raise Refused(f"the cell asks for {run.n_chips} chips, JAX sees {len(devices)}")
        run.peaks = load_peaks(dev.device_kind, run.bench)


def memory_peak(run: Run) -> int | None:
    peaks = []
    for dev in run.devices():
        stats = dev.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def reduce_trace(run: Run) -> None:
    from bench import trace_reduce

    w = run.window
    try:
        events = trace_reduce.load_xplane(trace_reduce.find_xplane(w.trace_dir))
    finally:
        shutil.rmtree(w.trace_dir, ignore_errors=True)
    run.reduction = trace_reduce.Reduction(events)


def read_per_layer(run: Run) -> dict:
    out = {}
    for m in per_layer_metrics(run.benchmark, run.cell):
        mod = load_module(metric_file(run.bench, m["name"]), f"bench_metric_{m['name']}")
        value = mod.read(run)
        if value is None:
            continue
        if not math.isfinite(value):
            raise ValueError(f"metric {m['name']} read {value}")
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(*, workload: str, seed: int, seconds: float, trace: bool,
             root: Path = ROOT, bench: Path = BENCH, require_tpu: bool = True,
             overrides: dict | None = None, t_start: float | None = None) -> dict:
    """One run; returns the result line's object.  ``overrides`` (tests
    only) replaces config and traffic entries to shrink a cell."""
    benchmark, cell, cfg, traffic = resolve(workload, root, bench, overrides)
    driver = load_module(driver_file(bench, traffic["driver"]),
                         f"bench_driver_{traffic['driver']}")
    run = Run(benchmark=benchmark, cell=cell, cfg=cfg, traffic=traffic, seed=seed,
              seconds=seconds, trace=trace, bench=bench, t_start=t_start)

    src = root / "src"
    if not (src / "repro").is_dir():
        raise Refused(f"the program is not in this checkout ({src})")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    import jax  # noqa: F401  (the first touch of the chip)

    cache = enable_compile_cache(root)
    describe_device(run, require_tpu)
    run.mark("import")
    run.note(f"cell {workload}: config {cell['config']}, traffic {cell['traffic']} "
             f"(driver {traffic['driver']}), seed {seed}, {seconds:g} s, "
             f"trace {int(trace)}; device {run.device}; compile cache {cache}")

    try:
        with CompileCounter(run):
            driver.setup(run)
            driver.window(run)
        mem = memory_peak(run)
    finally:
        driver.release(run)  # the program's state; the inputs stay for the check
        gc.collect()
    setup_s = run.window.t0 - run.t_start
    run.e2e["setup_s"] = setup_s

    split = ", ".join(f"{k}={v:.3f}" for k, v in run.marks.items())
    run.note(f"setup split (s): {split}; setup_s={setup_s:.3f}")
    run.note(f"compiles inside the window: {run.compiles['backend_compiles']} backend "
             f"compiles, {run.compiles['cache_loads']} cache loads (expected 0, 0)")

    device = dict(run.device, memory_peak_bytes=mem)
    breakdown = None
    if trace:
        reduce_trace(run)
        used = list(range(run.n_chips))
        red = run.reduction
        device["busy_s"] = red.busy_mean_s(used)
        device["window_s"] = red.window_s
        breakdown = {"device_ops": red.top_ops(10), "idle_gaps": red.idle_gaps(used, 10)}
        run.note("device busy per chip in the traced window (s): "
                 + ", ".join(f"{d}={red.busy_s(d):.6f}" for d in used))
        metrics = read_per_layer(run)
        run.note("end-to-end readings of this traced run (not metrics): "
                 + ", ".join(f"{k}={v}" for k, v in run.e2e.items()))
    else:
        metrics = {m["name"]: {"value": float(run.e2e[m["name"]]), "unit": m["unit"]}
                   for m in e2e_metrics(benchmark, cell)}

    checks = driver.check(run)
    correct = bool(checks) and all(c.ok for c in checks)
    for c in checks:
        print(f"check {c.name}: {c.value:g} (limit {c.limit:g}) "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr, flush=True)
    out = {
        "correct": correct,
        "attempted": int(run.attempted),
        "failed": int(run.failed),
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit} for c in checks}
    return out
