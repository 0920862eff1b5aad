"""Driver ``http``: raw-binary ``:predict`` requests over HTTP to one
`HdcHttpServer`, sent by the load generator (`bench/loadgen.py`) in its
own process.

Traffic keys: ``loop``, "open" (requests due on a seeded schedule:
``arrival`` "poisson" at ``rate_per_s``, each timed from when it was
due) or "closed" (each of the ``connections`` sends its next request as
soon as its last answer arrives, with no think time, until the window's
time is up); ``images_per_request`` (one block, served by one device
step when it equals ``batch_size``), ``image_pool`` distinct images
cycled through, ``connections``, the deployment (``batch_size``,
``max_delay_ms``, ``max_queue_depth``, optionally ``replicas`` and
``placement``), the served model's training set (``n_train``,
``fit_batch``), ``trace_seconds``.

Set-up trains the served model from the seed (`fit_batches` over a
device-made training set), saves it, registers it through
`ModelRegistry.register_checkpoint` (one unpinned engine by default, a
`ReplicaPool` for more replicas), starts the server on a local port,
and starts the load generator, which warms every connection.  The
window opens when the generator is told to go and closes when it has
every answer.  The program's fleet-merged stage histograms and counters
(`ModelRegistry.metrics_state`) and a pool's per-replica dispatch counts
(`describe_entry`) are read before and after, so the per-layer metrics
see the window alone.

End-to-end: ``predict_p99_ms``, every request timed from when it was
due, and ``predict_images_per_s``, the images answered with status 200
over the window; a cell reports the one `BENCHMARK.json` gives it.

Checked: every label returned over the wire against the reference's
label for that image; no request left without an answer; no request
answered with any status but 200 (shed, refused or failed).  Control:
`ServingEngine.predict` returns the lower reference's labels, from
class words it trained on the served model's set.
"""

from __future__ import annotations

import json
import shutil
import subprocess
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from bench import inputs, preflight
from bench.spans import Hist

MODEL = "bench"
LOADGEN = Path(__file__).resolve().parents[1] / "loadgen.py"
FAILED_LATENCY_MS = 60_000.0  # a request without an answer misses every limit


def setup(run) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core import HDCModel, hdc_model
    from repro.serving import ModelRegistry
    from repro.transport import HdcHttpServer

    c, t, s = run.cfg, run.traffic, run.state
    x, y = inputs.device_dataset(run.seed, t["n_train"], c["n_features"], c["n_classes"])
    fb = t["fit_batch"]
    batches = [(x[i : i + fb], y[i : i + fb]) for i in range(0, t["n_train"], fb)]
    jax.block_until_ready(batches)
    s.update(x=x, y=y)
    run.mark("data")
    model = HDCModel.create(run.hdc_config()).fit_batches(batches)
    s["workdir"] = tempfile.mkdtemp(prefix="bench_serve_")
    model.save(Path(s["workdir"]) / "ckpt", step=0)
    run.mark("build")

    preflight.check_backend(run, model.cfg.encoder)
    registry = ModelRegistry()
    s["registry"] = registry
    engine = registry.register_checkpoint(
        MODEL, Path(s["workdir"]) / "ckpt", step=0, batch_size=t["batch_size"],
        impl="auto", replicas=t.get("replicas", 1),
        placement=t.get("placement", "auto"), max_delay_ms=t["max_delay_ms"], start=True,
    ).engine
    preflight.native(run, hdc_model.predict_packed.lower(
        engine.model, jnp.zeros((t["batch_size"], c["n_features"]), jnp.float32),
        engine.class_words, impl=engine.impl), "predict step")
    run.mark("warm")

    server = HdcHttpServer(registry, port=0, max_queue_depth=t["max_queue_depth"])
    s["server"] = server.start()
    host, port = server.address
    spec = {
        "host": host, "port": port, "model": MODEL, "seed": run.seed,
        "seconds": run.seconds, "traffic": t, "pool": t["image_pool"],
        "n_features": c["n_features"], "images_per_request": t["images_per_request"],
        "connections": t["connections"], "timeout_s": FAILED_LATENCY_MS / 1e3,
        "loop": t["loop"],
        "out": str(Path(s["workdir"]) / "loadgen.npz"),
    }
    spec_path = Path(s["workdir"]) / "loadgen.json"
    spec_path.write_text(json.dumps(spec))
    child = subprocess.Popen([sys.executable, str(LOADGEN), str(spec_path)],
                             stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
    s["child"], s["loadgen_out"] = child, spec["out"]
    line = child.stdout.readline()
    if line.strip() != "ready":
        raise RuntimeError(f"load generator did not start: {line!r}")
    run.mark("server")


def _snapshot(run) -> tuple[dict, list[int] | None]:
    """Fleet-merged serving metrics, and a pool's blocks per replica."""
    registry = run.state["registry"]
    return (registry.metrics_state()[MODEL]["serving"],
            registry.describe_entry(MODEL).get("n_dispatched"))


def window(run) -> dict:
    """The generator's window; sets both end-to-end readings."""
    s = run.state
    child = s["child"]
    before = _snapshot(run)
    with run.measure() as w:
        child.stdin.write("go\n")
        child.stdin.flush()
        if w.tracing:
            time.sleep(min(w.trace_s, run.seconds))
            w.stop_trace()
        summary = child.stdout.readline()
    if child.wait(timeout=120) != 0 or not summary:
        raise RuntimeError(f"load generator failed (exit {child.returncode})")
    after = _snapshot(run)
    lg = json.loads(summary)
    run.note(f"load generator: {lg['requests']} requests, {lg['ok']} answered 200, "
             f"at most {lg['outstanding_max']} outstanding; sent late by p50 "
             f"{lg['late_p50_ms']:.3f} ms, p99 {lg['late_p99_ms']:.3f} ms, "
             f"max {lg['late_max_ms']:.3f} ms")

    res = dict(np.load(s["loadgen_out"]))
    s["res"] = res
    ok = res["status"] == 200
    run.attempted = int(len(ok))
    run.failed = int((~ok).sum())
    # both end-to-end readings of a serving window; a cell reports the
    # one BENCHMARK.json gives it (tails below the knee, rate above it)
    run.e2e["predict_p99_ms"] = float(np.percentile(latency_ms(res), 99))
    images = int(ok.sum()) * run.traffic["images_per_request"]
    run.e2e["predict_images_per_s"] = images / (float(np.nanmax(res["done"]))
                                                - float(res["t0"]))

    (b, b_disp), (a, a_disp) = before, after
    run.counters = {k: a["counters"][k] - b["counters"][k]
                    for k in ("n_batches", "n_slots", "n_padded", "n_requests", "n_shed")}
    if a_disp is not None:
        run.counters["n_dispatched"] = [x - y for x, y in zip(a_disp, b_disp)]
        run.note(f"blocks dispatched per replica in the window: "
                 f"{run.counters['n_dispatched']}")
    run.spans = {name: Hist.delta(b["stages"].get(name), h)
                 for name, h in a["stages"].items()}
    run.work.update(window_s=w.seconds)
    return res


def release(run) -> None:
    s = run.state
    child = s.pop("child", None)
    if child is not None and child.poll() is None:
        child.kill()
        child.wait(timeout=30)
    server = s.pop("server", None)
    if server is not None:
        server.stop()
    registry = s.pop("registry", None)
    if registry is not None:
        registry.shutdown()
    workdir = s.pop("workdir", None)
    if workdir is not None:
        shutil.rmtree(workdir, ignore_errors=True)


def check(run):
    import jax.numpy as jnp

    from bench.harness import Check
    from bench.reference import Reference

    s, t, c = run.state, run.traffic, run.cfg
    ref = Reference(c, run.seed)
    sums = ref.class_sums(s["x"], s["y"])
    words = ref.pack(sums)
    rounded = int((np.asarray(words) != np.asarray(ref.pack_exact(sums))).sum())
    run.note(f"reference: class words whose float32 centering differs from exact "
             f"integer centering: {rounded} words")
    pool = inputs.images_np(run.seed, inputs.STREAM_POOL, t["image_pool"],
                            c["n_features"])
    want = ref.labels(jnp.asarray(pool), words)
    res = s["res"]
    per = t["images_per_request"]
    ok = res["status"] == 200
    want_rows = want[(res["block"][:, None] * per + np.arange(per)[None, :])]
    differing = int((res["labels"][ok] != want_rows[ok]).sum())
    never = int((res["status"] == -1).sum())
    not_ok = int((res["status"] != 200).sum())
    return [
        Check("labels_differing", float(differing), 0.0),
        Check("requests_never_answered", float(never), 0.0),
        Check("requests_not_ok", float(not_ok), 0.0),
    ]


def control(cfg: dict, traffic: dict, seed: int):
    import jax.numpy as jnp

    from bench.control import lower_reference
    from repro.serving import ServingEngine

    low = lower_reference(cfg, seed)
    x, y = inputs.device_dataset(seed, traffic["n_train"], cfg["n_features"],
                                 cfg["n_classes"])
    words = low.pack(low.class_sums(x, y))

    def predict(engine, images):
        return low.labels(jnp.asarray(images, jnp.float32), words)

    return [(ServingEngine, "predict", predict)]


def latency_ms(res: dict) -> np.ndarray:
    """Each request's latency from when it was due; failures miss all."""
    lat = (res["done"] - res["due"]) * 1e3
    return np.where(res["status"] == 200, lat, FAILED_LATENCY_MS)
