#!/usr/bin/env python3
"""Bring-up smoke: the uHD train -> serve -> search path on a TPU chip.

    python chip_smoke.py             # one chip: fit, HTTP predict, search
    python chip_smoke.py --chips 4   # four chips: pool, sharded, sharded fit

One process drives the system through the entry points a user calls,
at the paper's widths (H=784, C=10, D=8192, 16 levels) on the
`synth_mnist` dataset, and checks every result bit-for-bit against a
plain reference computed on the same chip (the arithmetic is integer,
so there is no tolerance):

  * fit    — `HDCModel.fit_batches` over 8192 images in 2048-image
    steps, both encoders; class sums == the pure-JAX datapath.
  * serve  — `ModelRegistry.register_checkpoint` -> `HdcHttpServer` ->
    `HdcClient`: 256 raw-binary `:predict` requests with a mid-stream
    watcher promotion uhd -> uhd_dynamic; labels == `predict` with
    ``similarity="hamming"`` on the pure-JAX encoder.  One `:search`
    at k=C over the class words == `hamming_topk_oracle`.
  * search — an `ItemMemory` of 262,144 rows (256 MiB at D=8192),
    k=10 for a handful of queries == `hamming_topk_oracle`.

``--chips 4`` runs only what exists across chips, each against the
one-chip engine: a `ReplicaPool` of four one-chip replicas, one
four-chip `ShardedExecution` (d_local=2048, W=64), and
`partial_fit_sharded` over a (data=2, model=2) mesh.

Before any phase it refuses a run that would quietly leave the chip:
no TPU, an "auto" backend or packed impl that is not "pallas", or
kernels set to interpret mode.  Phase wall times (compilation
included) are printed as smoke timings, not metrics.  The last stdout
line is the JSON contract line, printed only when every check passed;
any failure exits non-zero without it.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import dataclasses
import json
import sys
import tempfile
import threading
import time
import traceback
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"
ENCODERS = ("uhd", "uhd_dynamic")
# pure-JAX datapath of each encoder: the reference every kernel must match
REF_BACKEND = {"uhd": "blocked", "uhd_dynamic": "ref"}


class SmokeError(RuntimeError):
    """A smoke check failed."""


def check(ok, what: str) -> None:
    # explicit, not `assert`: `python -O` must not strip the checks
    if not ok:
        raise SmokeError(what)


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Scale of one smoke run (H and C come from the dataset)."""

    d: int = 8192
    levels: int = 16
    n_train: int = 8192
    fit_batch: int = 2048
    n_requests: int = 256
    serve_batch: int = 64
    store_rows: int = 262_144
    top_k: int = 10
    n_queries: int = 8
    seed: int = 0


def log(msg: str) -> None:
    print(msg, flush=True)


def _on_tpu() -> bool:
    import jax

    return jax.default_backend() == "tpu"


def _native(lowered, what: str) -> None:
    """On the chip, the program must carry compiled Pallas kernels
    (``tpu_custom_call``), not their interpret-mode emulation."""
    if _on_tpu():
        check("tpu_custom_call" in lowered.as_text(),
              f"{what}: no native Pallas kernel in the lowered program")


def _ref_model(model, **overrides):
    """The same trained state under another config (e.g. the pure-JAX
    backend and hamming similarity) — the reference for predict."""
    from repro.core import HDCModel

    cfg = dataclasses.replace(model.cfg, **overrides)
    return HDCModel.from_parts(cfg, model.codebooks, model.class_sums, model.n_seen)


# ---------------------------------------------------------------------------
# preflight: nothing may quietly leave the chip
# ---------------------------------------------------------------------------


def preflight(n_chips: int) -> dict:
    import jax

    from repro.core import item_memory, registry
    from repro.kernels import ops
    from repro.serving.execution import resolve_impl

    devices = jax.devices()
    dev = devices[0]
    check(dev.platform == "tpu", f"no TPU: JAX runs on {dev.platform!r}")
    check(len(devices) >= n_chips,
          f"{n_chips} chips asked for, JAX sees {len(devices)}")
    for enc in ENCODERS:
        got = registry.resolve_backend("auto", encoder=enc)
        check(got == "pallas", f"encoder {enc!r}: auto backend is {got!r}")
    check(resolve_impl("auto") == "pallas",
          f"packed impl auto is {resolve_impl('auto')!r}")
    check(item_memory.ItemMemory(32).impl == "pallas", "ItemMemory impl is not pallas")
    check(not ops._interpret_default(), "Pallas kernels default to interpret mode")
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; backend "
        f"auto=pallas for {', '.join(ENCODERS)}; packed impl auto=pallas; "
        "kernels native")
    return {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------


def _batches(x, y, size: int):
    return [(x[i : i + size], y[i : i + size]) for i in range(0, len(x), size)]


def phase_fit(ds, sizes: Sizes, *, backend: str = "auto") -> dict:
    """Train both encoders through `fit_batches`; class sums must equal
    the pure-JAX datapath's, integer for integer."""
    import jax
    import jax.numpy as jnp

    from repro.core import HDCConfig, HDCModel, encoding, hdc_model, registry

    x = jnp.asarray(ds.train_images[: sizes.n_train])
    y = jnp.asarray(ds.train_labels[: sizes.n_train])
    batches = _batches(x, y, sizes.fit_batch)
    models = {}
    for enc in ENCODERS:
        cfg = HDCConfig(
            n_features=ds.n_features, n_classes=ds.n_classes, d=sizes.d,
            levels=sizes.levels, encoder=enc, backend=backend, seed=sizes.seed,
        )
        model = HDCModel.create(cfg)
        _native(jax.jit(hdc_model._partial_fit).lower(model, *batches[0]),
                f"fit step ({enc})")
        model = model.fit_batches(batches)
        jax.block_until_ready(model.class_sums)

        encoder = registry.get_encoder(enc)
        ref_step = jax.jit(lambda xb, yb: encoder.fit_bundle(
            cfg, model.codebooks,
            encoding.quantize_images(xb, cfg.levels, cfg.max_intensity), yb,
            backend=REF_BACKEND[enc],
        ))
        want = sum(np.asarray(ref_step(xb, yb), np.int64) for xb, yb in batches)
        check(model.n_examples == sizes.n_train,
              f"fit ({enc}): n_examples {model.n_examples} != {sizes.n_train}")
        check(np.array_equal(np.asarray(model.class_sums), want),
              f"fit ({enc}): class sums differ from the {REF_BACKEND[enc]!r} datapath")
        models[enc] = model
    log(f"fit: {sizes.n_train} images in {sizes.fit_batch}-image steps, "
        f"{'/'.join(ENCODERS)}: class sums == pure-JAX datapath")
    return models


def _http_stream(host, port, name, images, promoted: threading.Event,
                 wait_from: int, workers: int = 4) -> np.ndarray:
    """One raw-binary `:predict` request per image from `workers`
    keep-alive clients; requests from index `wait_from` on are held
    until `promoted` is set, so the promoted engine serves them."""
    from repro.transport import HdcClient

    out = np.full(len(images), -1, np.int32)

    def worker(w: int) -> None:
        with HdcClient(host, port, timeout_s=300.0) as client:
            for i in range(w, len(images), workers):
                if i >= wait_from:
                    check(promoted.wait(timeout=300.0), "promotion never came")
                out[i] = client.predict_batch(name, images[i : i + 1])[0]

    with concurrent.futures.ThreadPoolExecutor(workers) as pool:
        for fut in [pool.submit(worker, w) for w in range(workers)]:
            fut.result()
    return out


def _served(registry, name: str) -> int:
    return int(registry.batcher(name).metrics.snapshot()["n_requests"])


def phase_serve(ds, models: dict, sizes: Sizes, workdir: Path, *,
                impl: str = "auto") -> None:
    """Checkpoint -> registry -> HTTP server -> clients, with a watcher
    promoting uhd -> uhd_dynamic while requests are in flight."""
    import jax.numpy as jnp

    from repro.core import hdc_model
    from repro.kernels import ref as kref
    from repro.serving import ModelRegistry
    from repro.transport import HdcClient, HdcHttpServer, ReloadWatcher

    model = models["uhd"]
    name = "uhd"
    ckpt = workdir / "ckpt"
    images = np.asarray(ds.test_images[: sizes.n_requests], np.float32)
    model.save(ckpt, step=0)

    registry = ModelRegistry()
    server = None
    try:
        registry.register_checkpoint(
            name, ckpt, step=0, batch_size=sizes.serve_batch, impl=impl, start=True,
        )
        engine0 = registry.engine(name)
        if _on_tpu():
            check(engine0.impl == "pallas", f"engine impl is {engine0.impl!r}")
        _native(hdc_model.predict_packed.lower(
            engine0.model, jnp.zeros((sizes.serve_batch, ds.n_features)),
            engine0.class_words, impl=engine0.impl), "predict step")
        promoted = threading.Event()
        watcher = ReloadWatcher(
            registry, name, interval_s=0.05,
            on_promote=lambda n, s: promoted.set() if s == 1 else None,
        ).start()
        server = HdcHttpServer(registry, port=0).start()
        host, port = server.address

        half, last = sizes.n_requests // 2, sizes.n_requests - sizes.n_requests // 4
        with concurrent.futures.ThreadPoolExecutor(1) as bg:
            stream = bg.submit(_http_stream, host, port, name, images, promoted, last)
            # publish step 1 once half the stream is answered; requests
            # [half, last) are in flight across the swap, the rest wait
            # for it so the promoted engine provably serves some
            deadline = time.time() + 300.0
            while _served(registry, name) < half and not stream.done():
                check(time.time() < deadline, "stream stalled before half")
                time.sleep(0.005)
            model.convert("uhd_dynamic").save(ckpt, step=1)
            served = stream.result()
        check(promoted.is_set(), "watcher did not promote step 1")
        engine1 = registry.engine(name)
        check(engine1.step == 1 and engine1.model.cfg.encoder == "uhd_dynamic",
              f"serving step {engine1.step} ({engine1.model.cfg.encoder})")

        want = np.asarray(_ref_model(
            model, similarity="hamming", backend=REF_BACKEND["uhd"]).predict(images))
        check(np.array_equal(served, want),
              f"HTTP labels differ from predict(similarity='hamming') in "
              f"{int((served != want).sum())} of {len(want)}")

        # :search over the class words at k = C
        k = model.cfg.n_classes
        q = images[: sizes.n_queries]
        with HdcClient(host, port, timeout_s=300.0) as client:
            idx, dist = client.search(name, q, k=k)
            check(client.healthz()["models"][name]["step"] == 1, "healthz step != 1")
        ref = _ref_model(model, backend=REF_BACKEND["uhd"])
        qw = ref.pack_queries(ref.encode(jnp.asarray(q)))
        ridx, rdist = kref.hamming_topk_oracle(qw, ref.pack(), model.cfg.d, k)
        check(np.array_equal(idx, np.asarray(ridx))
              and np.array_equal(dist, np.asarray(rdist)),
              "HTTP :search differs from hamming_topk_oracle")
        watcher_n = watcher.n_promotions
    finally:
        if server is not None:
            server.stop()
        registry.shutdown()
    log(f"serve: {sizes.n_requests} raw-binary :predict requests over HTTP, "
        f"uhd -> uhd_dynamic promoted mid-stream ({watcher_n} promotion); "
        f"labels == predict(similarity='hamming'); :search k={k} == oracle")


def phase_search(ds, models: dict, sizes: Sizes, *, impl: str | None = None) -> None:
    """A 262,144-row `ItemMemory` (256 MiB of packed rows at D=8192)
    with queries planted at known rows; top-k == the full-argsort
    oracle over the same rows, computed on the same chip."""
    import jax
    import jax.numpy as jnp

    from repro.core import ItemMemory, hdc_model, unary
    from repro.kernels import ref as kref

    model, k = models["uhd_dynamic"], sizes.top_k
    w = unary.n_words(sizes.d)
    rows = jax.random.bits(
        jax.random.key(sizes.seed), (sizes.store_rows, w), jnp.uint32
    )
    q = model.pack_queries(model.encode(jnp.asarray(ds.test_images[: sizes.n_queries])))
    # each query planted once at a known row, query 0 twice: its two
    # exact matches tie at distance 0 and the lower index must win
    planted = np.arange(sizes.n_queries) * (sizes.store_rows // sizes.n_queries) + 7
    rows = rows.at[planted].set(q).at[planted[0] + 1].set(q[0])

    mem = ItemMemory(sizes.d, impl=impl)
    mem.add_packed(np.asarray(rows))
    check(mem.nbytes == sizes.store_rows * w * 4, f"store holds {mem.nbytes} bytes")
    _native(jax.jit(lambda a, b: hdc_model._packed_topk(a, b, sizes.d, k, mem.impl))
            .lower(q, rows), "search scan")
    idx, dist = mem.search(q, k)
    ridx, rdist = kref.hamming_topk_oracle(q, rows, sizes.d, k)
    check(np.array_equal(idx, np.asarray(ridx))
          and np.array_equal(dist, np.asarray(rdist)),
          "ItemMemory.search differs from hamming_topk_oracle")
    check(np.array_equal(idx[:, 0], planted) and not dist[:, 0].any(),
          "a planted row was not found at distance 0")
    check(idx[0, 1] == planted[0] + 1 and dist[0, 1] == 0,
          "tie at distance 0 not broken by lowest index")
    log(f"search: ItemMemory {sizes.store_rows} rows ({mem.nbytes / 2**20:.0f} MiB), "
        f"k={k}, {sizes.n_queries} queries == hamming_topk_oracle (impl {mem.impl})")


# ---------------------------------------------------------------------------
# four chips: only what exists across chips, against the one-chip engine
# ---------------------------------------------------------------------------


def phase_pool(ds, models: dict, sizes: Sizes, workdir: Path, devices, *,
               impl: str = "auto") -> None:
    """One replica per chip behind one name: every replica answers on
    its own device, and labels / top-k equal the one-chip engine's."""
    from repro.serving import DeviceExecution, ModelRegistry, ServingEngine

    model, k = models["uhd_dynamic"], sizes.top_k
    images = np.asarray(ds.test_images[: sizes.serve_batch], np.float32)
    one = ServingEngine(model, batch_size=sizes.serve_batch,
                        execution=DeviceExecution(impl=impl, device=devices[0]))
    want, (widx, wdist) = one.predict(images), one.search(images, k)

    ckpt = workdir / "pool_ckpt"
    model.save(ckpt, step=0)
    registry = ModelRegistry()
    try:
        pool = registry.register_checkpoint(
            "pool", ckpt, step=0, batch_size=sizes.serve_batch, impl=impl,
            placement="device", replicas=len(devices), start=True,
        )
        check(len(pool.replicas) == len(devices), "one replica per chip")
        for i, replica in enumerate(pool.replicas):
            eng, dev = replica.engine, devices[i]
            check(eng.execution.device == dev,
                  f"replica {i} pinned to {eng.execution.device}")
            labels = eng.execution.predict(eng.model, eng.class_words, images)
            idx, dist = eng.execution.search(eng.model, eng.class_words, images, k)
            for out in (labels, idx, dist):
                check(out.devices() == {dev},
                      f"replica {i}: output on {out.devices()}, not {dev}")
            check(np.array_equal(np.asarray(labels), want), f"replica {i}: labels differ")
            check(np.array_equal(np.asarray(idx), widx)
                  and np.array_equal(np.asarray(dist), wdist),
                  f"replica {i}: top-k differs")
        # and through the pool's own least-loaded dispatch
        futures = [f for j in range(0, len(images), 8)
                   for f in pool.submit_block(images[j : j + 8])]
        got = np.asarray([f.result(timeout=300.0) for f in futures], np.int32)
        check(np.array_equal(got, want), "pool-dispatched labels differ")
        spread = list(pool.n_dispatched)
    finally:
        registry.shutdown()
    log(f"pool: {len(devices)} one-chip replicas, outputs on their own device, "
        f"labels + top-{k} == one-chip engine; dispatch per replica {spread}")


def phase_sharded(ds, models: dict, sizes: Sizes, devices, *,
                  impl: str = "auto") -> None:
    """One engine D-sharded over every chip (one psum per request):
    labels and top-k equal the one-chip engine's, both encoders."""
    from repro.core import unary
    from repro.serving import DeviceExecution, ServingEngine, ShardedExecution

    images = np.asarray(ds.test_images[: sizes.serve_batch], np.float32)
    k = sizes.top_k
    for enc, model in models.items():
        one = ServingEngine(model, batch_size=sizes.serve_batch,
                            execution=DeviceExecution(impl=impl, device=devices[0]))
        sharded = ServingEngine(model, batch_size=sizes.serve_batch,
                                execution=ShardedExecution(devices=devices, impl=impl))
        check(sharded.execution.n_shards == len(devices), "shard count")
        d_local = sizes.d // len(devices)
        check(sharded.class_words.shape[1] == len(devices) * unary.n_words(d_local),
              f"class words {sharded.class_words.shape}")
        check(np.array_equal(sharded.predict(images), one.predict(images)),
              f"sharded labels differ ({enc})")
        sidx, sdist = sharded.search(images, k)
        widx, wdist = one.search(images, k)
        check(np.array_equal(sidx, widx) and np.array_equal(sdist, wdist),
              f"sharded top-k differs ({enc})")
    log(f"sharded: one {len(devices)}-chip ShardedExecution (d_local={d_local}, "
        f"W={unary.n_words(d_local)}), {'/'.join(models)}: labels + top-{k} "
        "== one-chip engine")


def phase_fit_sharded(ds, models: dict, sizes: Sizes, devices) -> None:
    """`partial_fit_sharded` over a (data=2, model=n/2) mesh: batch psum
    plus per-D-slice generation; class sums == the one-chip fit."""
    import jax.numpy as jnp

    from repro.core import HDCModel, partial_fit_sharded
    from repro.launch.mesh import _make_mesh

    mesh = _make_mesh((2, len(devices) // 2), ("data", "model"))
    x = jnp.asarray(ds.train_images[: sizes.n_train])
    y = jnp.asarray(ds.train_labels[: sizes.n_train])
    for enc, single in models.items():
        model = HDCModel.create(single.cfg).shard(mesh)
        for xb, yb in _batches(x, y, sizes.fit_batch):
            model = partial_fit_sharded(model, xb, yb, mesh=mesh)
        check(np.array_equal(np.asarray(model.class_sums), np.asarray(single.class_sums)),
              f"partial_fit_sharded class sums differ from one chip ({enc})")
        check(model.n_examples == single.n_examples, f"n_examples ({enc})")
    log(f"fit_sharded: partial_fit_sharded over mesh {dict(mesh.shape)}, "
        f"{'/'.join(models)}: class sums == one-chip fit")


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def _timed(timings: dict, name: str, fn, *args, **kw):
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    timings[name] = time.perf_counter() - t0
    return out


def run(n_chips: int, sizes: Sizes = Sizes()) -> dict:
    """Every phase of one smoke run; returns the contract's device dict.
    Raises on the first failed check."""
    import jax

    from repro.data import load_dataset
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    cache = {"hits": 0, "misses": 0}

    def count_cache(event: str, **kw) -> None:
        kind = event.removeprefix("/jax/compilation_cache/cache_")
        if kind in cache:
            cache[kind] += 1

    jax.monitoring.register_event_listener(count_cache)
    device = preflight(n_chips)
    timings: dict[str, float] = {}
    # named explicitly: load_dataset("mnist") falls back to synth_mnist
    ds = _timed(timings, "data", load_dataset, "synth_mnist",
                n_train=sizes.n_train, n_test=sizes.n_requests, seed=sizes.seed)
    log(f"config: synth_mnist H={ds.n_features} C={ds.n_classes} D={sizes.d} "
        f"levels={sizes.levels}; compile cache {cache_dir}")
    scratch = ROOT / "artifacts"  # git-ignored, inside the checkout
    scratch.mkdir(exist_ok=True)
    with tempfile.TemporaryDirectory(prefix="chip_smoke_", dir=scratch) as tmp:
        workdir = Path(tmp)
        models = _timed(timings, "fit", phase_fit, ds, sizes)
        if n_chips == 1:
            _timed(timings, "serve", phase_serve, ds, models, sizes, workdir)
            _timed(timings, "search", phase_search, ds, models, sizes)
        else:
            devices = jax.devices()[:n_chips]
            _timed(timings, "pool", phase_pool, ds, models, sizes, workdir, devices)
            _timed(timings, "sharded", phase_sharded, ds, models, sizes, devices)
            _timed(timings, "fit_sharded", phase_fit_sharded, ds, models, sizes, devices)
    log("smoke timings (wall s, compilation included; not metrics): "
        + ", ".join(f"{k}={v:.3f}" for k, v in timings.items()))
    log(f"compile cache {cache_dir}: {cache['hits']} hits, {cache['misses']} misses")
    return device


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: fit, HTTP predict, search on one chip; 4: replica "
                         "pool, sharded engine and sharded fit across four")
    args = ap.parse_args(argv)
    # this checkout's sources, never an installed copy: alone, the script fails
    sys.path.insert(0, str(SRC))
    try:
        import repro.core
    except ImportError as e:
        print(f"chip_smoke: cannot import this checkout's repro from {SRC}: {e}",
              file=sys.stderr)
        return 2
    if Path(repro.core.__file__).resolve().parents[2] != SRC:
        print(f"chip_smoke: repro imported from {repro.core.__file__}, not {SRC}",
              file=sys.stderr)
        return 2
    try:
        device = run(args.chips)
    except Exception:  # the smoke's boundary: report and fail
        traceback.print_exc()
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
