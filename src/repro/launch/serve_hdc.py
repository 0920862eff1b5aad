"""HDC inference service driver: train -> checkpoint -> load -> serve.

    PYTHONPATH=src python -m repro.launch.serve_hdc --smoke

The packed-hypervector counterpart of `repro.launch.serve`: a trained
`HDCModel` is checkpointed, loaded into a `ServingEngine` (class HVs
binarized + bit-packed once), registered in a `ModelRegistry`, and a
synthetic request stream is pushed through the slot-based micro-batcher
one image at a time.  `--smoke` runs the whole loop on a synthetic
dataset and exercises hot reload mid-stream: the trainer continues with
`partial_fit`, publishes a newer checkpoint step, and the registry
swaps engines without dropping any queued request.  Prints p50/p99
latency, throughput (img/s), batch occupancy and served accuracy.

Serving an existing checkpoint:

    PYTHONPATH=src python -m repro.launch.serve_hdc --ckpt /path/to/ckpt
"""

from __future__ import annotations

import argparse
import dataclasses
import tempfile
import time

import numpy as np

from repro.core import HDCConfig, HDCModel
from repro.data import load_dataset
from repro.serving import ModelRegistry, ServingEngine
from repro.launch.compile_cache import enable_compile_cache


def _print_stats(name: str, snap: dict, n_served: int, serve_wall_s: float) -> None:
    # throughput over the serving wall clock only (the snapshot's
    # elapsed_s also spans non-serving work like retraining/reloads)
    print(
        f"[{name}] served {n_served} requests in "
        f"{serve_wall_s:.2f}s: {n_served / serve_wall_s:.1f} img/s | "
        f"latency p50 {snap['p50_ms']:.2f}ms p99 {snap['p99_ms']:.2f}ms "
        f"mean {snap['mean_ms']:.2f}ms | {snap['n_batches']} batches, "
        f"occupancy {snap['batch_occupancy']:.2f}, "
        f"reloads {snap['n_reloads']}, errors {snap['n_errors']}"
    )


def _serve_stream(
    registry: ModelRegistry,
    name: str,
    images: np.ndarray,
    *,
    timeout: float = 120.0,
) -> tuple[np.ndarray, float]:
    """Push images one request at a time; labels in order + wall seconds."""
    t0 = time.perf_counter()
    futures = [registry.submit(name, img) for img in images]
    labels = np.asarray([f.result(timeout=timeout) for f in futures], np.int32)
    return labels, time.perf_counter() - t0


def run_smoke(args) -> int:
    ds = load_dataset(args.dataset, n_train=args.n_train, n_test=args.requests)
    cfg = HDCConfig(
        n_features=ds.n_features, n_classes=ds.n_classes, d=args.d,
        levels=args.levels, encoder=args.encoder, backend=args.backend,
    )
    name = args.encoder
    ckpt_dir = args.ckpt or tempfile.mkdtemp(prefix="hdc_serve_smoke_")

    # -- train + publish step 0 (first half of the training stream) ------
    half = len(ds.train_images) // 2
    t0 = time.time()
    model = HDCModel.create(cfg).fit(ds.train_images[:half], ds.train_labels[:half])
    model.save(ckpt_dir, step=0)
    print(f"trained on {half} images + checkpointed step 0 "
          f"({time.time()-t0:.1f}s) -> {ckpt_dir}")

    # -- load behind the service -----------------------------------------
    registry = ModelRegistry()
    # pin step 0 explicitly: a reused --ckpt dir may hold newer stale steps
    batcher = registry.register_checkpoint(
        name, ckpt_dir, step=0, batch_size=args.batch, impl=args.impl, start=True
    )
    engine = registry.engine(name)
    print(f"engine loaded: {engine.describe()}")

    # parity: the packed path must agree with HDCModel.predict (hamming)
    probe = ds.test_images[: args.batch]
    served = engine.predict(probe)
    model_h = engine.model.replace(
        cfg=dataclasses.replace(engine.model.cfg, similarity="hamming")
    )
    direct = np.asarray(model_h.predict(probe))
    assert np.array_equal(served, direct), "packed path diverged from predict"
    print(f"packed-path parity vs HDCModel.predict: OK ({len(probe)} images)")

    # -- serve first half of the stream ----------------------------------
    n1 = len(ds.test_images) // 2
    preds1, wall1 = _serve_stream(registry, name, ds.test_images[:n1])

    # -- trainer publishes step 1; service hot-reloads mid-stream --------
    model = engine.model.partial_fit(ds.train_images[half:], ds.train_labels[half:])
    model.save(ckpt_dir, step=1)
    swapped = registry.hot_reload(name, step=1)  # pinned: dir may be reused
    assert swapped == 1, f"expected hot reload to step 1, got {swapped}"
    print(f"hot-reloaded to step {swapped} "
          f"(n_seen {registry.engine(name).model.n_examples}) "
          f"with {batcher.queue_depth()} requests queued")

    # -- serve the rest of the stream on the new engine ------------------
    preds2, wall2 = _serve_stream(registry, name, ds.test_images[n1:])
    preds = np.concatenate([preds1, preds2])
    acc = float((preds == ds.test_labels).mean())

    registry.stop_all()
    _print_stats(name, batcher.metrics.snapshot(), len(preds), wall1 + wall2)
    print(f"served accuracy over {len(preds)} requests: {acc:.4f}")
    print("smoke OK")
    return 0


def run_serve(args) -> int:
    """Serve an existing checkpoint against a synthetic request stream."""
    registry = ModelRegistry()
    batcher = registry.register_checkpoint(
        "uhd", args.ckpt, batch_size=args.batch, impl=args.impl, start=True
    )
    engine = registry.engine("uhd")
    print(f"engine loaded: {engine.describe()}")
    rng = np.random.default_rng(0)
    stream = rng.uniform(
        0, 255, (args.requests, engine.model.cfg.n_features)
    ).astype(np.float32)
    _, wall = _serve_stream(registry, "uhd", stream)
    registry.stop_all()
    _print_stats("uhd", batcher.metrics.snapshot(), len(stream), wall)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="full train -> checkpoint -> load -> serve loop")
    ap.add_argument("--ckpt", default=None,
                    help="checkpoint dir (serve target, or smoke output)")
    ap.add_argument("--dataset", default="synth_mnist")
    ap.add_argument("--d", type=int, default=1024)
    ap.add_argument("--levels", type=int, default=16)
    ap.add_argument("--n-train", type=int, default=1024)
    ap.add_argument("--requests", type=int, default=256)
    ap.add_argument("--batch", type=int, default=32,
                    help="static serving batch (slot count)")
    ap.add_argument("--encoder", default="uhd",
                    help="registered encoder (uhd | uhd_dynamic | baseline)")
    ap.add_argument("--backend", default="auto",
                    help="encode datapath (registry name or auto)")
    ap.add_argument("--impl", default="auto",
                    help="packed similarity: auto | pallas | jnp")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.smoke:
        return run_smoke(args)
    if not args.ckpt:
        ap.error("--ckpt is required unless --smoke")
    return run_serve(args)


if __name__ == "__main__":
    raise SystemExit(main())
