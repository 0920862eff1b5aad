"""The paper's system, end-to-end and sharded: uHD single-pass training,
optionally refined by mispredict-driven retraining epochs.

    PYTHONPATH=src python -m repro.launch.train_hdc --dataset synth_mnist \
        --d 8192 --backend auto --retrain-epochs 3 --compare-baseline

Built on the `HDCModel` API: create -> fit_batches (streamed) ->
retrain_batches per epoch (DESIGN.md §15) -> evaluate -> save.  The
datapath is picked by name (--backend) through the encoder/backend
registry; "auto" resolves per platform (Pallas on TPU, MXU-unary
matmul elsewhere).

Under a mesh the image batch shards over the batch axes and the class
bundling reduces with one psum of (C, D) — the distributed form of the
paper's single-pass class-hypervector accumulation (DESIGN.md §3).
"""

from __future__ import annotations

import argparse
import time

import numpy as np

from repro.core import HDCConfig, HDCModel, baseline_iterative_search
from repro.data import load_dataset
from repro.distributed.sharding import set_current_mesh
from repro.launch.mesh import mesh_for
from repro.launch.compile_cache import enable_compile_cache


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="synth_mnist")
    ap.add_argument("--d", type=int, default=8192)
    ap.add_argument("--levels", type=int, default=16)
    ap.add_argument("--n-train", type=int, default=4096)
    ap.add_argument("--n-test", type=int, default=1024)
    ap.add_argument("--encoder", default="uhd",
                    help="registered encoder (uhd | uhd_dynamic | baseline)")
    ap.add_argument(
        "--backend", default="auto",
        help="encode datapath: auto, or a backend registered for the "
             "chosen encoder (a bad name errors listing the options)",
    )
    ap.add_argument("--batch-size", type=int, default=2048)
    ap.add_argument(
        "--shard-map", action="store_true",
        help="train via the explicit shard_map path (batch-axis psum + "
             "per-D-slice generation) instead of GSPMD inference; "
             "bit-identical results (DESIGN.md §9)",
    )
    ap.add_argument("--save-dir", default=None,
                    help="checkpoint the trained HDCModel here")
    ap.add_argument(
        "--ckpt-shards", type=int, default=0,
        help="with --save-dir: also write the checkpoint as N per-host "
             "D-shards through CheckpointManager.save_shard (simulated "
             "hosts in this single process) and verify the stitched "
             "restore",
    )
    ap.add_argument(
        "--retrain-epochs", type=int, default=0,
        help="after the single pass, N epochs of mispredict-driven "
             "retraining (HDCModel.retrain_batches); prints the test "
             "accuracy and the share of training images mispredicted "
             "in each epoch",
    )
    ap.add_argument("--compare-baseline", action="store_true")
    ap.add_argument("--baseline-iters", type=int, default=5)
    args = ap.parse_args(argv)
    enable_compile_cache()

    mesh = mesh_for()
    set_current_mesh(mesh)
    ds = load_dataset(args.dataset, n_train=args.n_train, n_test=args.n_test)
    tag = " (synthetic)" if ds.synthetic else ""
    print(f"dataset {ds.name}{tag}: {ds.train_images.shape[0]} train / "
          f"{ds.test_images.shape[0]} test, {ds.n_classes} classes")

    cfg = HDCConfig(
        n_features=ds.n_features, n_classes=ds.n_classes, d=args.d,
        levels=args.levels, encoder=args.encoder, backend=args.backend,
    )

    def batches():
        for i in range(0, len(ds.train_images), args.batch_size):
            yield (ds.train_images[i : i + args.batch_size],
                   ds.train_labels[i : i + args.batch_size])

    t0 = time.time()
    if args.shard_map:
        from repro.core import partial_fit_sharded

        model = HDCModel.create(cfg).shard(mesh)
        for images, labels in batches():
            model = partial_fit_sharded(model, images, labels, mesh=mesh)
        mode = "shard_map"
    else:
        model = HDCModel.create(cfg).fit_batches(batches())
        mode = "gspmd"
    acc = model.evaluate(ds.test_images, ds.test_labels)
    print(f"{args.encoder}  D={args.d} backend={args.backend} [{mode}]: "
          f"accuracy {acc:.4f}  "
          f"({model.n_examples} images, single pass, {time.time()-t0:.1f}s)")

    if args.retrain_epochs:
        train = list(batches())
        n_train = len(ds.train_images)
        for epoch in range(1, args.retrain_epochs + 1):
            t0 = time.time()
            model, wrong = model.retrain_batches(train)
            n_wrong = int(wrong.sum())
            acc = model.evaluate(ds.test_images, ds.test_labels)
            print(f"retrain epoch {epoch}/{args.retrain_epochs}: accuracy {acc:.4f}  "
                  f"({n_wrong} of {n_train} training images mispredicted, "
                  f"{n_wrong / n_train:.2%}; {time.time()-t0:.1f}s)")

    if args.save_dir:
        if args.ckpt_shards > 1:
            from repro.checkpoint.manager import CheckpointManager

            for pi in range(args.ckpt_shards):
                model.save_shard(
                    args.save_dir, step=0,
                    process_index=pi, process_count=args.ckpt_shards,
                )
            CheckpointManager(args.save_dir).finalize_shards(0)
        else:
            model.save(args.save_dir, step=0)
        restored = HDCModel.load(args.save_dir)
        ok = restored.cfg == model.cfg and bool(
            (restored.class_sums == model.class_sums).all()
        )
        shard_note = f", {args.ckpt_shards} host shards" if args.ckpt_shards > 1 else ""
        print(f"checkpointed to {args.save_dir} (round-trip ok: {ok}{shard_note})")

    if args.compare_baseline:
        t0 = time.time()
        accs = baseline_iterative_search(
            cfg, ds.train_images, ds.train_labels, ds.test_images, ds.test_labels,
            iterations=args.baseline_iters,
        )
        print(
            f"baseline HDC over i=1..{args.baseline_iters}: "
            f"avg {np.mean(accs):.4f} best {np.max(accs):.4f} "
            f"({time.time()-t0:.1f}s, {args.baseline_iters} full retrains)"
        )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
