"""HTTP serving driver: train -> publish -> serve over a real socket.

    PYTHONPATH=src python -m repro.launch.serve_http --smoke

The network counterpart of `repro.launch.serve_hdc`: the same packed
serving stack, but fronted by `repro.transport` (DESIGN.md §8) — an
`HdcHttpServer` on a real TCP socket, `HdcClient` workers generating
traffic, and a `ReloadWatcher` doing the checkpoint promotion that PR 2
required a manual `hot_reload()` call for.

`--smoke` runs the full production shape end to end:

  1. train an `HDCModel`, publish checkpoint step 0, register it and
     start the drain thread + reload watcher + HTTP server;
  2. verify transport parity: labels over HTTP (JSON single and raw
     binary batch) are bit-identical to the in-process engine;
  3. stream requests from concurrent client threads; **mid-traffic**
     the trainer publishes step 1 — the `convert`-ed table ->
     `uhd_dynamic` artifact of the same model state — and the watcher
     promotes it with requests in flight.  Because conversion is exact,
     every label of the stream must still match the step-0 engine
     bit-for-bit, whichever side of the swap served it;
  4. exercise the admission-control edges (413 oversize payload) and
     the `/metrics` + `/healthz` control plane;
  5. drain shutdown: server stops accepting and drains in-flight
     connections, then the registry stops watcher -> batcher -> engine.

`--replicas N` (with optional `--placement`) deploys the entry as a
replica fleet (DESIGN.md §12): the smoke then additionally asserts pool
health/placement reporting, per-replica Prometheus series, and that the
mid-traffic promotion swaps every replica atomically.  Pair with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to exercise
sharded replicas on a forced CPU mesh.

Serving an existing checkpoint directory (watcher follows the trainer):

    PYTHONPATH=src python -m repro.launch.serve_http --ckpt /path/to/ckpt
"""

from __future__ import annotations

import argparse
import concurrent.futures
import tempfile
import time

import numpy as np

from repro.core import HDCConfig, HDCModel
from repro.data import load_dataset
from repro.serving import ModelRegistry
from repro.transport import HdcClient, HdcHttpServer, ReloadWatcher, TransportError
from repro.launch.compile_cache import enable_compile_cache


def _stream_over_http(
    host: str,
    port: int,
    name: str,
    images: np.ndarray,
    *,
    workers: int = 4,
    chunk: int = 8,
) -> np.ndarray:
    """Push images through concurrent clients (one keep-alive connection
    per worker, binary hot path); returns labels in input order."""
    out = np.full(len(images), -1, np.int32)

    def worker(start: int) -> None:
        with HdcClient(host, port, timeout_s=120.0) as client:
            for i in range(start, len(images), workers * chunk):
                block = images[i : i + chunk]
                out[i : i + len(block)] = client.predict_batch(name, block)

    with concurrent.futures.ThreadPoolExecutor(workers) as pool:
        list(pool.map(worker, [w * chunk for w in range(workers)]))
    assert (out >= 0).all(), "stream left unserved requests"
    return out


def _entry_snapshot(batcher) -> dict:
    """Metrics snapshot for a registry entry: fleet-merged for a
    `ReplicaPool`, the batcher's own for a single engine."""
    merged = getattr(batcher, "merged_metrics", None)
    return (merged() if merged is not None else batcher.metrics).snapshot()


def run_smoke(args) -> int:
    ds = load_dataset(args.dataset, n_train=args.n_train, n_test=args.requests)
    cfg = HDCConfig(
        n_features=ds.n_features, n_classes=ds.n_classes, d=args.d,
        levels=args.levels, encoder=args.encoder, backend=args.backend,
    )
    name = args.encoder
    ckpt_dir = args.ckpt or tempfile.mkdtemp(prefix="hdc_serve_http_smoke_")

    # -- 1: train + publish step 0, bring the service up ------------------
    t0 = time.time()
    model = HDCModel.create(cfg).fit(ds.train_images, ds.train_labels)
    model.save(ckpt_dir, step=0)
    print(f"trained {len(ds.train_images)} images + checkpointed step 0 "
          f"({time.time()-t0:.1f}s) -> {ckpt_dir}")

    registry = ModelRegistry(trace_jsonl=args.trace_jsonl)
    batcher = registry.register_checkpoint(
        name, ckpt_dir, step=0, batch_size=args.batch, impl=args.impl,
        placement=args.placement, replicas=args.replicas,
        max_depth=args.max_queue_depth, start=True,
    )
    engine0 = registry.engine(name)
    entry_desc = registry.describe_entry(name)
    print(f"placement: {entry_desc['placement']}"
          + (f" x{entry_desc['n_replicas']} replicas"
             if "n_replicas" in entry_desc else ""))
    watcher = ReloadWatcher(
        registry, name, interval_s=args.watch_interval,
        on_promote=lambda n, s: print(f"[watcher] promoted {n!r} to step {s}"),
    ).start()
    server = HdcHttpServer(
        registry, host=args.host, port=args.port,
        max_body_bytes=args.max_body_bytes,
        enable_profiling=args.enable_profiling,
    ).start()
    host, port = server.address
    print(f"serving {engine0.describe()}")
    print(f"listening on http://{host}:{port} "
          f"(watcher interval {args.watch_interval}s)")

    # -- 2: transport parity against the in-process engine ----------------
    with HdcClient(host, port) as client:
        assert client.healthz()["status"] == "ok"
        probe = np.asarray(ds.test_images[: args.batch], np.float32)
        direct = engine0.predict(probe)
        via_json = np.asarray([client.predict(name, img) for img in probe[:4]])
        via_bin = client.predict_batch(name, probe)
        assert np.array_equal(via_json, direct[:4]), "JSON path diverged"
        assert np.array_equal(via_bin, direct), "binary path diverged"
        print(f"transport parity vs in-process engine: OK ({len(probe)} images)")

        # 413: oversize payloads are refused before they are buffered
        try:
            client.predict_batch(
                name,
                np.zeros((args.max_body_bytes // (4 * ds.n_features) + 2,
                          ds.n_features), np.float32),
            )
            raise AssertionError("oversize payload was not refused")
        except TransportError as e:
            assert e.status == 413, e
            print(f"admission control: oversize payload -> 413 OK")

    # -- 3: stream with a watcher-driven table->dynamic promotion ---------
    # the whole request stream flows continuously; when roughly half of
    # it has been served the trainer publishes step 1 — the *exact*
    # `convert`-ed table -> uhd_dynamic representation — and the watcher
    # promotes it with requests in flight.  Conversion is exact, so
    # every label must match the step-0 engine bit-for-bit, whichever
    # engine served it; the swap is visible only in /healthz (step) and
    # metrics (n_reloads).
    n_before = _entry_snapshot(batcher)["n_requests"]
    half = len(ds.test_images) // 2
    t_serve0 = time.perf_counter()
    with concurrent.futures.ThreadPoolExecutor(1) as stream_pool:
        stream_fut = stream_pool.submit(
            _stream_over_http, host, port, name, ds.test_images
        )
        while (_entry_snapshot(batcher)["n_requests"] - n_before < half
               and not stream_fut.done()):
            time.sleep(0.01)

        table_bytes = int(engine0.describe()["codebook_bytes"])
        model.convert("uhd_dynamic").save(ckpt_dir, step=1)
        print("published step 1 (uhd_dynamic convert of the same state) "
              f"with the stream in flight")
        deadline = time.time() + max(30.0, 50 * args.watch_interval)
        while registry.engine(name).step != 1:
            if time.time() > deadline:
                raise AssertionError("watcher did not promote step 1 in time")
            time.sleep(args.watch_interval / 4)
        promoted = registry.engine(name)
        print(f"watcher promoted mid-traffic: step {promoted.step}, "
              f"encoder {promoted.model.cfg.encoder!r}, codebook "
              f"{table_bytes} -> {promoted.describe()['codebook_bytes']} bytes")

        preds = stream_fut.result()
    serve_wall = time.perf_counter() - t_serve0

    # bit-identical across the whole stream, both sides of the promotion
    reference = np.asarray(engine0.predict(ds.test_images))
    assert np.array_equal(preds, reference), \
        "labels diverged across the table->dynamic promotion"
    acc = float((preds == ds.test_labels).mean())

    # -- 4: control plane reflects what happened --------------------------
    with HdcClient(host, port) as client:
        snap = client.metrics()[name]
        health = client.healthz()["models"][name]
        trace_entries = client.traces()
        prom = client.metrics(prometheus=True)
    assert snap["n_reloads"] >= 1, snap
    assert health["step"] == 1 and health["watcher"]["n_promotions"] >= 1

    if args.replicas > 1:
        # the promotion was atomic over the whole fleet: every replica
        # is at step 1, and the control plane reports the fleet shape
        assert health["placement"] == "pool", health
        assert [r["replica"] for r in health["replicas"]] == list(
            range(args.replicas)
        ), health
        assert all(r["step"] == 1 for r in health["replicas"]), health
        assert all(
            r.engine.step == 1 for r in registry.batcher(name).replicas
        )
        print(f"fleet: all {args.replicas} replicas at step 1 after the "
              "mid-traffic promotion (atomic swap) OK")

    # observability (DESIGN.md §11): every streamed request left a trace
    # whose four spans are disjoint sub-intervals of [submit, done] —
    # their sum can never exceed the end-to-end latency
    req_traces = [t for t in trace_entries if t["kind"] == "request"]
    assert len(req_traces) >= min(args.requests, 1024), len(req_traces)
    for t in req_traces:
        spans = t["spans"]
        assert set(spans) == {"queue_ms", "assembly_ms", "device_ms",
                              "write_ms"}, spans
        assert sum(spans.values()) <= t["e2e_ms"] + 1e-6, t
    promo_events = [t for t in trace_entries
                    if t["kind"] == "event" and t["event"] == "promotion"]
    assert promo_events and promo_events[-1]["step"] == 1, promo_events
    assert "uhd_requests_total" in prom, prom[:200]
    assert "uhd_stage_latency_seconds_bucket" in prom, prom[:200]
    if args.replicas > 1:
        # pool entries break the uhd_* families out per replica
        assert 'replica="pool"' in prom and 'replica="0"' in prom, prom[:400]
    print(f"traces: {len(req_traces)} request spans + {len(promo_events)} "
          "promotion events, span sums <= e2e: OK")
    print(f"prometheus exposition: {len(prom.splitlines())} lines OK")
    if args.trace_jsonl:
        print(f"trace JSONL streamed to {args.trace_jsonl}")

    # -- 5: drain shutdown -------------------------------------------------
    server.stop()
    registry.shutdown()
    assert not watcher.running()

    n = len(preds)
    print(
        f"[{name}] served {n} HTTP requests in {serve_wall:.2f}s: "
        f"{n / serve_wall:.1f} img/s | latency p50 {snap['p50_ms']:.2f}ms "
        f"p99 {snap['p99_ms']:.2f}ms | {snap['n_batches']} batches, "
        f"occupancy {snap['batch_occupancy']:.2f}, reloads {snap['n_reloads']}, "
        f"shed {snap['n_shed']}, errors {snap['n_errors']}"
    )
    print(f"served accuracy over {n} requests: {acc:.4f}")
    print("smoke OK")
    return 0


def run_serve(args) -> int:
    """Serve an existing checkpoint dir over HTTP until interrupted; the
    watcher follows whatever steps the trainer publishes there."""
    registry = ModelRegistry(trace_jsonl=args.trace_jsonl)
    registry.register_checkpoint(
        args.name, args.ckpt, batch_size=args.batch, impl=args.impl,
        placement=args.placement, replicas=args.replicas,
        max_depth=args.max_queue_depth, start=True,
    )
    print(f"placement: {registry.describe_entry(args.name)['placement']}")
    watcher = ReloadWatcher(
        registry, args.name, interval_s=args.watch_interval,
        on_promote=lambda n, s: print(f"[watcher] promoted {n!r} to step {s}"),
    ).start()
    server = HdcHttpServer(
        registry, host=args.host, port=args.port,
        max_body_bytes=args.max_body_bytes,
        enable_profiling=args.enable_profiling,
    ).start()
    print(f"serving {registry.engine(args.name).describe()}")
    print(f"listening on http://{server.host}:{server.port} — Ctrl-C to stop")
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        print("draining...")
    finally:
        server.stop()
        registry.shutdown()
        assert not watcher.running()
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="train -> publish -> serve over a socket -> "
                         "watcher-driven promotion -> drain shutdown")
    ap.add_argument("--ckpt", default=None,
                    help="checkpoint dir (serve target, or smoke output)")
    ap.add_argument("--name", default="uhd", help="served model name")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0,
                    help="TCP port (0 = ephemeral)")
    ap.add_argument("--dataset", default="synth_mnist")
    ap.add_argument("--d", type=int, default=1024)
    ap.add_argument("--levels", type=int, default=16)
    ap.add_argument("--n-train", type=int, default=1024)
    ap.add_argument("--requests", type=int, default=256)
    ap.add_argument("--batch", type=int, default=32,
                    help="static serving batch (slot count)")
    ap.add_argument("--encoder", default="uhd",
                    help="registered encoder (uhd | uhd_dynamic | baseline)")
    ap.add_argument("--backend", default="auto")
    ap.add_argument("--impl", default="auto",
                    help="packed similarity: auto | pallas | jnp")
    ap.add_argument("--replicas", type=int, default=1,
                    help="engine replicas behind the model name (a "
                         "ReplicaPool with least-loaded dispatch)")
    ap.add_argument("--placement", default="auto",
                    help="execution placement per replica: auto | device "
                         "| sharded (shard_map packed predict over the "
                         "replica's device group)")
    ap.add_argument("--watch-interval", type=float, default=0.2,
                    help="reload watcher poll interval (seconds)")
    ap.add_argument("--max-queue-depth", type=int, default=1024,
                    help="admission bound: queued requests before 429")
    ap.add_argument("--max-body-bytes", type=int, default=4 << 20,
                    help="admission bound: request payload before 413")
    ap.add_argument("--trace-jsonl", default=None,
                    help="stream finished trace entries to this JSONL file")
    ap.add_argument("--enable-profiling", action="store_true",
                    help="allow POST /v1/debug/profile (jax.profiler "
                         "capture); off by default")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.smoke:
        return run_smoke(args)
    if not args.ckpt:
        ap.error("--ckpt is required unless --smoke")
    return run_serve(args)


if __name__ == "__main__":
    raise SystemExit(main())
