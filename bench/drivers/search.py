"""Driver ``search``: a stream of top-k searches over a packed store.

Each call encodes a block of query images, packs them and scans the
whole store through `hdc_model.search_packed` (the jitted encode ->
pack -> `hamming_topk` entry), and returns the (B, k) indices and
distances to the host.  ``in_flight`` calls are dispatched ahead of the
one whose result is read, so the chip stays fed while the host stalls;
when the window's time is up nothing more is sent, every call sent is
waited for, and the window closes after that wait: all of that work
counts, over all of that time.  The store is made on the device from
the seed; a few queries of the first block are planted at known rows
(the first of them twice, on adjacent rows, so a tie at distance 0 must
go to the lower index).

Traffic keys: ``store_rows``, ``batch`` queries per call, ``k``,
``query_blocks`` distinct blocks cycled through, ``in_flight`` calls
dispatched ahead, ``planted``, ``check_blocks`` blocks compared with
the reference (block 0 and a sample drawn from the seed; every call of
those blocks is compared), ``trace_seconds``.

End-to-end: ``search_queries_per_s`` = queries answered over the
window.  Control: `hdc_model.search_packed` returns the lower
reference's top-k of its queries over the store.
"""

from __future__ import annotations

import numpy as np

E2E = "search_queries_per_s"
STREAM_CHECK = 11


def setup(run) -> None:
    import jax
    import jax.numpy as jnp

    from bench import inputs, preflight
    from bench.reference import Reference
    from repro.core import HDCModel, hdc_model, unary
    from repro.serving.execution import resolve_impl

    c, t = run.cfg, run.traffic
    b, n_blocks = t["batch"], t["query_blocks"]
    pool = jnp.asarray(inputs.images_np(run.seed, inputs.STREAM_POOL, b * n_blocks,
                                        c["n_features"]))
    blocks = [pool[i * b : (i + 1) * b] for i in range(n_blocks)]
    words = unary.n_words(c["d"])
    rows = inputs.device_store(run.seed, t["store_rows"], words)
    # planted rows: reference words of the first queries, at rows drawn
    # from the seed; query 0 also on the next row (a tie at distance 0)
    ref = Reference(c, run.seed)
    n_plant = t["planted"]
    at = 2 * inputs.rng(run.seed, inputs.STREAM_PLANT).choice(
        t["store_rows"] // 2, size=n_plant, replace=False)  # even: at[0] + 1 is free
    planted = ref.query_words(blocks[0][:n_plant])
    rows = rows.at[at].set(planted).at[at[0] + 1].set(planted[0])
    jax.block_until_ready(rows)
    run.mark("data")

    model = HDCModel.create(run.hdc_config())
    jax.block_until_ready(model.codebooks)
    impl = resolve_impl("auto")
    run.mark("build")
    preflight.check_backend(run, model.cfg.encoder)
    preflight.native(run, hdc_model.search_packed.lower(
        model, blocks[0], rows, k=t["k"], impl=impl), "search step")
    jax.block_until_ready(hdc_model.search_packed(model, blocks[0], rows, k=t["k"],
                                                  impl=impl))
    run.mark("warm")
    run.state.update(blocks=blocks, rows=rows, model=model, impl=impl, ref=ref,
                     plant_at=np.asarray(at))


def window(run) -> None:
    from collections import deque

    from repro.core import hdc_model

    s, t = run.state, run.traffic
    model, rows, blocks, impl, k = s["model"], s["rows"], s["blocks"], s["impl"], t["k"]
    pending, out = deque(), []

    def read_one():
        idx, dist = pending.popleft()
        out.append((np.asarray(idx), np.asarray(dist)))

    with run.measure() as w:
        while w.elapsed() < run.seconds:
            sent = len(out) + len(pending)
            pending.append(hdc_model.search_packed(model, blocks[sent % len(blocks)],
                                                   rows, k=k, impl=impl))
            if len(pending) > t["in_flight"]:
                read_one()
            if w.tracing and w.elapsed() >= w.trace_s:
                while pending:  # the traced part holds whole calls
                    read_one()
                w.tick(len(out))
        while pending:
            read_one()
    s["out"] = out
    calls = len(out)
    run.e2e[E2E] = calls * t["batch"] / w.seconds
    run.attempted = calls * t["batch"]
    run.work.update(calls=calls, window_s=w.seconds, traced_calls=w.traced_work)


def release(run) -> None:
    run.state.pop("model", None)


def check(run):
    from bench import inputs
    from bench.harness import Check
    from bench.reference import topk_words

    s, t = run.state, run.traffic
    n_blocks, out, k = t["query_blocks"], s["out"], t["k"]
    used = sorted({i % n_blocks for i in range(len(out))})
    draw = inputs.rng(run.seed, STREAM_CHECK).permutation(used[1:])
    blocks = [0, *draw[: t["check_blocks"] - 1].tolist()]
    ref = s["ref"]
    differing = compared = 0
    planted_missed = 0
    for blk in blocks:
        ridx, rdist = topk_words(ref.query_words(s["blocks"][blk]), s["rows"], k)
        for i in range(blk, len(out), n_blocks):
            idx, dist = out[i]
            differing += int((idx != ridx).sum() + (dist != rdist).sum())
            compared += 1
            if blk == 0:
                at = s["plant_at"]
                planted_missed += int(((idx[: len(at), 0] != at)
                                       | (dist[: len(at), 0] != 0)).sum())
                planted_missed += int(idx[0, 1] != at[0] + 1 or dist[0, 1] != 0)
    run.note(f"reference: {compared} of {len(out)} calls compared "
             f"(blocks {blocks} of {n_blocks})")
    return [
        Check("topk_entries_differing", float(differing), 0.0),
        Check("planted_rows_missed", float(planted_missed), 0.0),
    ]


def control(cfg: dict, traffic: dict, seed: int):
    from bench.control import Stand, lower_reference
    from bench.reference import topk_words
    from repro.core import hdc_model

    low = lower_reference(cfg, seed)

    def search(model, images, rows, *, k, impl):
        return topk_words(low.query_words(images), rows, k)

    return [(hdc_model, "search_packed", Stand(hdc_model.search_packed, search))]
