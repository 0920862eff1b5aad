"""Streaming packed-Hamming top-k: the associative-memory search kernel.

Turns the (B, C) similarity matrix of `hamming_packed` into a running
k-best without ever materializing it: the grid is (B/bt, C/ct) with the
row-tile axis innermost, and *both* outputs — (bt, k) distances and
(bt, k) indices — map every j to the same block (``lambda i, j:
(i, 0)``), the Pallas revisiting pattern.  Each j-step XOR+popcounts
one (ct, W) row tile against the resident (bt, W) query block (8 query
rows at a time into a (bt, ct) VMEM scratch, so the working set is
bounded by ct * W, never by C), appends the ct candidates to the k
carried in the output refs, and re-selects the k best.  At C=1M /
D=8192 the stream is ~1 GB of packed rows read
once per query block — pure memory bandwidth, which is exactly what
`benchmarks/search_bench.py` measures against the roofline.

Ordering contract (DESIGN.md §14): rows ascend by (Hamming distance,
global row index) — lowest index wins ties.  The in-kernel merge is a
k-step selection loop built only from elementwise ops and min
reductions (no sort/argsort primitives, which Pallas-TPU lacks): each
step takes the minimum distance, then the minimum global index among
its holders, then masks that single candidate to the int32-max
sentinel.  Valid distances are <= d << 2^31, so the sentinel can never
collide with a real candidate.  Bit-identical to
`ref.hamming_topk_oracle` for every (B, C, D, k), including D%32 != 0
(packers zero the pad bits, which cancel in XOR) and duplicate rows.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.hamming_packed import popcount_distances, round_up

_I32_MAX = np.iinfo(np.int32).max


def _topk_kernel(q_ref, c_ref, idx_ref, dist_ref, dist_scr, *, k: int,
                 block_c: int, c_actual: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        idx_ref[...] = jnp.full(idx_ref.shape, _I32_MAX, jnp.int32)
        dist_ref[...] = jnp.full(dist_ref.shape, _I32_MAX, jnp.int32)

    # (bt, ct) Hamming distances of the resident query block against
    # this tile of the row stream, 8 query rows at a time.
    popcount_distances(q_ref, c_ref, dist_scr)
    dist_t = dist_scr[...]
    bt = dist_t.shape[0]
    gidx = j * block_c + jax.lax.broadcasted_iota(jnp.int32, (bt, block_c), 1)
    valid = gidx < c_actual  # grid-padded rows never win
    dist_t = jnp.where(valid, dist_t, _I32_MAX)
    gidx = jnp.where(valid, gidx, _I32_MAX)

    # Merge carry + tile candidates: (bt, k + ct) pool, pick k smallest
    # under the pinned (distance, index) order.  Unrolled over static k.
    dists = jnp.concatenate([dist_ref[...], dist_t], axis=1)
    idxs = jnp.concatenate([idx_ref[...], gidx], axis=1)
    out_d, out_i = [], []
    for _ in range(k):
        m = jnp.min(dists, axis=1, keepdims=True)
        pick = jnp.min(jnp.where(dists == m, idxs, _I32_MAX), axis=1,
                       keepdims=True)
        out_d.append(m)
        out_i.append(pick)
        # Exactly one candidate holds (m, pick) — real (dist, idx) pairs
        # are unique because gidx is; sentinel pairs are interchangeable.
        hit = (dists == m) & (idxs == pick)
        dists = jnp.where(hit, _I32_MAX, dists)
        idxs = jnp.where(hit, _I32_MAX, idxs)
    dist_ref[...] = jnp.concatenate(out_d, axis=1)
    idx_ref[...] = jnp.concatenate(out_i, axis=1)


def hamming_topk_pallas(
    q_words: jax.Array,
    c_words: jax.Array,
    d: int,
    k: int,
    *,
    block_b: int = 128,
    block_c: int = 256,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """q: (B, W) uint32, rows: (C, W) uint32 -> ((B, k) int32 indices,
    (B, k) int32 distances), each row ascending by (distance, index).

    B and C are arbitrary: operands are zero-padded up to the block
    grid; padded query rows are sliced off the result and padded store
    rows are masked to the sentinel in-kernel (their global index is
    >= C), so they never appear in a result.  The row tile shrinks with
    W (at most 2^16 words, so the (8, ct, W) XOR cube stays ~2 MiB at
    any store size) and to the store itself when C is small (the C~10
    predict path); the query tile shrinks to a small B.
    """
    b, w = q_words.shape
    c, w2 = c_words.shape
    assert w == w2
    if not 1 <= k <= c:
        raise ValueError(f"k must be in [1, {c}], got {k}")
    bt = min(block_b, round_up(b, 8))
    ct = min(block_c, max(8, (1 << 16) // w // 8 * 8), round_up(c, 8))
    bp, cp = round_up(b, bt), round_up(c, ct)
    if bp != b:
        q_words = jnp.pad(q_words, ((0, bp - b), (0, 0)))
    if cp != c:
        c_words = jnp.pad(c_words, ((0, cp - c), (0, 0)))

    idx, dist = pl.pallas_call(
        functools.partial(_topk_kernel, k=k, block_c=ct, c_actual=c),
        grid=(bp // bt, cp // ct),
        in_specs=[
            pl.BlockSpec((bt, w), lambda i, j: (i, 0)),
            pl.BlockSpec((ct, w), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bt, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bt, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bp, k), jnp.int32),
            jax.ShapeDtypeStruct((bp, k), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((bt, ct), jnp.int32)],
        interpret=interpret,
        name="hamming_topk",
        metadata={"hdc_kernel": "hamming_topk"},
    )(q_words, c_words)
    return idx[:b], dist[:b]
