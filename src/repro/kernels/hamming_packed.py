"""Packed-binary hypervector similarity: XOR + popcount on uint32 lanes.

Inference-side unary machinery (uHD contributions 3/4 at classification
time): binarized hypervectors are stored 32 dims/word; the ±1 dot
product is  d - 2 * popcount(q ^ c).  The VPU's native
``population_count`` is the paper's popcounter circuit.

Grid (B/bt, C/ct); the word axis W is small (D/32 <= 512 for D <= 16K)
and kept whole per block.  Inside a block the XOR + popcount runs 8
query rows at a time (`popcount_distances`), so the transient cube is
(8, ct, W) whatever bt is.  TPU block rules: ct is the whole padded
class axis when C <= block_c, else a multiple of 128 (the output
block's lane dim).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def popcount_distances(q_ref, c_ref, out_ref):
    """out (bt, ct) int32 = popcount(q[b] ^ c[r]) summed over words, for
    a (bt, W) query block and a (ct, W) row tile.  Walks the query block
    8 rows (one sublane tile) at a time, bounding the XOR cube to
    (8, ct, W) in VMEM."""
    c = c_ref[...]

    def rows(g, carry):
        r0 = pl.multiple_of(g * 8, 8)
        q = q_ref[pl.ds(r0, 8), :]
        pc = jax.lax.population_count(q[:, None, :] ^ c[None, :, :])
        out_ref[pl.ds(r0, 8), :] = pc.astype(jnp.int32).sum(-1)
        return carry

    jax.lax.fori_loop(0, q_ref.shape[0] // 8, rows, 0)


def _hamming_kernel(q_ref, c_ref, o_ref, *, d: int):
    popcount_distances(q_ref, c_ref, o_ref)
    o_ref[...] = d - 2 * o_ref[...]


def round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def hamming_packed_pallas(
    q_words: jax.Array,
    c_words: jax.Array,
    d: int,
    *,
    block_b: int = 128,
    block_c: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """q: (B, W) uint32, c: (C, W) uint32 -> (B, C) int32 scores.

    B and C may be arbitrary (a serving request batch, C=10 classes):
    operands are zero-padded up to the block grid and the result is
    sliced back — padded rows cost grid cells but never leak scores.
    Small B / C shrink their tile to the padded extent (one block);
    ``block_c`` must be a multiple of 128 for stores larger than it.
    """
    b, w = q_words.shape
    c, w2 = c_words.shape
    assert w == w2
    bt, ct = min(block_b, round_up(b, 8)), min(block_c, round_up(c, 8))
    bp, cp = round_up(b, bt), round_up(c, ct)
    if bp != b:
        q_words = jnp.pad(q_words, ((0, bp - b), (0, 0)))
    if cp != c:
        c_words = jnp.pad(c_words, ((0, cp - c), (0, 0)))

    out = pl.pallas_call(
        functools.partial(_hamming_kernel, d=d),
        grid=(bp // bt, cp // ct),
        in_specs=[
            pl.BlockSpec((bt, w), lambda i, j: (i, 0)),
            pl.BlockSpec((ct, w), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bt, ct), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bp, cp), jnp.int32),
        interpret=interpret,
        name="hamming_packed",
        metadata={"hdc_kernel": "hamming_packed"},
    )(q_words, c_words)
    return out[:b, :c]
