"""Jit-ready public wrappers around the Pallas kernels.

Each op pads its operands to the kernel's block grid, launches the
kernel (interpret=True automatically off-TPU so the whole framework
runs/validates on CPU), and slices/corrects the result.  Semantics of
op X match `repro.kernels.ref.X` exactly; tests enforce this across a
shape/dtype sweep.

These ops back the "pallas" backend registered in
`repro.core.encoders` — model code reaches them via
`HDCConfig(backend="pallas")`, never by importing this module directly.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import unary
from repro.kernels import ref
from repro.kernels.bundle_binarize import bundle_binarize_pallas
from repro.kernels.encode_bundle import (
    encode_bundle_dynamic_pallas,
    encode_bundle_pallas,
    encode_tiles,
    fit_bundle_dynamic_pallas,
    fit_bundle_pallas,
    fit_tiles,
)
from repro.kernels.encode_unary_mxu import encode_unary_mxu_pallas
from repro.kernels.hamming_packed import hamming_packed_pallas, round_up as _round_up
from repro.kernels.hamming_topk import hamming_topk_pallas


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def encode_bundle(
    x_q: jax.Array,
    sobol_q: jax.Array,
    *,
    block_b: int = 8,
    block_h: int = 128,
    block_d: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused uHD encode+bundle (VPU compare kernel). (B,H),(H,D) -> (B,D)."""
    if interpret is None:
        interpret = _interpret_default()
    b, h = x_q.shape
    d = sobol_q.shape[-1]
    bp, hp, dp = _round_up(b, block_b), _round_up(h, block_h), _round_up(d, block_d)
    # Padded features use intensity -1 (< every threshold): each contributes
    # exactly -1 per dim, corrected after the kernel.  Padded thresholds use
    # int32 max so they never flip a compare for padded D columns (sliced).
    xp = jnp.pad(x_q.astype(jnp.int32), ((0, bp - b), (0, hp - h)), constant_values=-1)
    sp = jnp.pad(
        sobol_q.astype(jnp.int32),
        ((0, hp - h), (0, dp - d)),
        constant_values=np.iinfo(np.int32).max,
    )
    out = encode_bundle_pallas(
        xp, sp, block_b=block_b, block_h=block_h, block_d=block_d, interpret=interpret
    )
    return out[:b, :d] + (hp - h)


def encode_bundle_dynamic(
    x_q: jax.Array,
    direction: jax.Array,
    d: int,
    *,
    levels: int | None = None,
    n_levels: int | None = None,
    skip: int = 1,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused encode+bundle with in-kernel Sobol generation (no HBM table).

    direction: (H, 32) uint direction integers.  With `levels` given they
    are the raw 32-bit integers from `sobol.direction_matrix(H)` and the
    generated points are right-shifted to [0, levels) in-kernel; with
    ``levels=None`` they are already M-bit quantized
    (`sobol.quantized_direction_matrix`) and used as-is — exact either
    way, since right-shift distributes over XOR.  `n_levels` is the
    number of threshold values, the configuration's level count: the
    kernel contracts one level per slice (DESIGN.md §9).  It defaults
    to `levels` and is required with pre-quantized directions.  `skip`
    must match the table's ``sobol_skip``; then the result equals
    ``encode_bundle(x_q, quantized_sobol_table)`` bit-for-bit.  Tiles
    follow from the shapes (`encode_tiles`).
    """
    if interpret is None:
        interpret = _interpret_default()
    n_levels = levels if n_levels is None else n_levels
    if n_levels is None:
        raise ValueError("pre-quantized directions need n_levels, the level count")
    b, h = x_q.shape
    shift = 0 if levels is None else 32 - (int(levels).bit_length() - 1)
    block_b, block_d = encode_tiles(b, h, d, n_levels)
    bp, hp, dp = _round_up(b, block_b), _round_up(h, 128), _round_up(d, block_d)
    # Padded features get intensity -1 and zero direction vectors, so
    # every generated threshold there is 0 and -1 >= l holds for no
    # level: they never count.  Padded rows and columns are sliced off.
    xp = jnp.pad(x_q.astype(jnp.int32), ((0, bp - b), (0, hp - h)), constant_values=-1)
    dirp = jnp.pad(direction.astype(jnp.uint32), ((0, hp - h), (0, 0)))
    out = encode_bundle_dynamic_pallas(
        xp, dirp, dp, n_levels, h=h, shift=shift, skip=skip,
        block_b=block_b, block_d=block_d, interpret=interpret,
    )
    return out[:b, :d]


def _padded_class_onehot(labels: jax.Array, c_pad: int, b_pad: int) -> jax.Array:
    """(B,) labels -> batch-major (b_pad, c_pad) int32 indicator, the
    transpose of ref.class_onehot (the fit kernels block it (bt, cp)).

    Padded batch rows carry label -1 and padded class columns match no
    real label, so both drop out with zero weight — the same
    out-of-range drop contract as the unpadded indicator.
    """
    lp = jnp.pad(
        labels.astype(jnp.int32), (0, b_pad - labels.shape[0]), constant_values=-1
    )
    return ref.class_onehot(lp, c_pad).T


def fit_bundle(
    x_q: jax.Array,
    sobol_q: jax.Array,
    labels: jax.Array,
    n_classes: int,
    levels: int,
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused training step over a threshold table. (B,H),(H,D),(B,) -> (C,D).

    Semantics = `ref.fit_bundle` for thresholds in [0, levels)
    (integer-exact class sums; the (B, D) hypervector batch never
    exists).  The kernel counts, per class, the (image, feature) pairs
    at or above each threshold; a class of n_c images then sums to
    2 * count - h * n_c.  Padded features match no level and padded
    batch rows and classes carry zero one-hot weight, so neither
    counts.  Tiles follow from B and D (`fit_tiles`).
    """
    if interpret is None:
        interpret = _interpret_default()
    b, h = x_q.shape
    d = sobol_q.shape[-1]
    block_b, block_d = fit_tiles(b, h, d)
    bp, hp, dp = _round_up(b, block_b), _round_up(h, 128), _round_up(d, block_d)
    cp = _round_up(max(n_classes, 16), 16)
    xp = jnp.pad(x_q.astype(jnp.int32), ((0, bp - b), (0, hp - h)), constant_values=-1)
    sp = jnp.pad(
        sobol_q,
        ((0, hp - h), (0, dp - d)),
        constant_values=np.iinfo(sobol_q.dtype).max,
    )
    oh = _padded_class_onehot(labels, cp, bp)
    counts = fit_bundle_pallas(
        xp, sp, oh.T, levels, block_b=block_b, block_d=block_d, interpret=interpret
    )
    n_c = oh[:, :n_classes].sum(axis=0, dtype=jnp.int32)
    return 2 * counts[:n_classes, :d] - h * n_c[:, None]


def fit_bundle_dynamic(
    x_q: jax.Array,
    direction: jax.Array,
    labels: jax.Array,
    n_classes: int,
    d: int,
    *,
    levels: int | None = None,
    skip: int | jax.Array = 1,
    block_b: int = 8,
    block_h: int = 128,
    block_d: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused table-free training step: in-kernel Sobol generation + encode
    + per-class bundling.  Semantics = `ref.fit_bundle_dynamic`.

    `skip` may be a traced scalar (D-sharded training passes
    ``sobol_skip + axis_index * d_local``); it rides into the kernel as
    a (1, 1) runtime operand, not a compile-time constant.  Padding
    contracts are those of `encode_bundle_dynamic` (zero direction rows
    for padded features) plus the per-class (hp - h) * count_c
    correction of `fit_bundle`.
    """
    if interpret is None:
        interpret = _interpret_default()
    b, h = x_q.shape
    shift = 0 if levels is None else 32 - (int(levels).bit_length() - 1)
    bp, hp, dp = _round_up(b, block_b), _round_up(h, block_h), _round_up(d, block_d)
    cp = _round_up(max(n_classes, 8), 8)
    xp = jnp.pad(x_q.astype(jnp.int32), ((0, bp - b), (0, hp - h)), constant_values=-1)
    dirp = jnp.pad(direction.astype(jnp.uint32), ((0, hp - h), (0, 0)))
    oh = _padded_class_onehot(labels, cp, bp)
    out = fit_bundle_dynamic_pallas(
        xp, dirp, oh, skip, dp, shift=shift, block_b=block_b, block_h=block_h,
        block_d=block_d, interpret=interpret,
    )
    counts = oh[:, :n_classes].sum(axis=0, dtype=jnp.int32)
    return out[:n_classes, :d] + (hp - h) * counts[:, None]


def encode_unary_mxu(
    x_q: jax.Array,
    sobol_q: jax.Array,
    levels: int,
    *,
    block_b: int = 128,
    block_d: int = 128,
    block_k: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """MXU-unary encode: thermometer/one-hot binary matmul. -> (B, D) int32."""
    if interpret is None:
        interpret = _interpret_default()
    b, h = x_q.shape
    d = sobol_q.shape[-1]
    u = unary.to_thermometer(x_q + 1, levels).reshape(b, h * levels)
    onehot = jax.nn.one_hot(sobol_q, levels, axis=1, dtype=jnp.bfloat16)
    o = onehot.reshape(h * levels, d)
    k = h * levels
    bp, dp, kp = _round_up(b, block_b), _round_up(d, block_d), _round_up(k, block_k)
    up = jnp.pad(u.astype(jnp.bfloat16), ((0, bp - b), (0, kp - k)))
    op = jnp.pad(o, ((0, kp - k), (0, dp - d)))
    out = encode_unary_mxu_pallas(
        up, op, h, block_b=block_b, block_d=block_d, block_k=block_k, interpret=interpret
    )
    return out[:b, :d]


def bundle_binarize(
    hvs: jax.Array,
    labels: jax.Array,
    n_classes: int,
    *,
    binarize: bool = True,
    block_c: int = 8,
    block_d: int = 512,
    block_b: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Class bundling + concurrent binarization. (B,D),(B,) -> (C,D)."""
    if interpret is None:
        interpret = _interpret_default()
    b, d = hvs.shape
    onehot = jax.nn.one_hot(labels, n_classes, dtype=jnp.float32).T  # (C, B)
    cp, dp, bp = (
        _round_up(n_classes, block_c),
        _round_up(d, block_d),
        _round_up(b, block_b),
    )
    # Padded batch rows have zero one-hot weight; padded classes/dims sliced.
    hp = jnp.pad(hvs.astype(jnp.int32), ((0, bp - b), (0, dp - d)))
    lp = jnp.pad(onehot, ((0, cp - n_classes), (0, bp - b)))
    out = bundle_binarize_pallas(
        hp,
        lp,
        binarize=binarize,
        block_c=block_c,
        block_d=block_d,
        block_b=block_b,
        interpret=interpret,
    )
    return out[:n_classes, :d]


def hamming_packed(
    q_words: jax.Array,
    c_words: jax.Array,
    d: int,
    *,
    block_b: int = 128,
    block_c: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Packed ±1 similarity. (B,W),(C,W) uint32 -> (B,C) int32."""
    if interpret is None:
        interpret = _interpret_default()
    # padding to the block grid happens inside hamming_packed_pallas
    return hamming_packed_pallas(
        q_words, c_words, d, block_b=block_b, block_c=block_c, interpret=interpret
    )


def hamming_topk(
    q_words: jax.Array,
    c_words: jax.Array,
    d: int,
    k: int,
    *,
    block_b: int = 128,
    block_c: int = 256,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Streaming packed top-k retrieval. (B,W),(C,W) uint32 ->
    ((B,k), (B,k)) int32 (indices, Hamming distances), each row
    ascending by (distance, index) — lowest index wins ties.
    Semantics = `ref.hamming_topk_oracle` exactly.
    """
    if interpret is None:
        interpret = _interpret_default()
    # tile choice and padding to the block grid happen inside
    # hamming_topk_pallas
    return hamming_topk_pallas(
        q_words, c_words, d, k, block_b=block_b, block_c=block_c,
        interpret=interpret,
    )


__all__ = [
    "encode_bundle",
    "encode_bundle_dynamic",
    "fit_bundle",
    "fit_bundle_dynamic",
    "encode_unary_mxu",
    "bundle_binarize",
    "hamming_packed",
    "hamming_topk",
    "ref",
]
