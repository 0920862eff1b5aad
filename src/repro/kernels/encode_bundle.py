"""Fused uHD encode+bundle Pallas kernel (the paper's core operation).

Computes hv[b,d] = sum_h (2*[x[b,h] >= S[h,d]] - 1) without ever
materializing the (B, H, D) level-hypervector tensor in HBM — the TPU
analogue of the paper's multiplier-less, position-free encoding
(contributions 1-2): the only HBM traffic is the quantized inputs and
the (B, D) accumulator.

Tiling: grid (B/bt, D/dt, H/ht); the H axis is the reduction — the
output block index_map ignores it, so the accumulator block stays
resident in VMEM across the H sweep (initialized at h==0).  The compare
broadcast (bt, ht, dt) lives entirely in VREG/VMEM; ht*dt is chosen so
the working set (x tile + sobol tile + compare cube + acc) fits VMEM
comfortably: 8*128*512*4B ≈ 2 MiB.

A `generate_sobol` variant regenerates the Sobol tile *inside* the
kernel from the (H, 32) direction matrix (Gray-code XOR), eliminating
the (H, D) threshold table from HBM entirely — the TPU mapping of the
paper's "dynamic generation instead of stored tables" theme.  See
ops.encode_bundle_dynamic, registered as the "pallas" backend of the
"uhd_dynamic" encoder.

The `fit_bundle*` kernels below fuse one more stage: per-class bundling
(training).  Their grid is (D/dt, B/bt, H/ht) — the D axis outermost so
each (C, dt) class-sum block stays resident in VMEM across the full
(B, H) sweep, with *both* batch and feature axes folded into the
accumulator.  The (B, D) hypervector batch therefore never exists in
HBM, even tiled: the only HBM traffic of a training step is the
quantized inputs, the label indicator, the encoder state (threshold
tile or direction matrix) and the (C, D) class sums (DESIGN.md §9).

TPU block rules shape every tile here: the last two dims of a block
are multiples of (8, 128) or equal the array's own dims.  So the H
tile is 128 lanes (callers pad H, e.g. 784 -> 896, and correct), and
the fit kernels take the label indicator batch-major, (B, cp) blocked
(bt, cp) with cp the whole padded class axis.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _sobol_tile(dirs, first, block_d: int, shift: int, n_bits: int):
    """In-kernel (ht, dt) quantized Sobol tile for points [first,
    first + block_d): point p = XOR of the direction columns selected
    by the bits of gray(p), right-shifted by `shift`.  `first` may be a
    runtime scalar."""
    idx = jax.lax.broadcasted_iota(jnp.uint32, (1, block_d), 1) + jnp.asarray(
        first
    ).astype(jnp.uint32)
    gray = idx ^ (idx >> jnp.uint32(1))
    acc = jnp.zeros((dirs.shape[0], block_d), jnp.uint32)
    for bit in range(n_bits):
        mask = (gray >> jnp.uint32(bit)) & jnp.uint32(1)  # (1, dt)
        acc = acc ^ (mask * dirs[:, bit : bit + 1])
    return (acc >> jnp.uint32(shift)).astype(jnp.int32)


def _bundle_into(o_ref, oh, hv):
    """o (cp, dt) += oh^T @ hv in int32 on the VPU: oh (bt, cp) {0,1}
    batch-major indicator, hv (bt, dt) hypervector slab.  One masked
    sublane reduction per class row (exact; cp is small)."""
    for c in range(oh.shape[1]):
        o_ref[c : c + 1, :] += (oh[:, c : c + 1] * hv).sum(
            axis=0, keepdims=True, dtype=jnp.int32
        )


def _encode_bundle_kernel(x_ref, s_ref, o_ref, *, ht: int):
    """x (bt, ht) int32, s (ht, dt) int32 -> accumulate o (bt, dt) int32."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    ge = x_ref[...][:, :, None] >= s_ref[...][None, :, :]  # (bt, ht, dt)
    contrib = 2 * ge.sum(axis=1, dtype=jnp.int32) - ht
    o_ref[...] += contrib


def encode_bundle_pallas(
    x_q: jax.Array,
    sobol_q: jax.Array,
    *,
    block_b: int = 8,
    block_h: int = 128,
    block_d: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Launch the fused encode+bundle kernel.

    Requires B % block_b == H % block_h == D % block_d == 0 (the ops.py
    wrapper pads and corrects).  Returns (B, D) int32.
    """
    b, h = x_q.shape
    h2, d = sobol_q.shape
    assert h == h2, (h, h2)
    assert b % block_b == 0 and h % block_h == 0 and d % block_d == 0

    grid = (b // block_b, d // block_d, h // block_h)
    return pl.pallas_call(
        functools.partial(_encode_bundle_kernel, ht=block_h),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, block_h), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_h, block_d), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((block_b, block_d), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, d), jnp.int32),
        interpret=interpret,
        name="encode_bundle",
        metadata={"hdc_kernel": "encode_bundle"},
    )(x_q.astype(jnp.int32), sobol_q.astype(jnp.int32))


def _encode_bundle_dyn_kernel(
    x_ref, dir_ref, o_ref, *, ht: int, block_d: int, shift: int, skip: int, n_bits: int
):
    """Sobol-free variant: thresholds are generated in VMEM from the
    direction matrix (dir_ref: (ht, n_bits) uint32) via Gray-code XOR.
    `shift` right-shifts raw 32-bit Sobol integers to quantized levels
    (0 when the direction numbers are pre-quantized).  `skip` offsets
    the point index so the generated sequence matches a table built
    with the same ``sobol_skip`` bit-for-bit.
    """
    k = pl.program_id(2)
    j = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    # Generate the (ht, dt) quantized Sobol tile for points
    # [skip + j*dt, skip + (j+1)*dt) — `skip` drops the leading points,
    # point 0 (all zeros) being degenerate, exactly like the table path.
    s = _sobol_tile(dir_ref[...], j * block_d + skip, block_d, shift, n_bits)

    ge = x_ref[...][:, :, None] >= s[None, :, :]
    o_ref[...] += 2 * ge.sum(axis=1, dtype=jnp.int32) - ht


def encode_bundle_dynamic_pallas(
    x_q: jax.Array,
    direction: jax.Array,
    d: int,
    *,
    shift: int = 0,
    skip: int = 1,
    block_b: int = 8,
    block_h: int = 128,
    block_d: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Fused encode+bundle with in-kernel Sobol generation.

    x_q: (B, H) int32; direction: (H, n_bits) uint direction integers
    (raw 32-bit with ``shift = 32 - M``, or M-bit pre-quantized with
    ``shift = 0``); `d` = hypervector dimensionality (number of Sobol
    points generated), `skip` = leading points dropped (``sobol_skip``).
    HBM traffic drops from O(H*D) (threshold table) to O(H*n_bits).
    """
    b, h = x_q.shape
    h2, n_bits = direction.shape
    assert h == h2
    assert b % block_b == 0 and h % block_h == 0 and d % block_d == 0

    grid = (b // block_b, d // block_d, h // block_h)
    return pl.pallas_call(
        functools.partial(
            _encode_bundle_dyn_kernel,
            ht=block_h,
            block_d=block_d,
            shift=shift,
            skip=skip,
            n_bits=n_bits,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, block_h), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_h, n_bits), lambda i, j, k: (k, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, block_d), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, d), jnp.int32),
        interpret=interpret,
        name="encode_bundle_dynamic",
        metadata={"hdc_kernel": "encode_bundle_dynamic"},
    )(x_q.astype(jnp.int32), direction.astype(jnp.uint32))


def _fit_bundle_kernel(x_ref, s_ref, oh_ref, o_ref, *, ht: int):
    """x (bt, ht) i32, s (ht, dt) i32, oh (bt, cp) i32 -> acc o (cp, dt).

    The (bt, dt) hypervector slab lives only in VREG/VMEM; it is
    contracted against the label indicator in int32 (exact) before the
    next grid step overwrites it.
    """
    i = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when((i == 0) & (k == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    ge = x_ref[...][:, :, None] >= s_ref[...][None, :, :]  # (bt, ht, dt)
    _bundle_into(o_ref, oh_ref[...], 2 * ge.sum(axis=1, dtype=jnp.int32) - ht)


def fit_bundle_pallas(
    x_q: jax.Array,
    sobol_q: jax.Array,
    onehot: jax.Array,
    *,
    block_b: int = 8,
    block_h: int = 128,
    block_d: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Fused encode+bundle+class-sum over a threshold table.

    x_q: (B, H) int32, sobol_q: (H, D) int32, onehot: (B, C) int32.
    Requires B/H/D divisible by their blocks (ops.py pads + corrects);
    C rides whole in one block.  Returns (C, D) int32 class sums.
    """
    b, h = x_q.shape
    h2, d = sobol_q.shape
    c = onehot.shape[1]
    assert h == h2 and onehot.shape[0] == b
    assert b % block_b == 0 and h % block_h == 0 and d % block_d == 0

    grid = (d // block_d, b // block_b, h // block_h)
    return pl.pallas_call(
        functools.partial(_fit_bundle_kernel, ht=block_h),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, block_h), lambda j, i, k: (i, k)),
            pl.BlockSpec((block_h, block_d), lambda j, i, k: (k, j)),
            pl.BlockSpec((block_b, c), lambda j, i, k: (i, 0)),
        ],
        out_specs=pl.BlockSpec((c, block_d), lambda j, i, k: (0, j)),
        out_shape=jax.ShapeDtypeStruct((c, d), jnp.int32),
        interpret=interpret,
        name="fit_bundle",
        metadata={"hdc_kernel": "fit_bundle"},
    )(x_q.astype(jnp.int32), sobol_q.astype(jnp.int32), onehot.astype(jnp.int32))


def _fit_bundle_dyn_kernel(
    x_ref, dir_ref, oh_ref, skip_ref, o_ref, *, ht: int, block_d: int, shift: int,
    n_bits: int,
):
    """Table-free fit_bundle: thresholds generated in VMEM per D-tile.

    `skip_ref` is a (1, 1) int32 *runtime* scalar in SMEM (unlike the static
    `skip` of the encode kernel): under D-axis sharding each shard
    passes ``sobol_skip + axis_index * d_local``, which is traced — so
    the first generated point index must be data, not a compile-time
    constant.
    """
    j = pl.program_id(0)
    i = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when((i == 0) & (k == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    s = _sobol_tile(dir_ref[...], j * block_d + skip_ref[0, 0], block_d, shift, n_bits)
    ge = x_ref[...][:, :, None] >= s[None, :, :]
    _bundle_into(o_ref, oh_ref[...], 2 * ge.sum(axis=1, dtype=jnp.int32) - ht)


def fit_bundle_dynamic_pallas(
    x_q: jax.Array,
    direction: jax.Array,
    onehot: jax.Array,
    skip: jax.Array,
    d: int,
    *,
    shift: int = 0,
    block_b: int = 8,
    block_h: int = 128,
    block_d: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Fused encode+bundle+class-sum with in-kernel Sobol generation.

    x_q: (B, H) int32; direction: (H, n_bits) uint; onehot: (B, C) int32;
    skip: (1, 1) int32 first-point index (may be traced — see the kernel
    docstring).  Returns (C, d) int32 class sums; neither the (H, D)
    threshold table nor the (B, D) hypervector batch ever touches HBM.
    """
    b, h = x_q.shape
    h2, n_bits = direction.shape
    c = onehot.shape[1]
    assert h == h2 and onehot.shape[0] == b
    assert b % block_b == 0 and h % block_h == 0 and d % block_d == 0

    grid = (d // block_d, b // block_b, h // block_h)
    return pl.pallas_call(
        functools.partial(
            _fit_bundle_dyn_kernel,
            ht=block_h,
            block_d=block_d,
            shift=shift,
            n_bits=n_bits,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, block_h), lambda j, i, k: (i, k)),
            pl.BlockSpec((block_h, n_bits), lambda j, i, k: (k, 0)),
            pl.BlockSpec((block_b, c), lambda j, i, k: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((c, block_d), lambda j, i, k: (0, j)),
        out_shape=jax.ShapeDtypeStruct((c, d), jnp.int32),
        interpret=interpret,
        name="fit_bundle_dynamic",
        metadata={"hdc_kernel": "fit_bundle_dynamic"},
    )(
        x_q.astype(jnp.int32),
        direction.astype(jnp.uint32),
        onehot.astype(jnp.int32),
        jnp.asarray(skip, jnp.int32).reshape(1, 1),
    )
