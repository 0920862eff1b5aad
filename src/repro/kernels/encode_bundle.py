"""Fused uHD encode+bundle Pallas kernels (the paper's core operation).

Each computes hv[b,d] = sum_h (2*[x[b,h] >= S[h,d]] - 1) without ever
materializing the (B, H, D) level-hypervector tensor in HBM — the TPU
analogue of the paper's multiplier-less, position-free encoding
(contributions 1-2).

`encode_bundle` (over a stored (H, D) threshold table) compares on the
VPU: grid (B/bt, D/dt, H/ht); the H axis is the reduction — the output
block index_map ignores it, so the accumulator block stays resident in
VMEM across the H sweep (initialized at h==0).  The compare broadcast
(bt, ht, dt) lives entirely in VREG/VMEM: 8*128*512*4B ≈ 2 MiB.

The thresholds take values in [0, L) for L = `levels`, so the compare
splits by level:

    [x >= S[h,d]] = sum_{l<L} [x >= l] * [S[h,d] == l]

which turns the compare-and-count into 0/1 int8 contractions on the
MXU, int32 results, exact (DESIGN.md §9).

`encode_bundle_dynamic` contracts each image this way, with no table:
count[b,d] = sum_l [x >= l] @ [S == l], hv = 2 * count - H.  Its grid
is (D/dt, L/16, B/bt), the whole padded H axis in every block.  At the
first step of each D tile the (Hp, dt) Sobol tile is generated in VMEM
from the (H, 32) direction matrix (Gray-code XOR over the direction
bits that a point index below skip + D can set: 14 at D = 8192); at
the first batch step of each grid row its level one-hots [S == l],
16 int8 (Hp, dt) slices, go to a VMEM scratch and stay there for the
batch sweep, and every batch step contracts the int8 thermometer
slices [x >= l] (bt, Hp) with them.  Neither the (H, D) table nor its
one-hots ever touch HBM — the TPU mapping of the paper's "dynamic
generation instead of stored tables" theme; the HBM traffic is the
quantized inputs, the directions and the (B, D) hypervectors.  Tiles
follow from (B, H, D, L) alone (`encode_tiles`); rows past the first
16 levels (L > 16) write partial counts that ops.py sums.  It is the
"pallas" backend of the "uhd_dynamic" encoder (ops.encode_bundle_dynamic).

The `fit_bundle*` kernels below fuse one more stage: per-class bundling
(training).  The D axis is outside the batch axis in their grids, so
each (C, dt) class-sum block stays resident in VMEM across the whole
batch sweep,
with *both* batch and feature axes folded into the accumulator.  The
(B, D) hypervector batch therefore never exists in HBM, even tiled:
the only HBM traffic of a training step is the quantized inputs, the
label indicator, the encoder state (threshold table or direction
matrix) and the (C, D) class sums (DESIGN.md §9).

`fit_bundle` (over the table) uses the level split with the batch
first: class sums being linear in the images, the count per class is
sum_l A_l @ [S == l] with A_l = oh^T [x >= l] (Cp, Hp) the class-c
images at or above level l in each feature.  Its grid is (L/16, D/dt,
B/bt): the first D tile's batch sweep folds the batch into A (a VMEM
scratch), the last batch step of each D tile contracts A with the
table tile, 16 levels per grid row (DESIGN.md §9).  `fit_bundle_dynamic`
still compares on the VPU: grid (D/dt, B/bt, H/ht), the (bt, ht, dt)
compare cube reduced over sublanes.

TPU block rules shape every tile here: the last two dims of a block
are multiples of (8, 128) or equal the array's own dims.  So the H
tile is 128 lanes (callers pad H, e.g. 784 -> 896, and correct), and
`fit_bundle_dynamic` takes the label indicator batch-major, (B, cp)
blocked (bt, cp) with cp the whole padded class axis; `fit_bundle`
takes it transposed, (Cp, B) blocked (Cp, bt), bt a multiple of 128.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.hamming_packed import round_up

# levels per grid row of the MXU kernels, unrolled in their bodies: the
# code and the VMEM scratches stay the size of the paper's 16 levels at
# any level count
LEVEL_CHUNK = 16

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


def encode_tiles(b: int, h: int, d: int, levels: int) -> tuple[int, int]:
    """(bt, dt) of `encode_bundle_dynamic_pallas` for B images of H
    features over D dimensions at `levels` threshold values.

    The whole padded feature axis rides in every block.  dt is the
    widest of 512, 256, 128 lanes whose VMEM-resident thresholds, an
    int32 (Hp, dt) tile and its int8 one-hot slice for each level of a
    grid row, stay within 9 MiB (less only for a narrower D).  The
    batch splits into the fewest tiles of at most 256 rows and 2**18
    elements, a multiple of 32 (the int8 sublane tile of the
    thermometer), so a ragged last step pads little.  Up to 4096
    features fit a v5e's default scoped VMEM.
    """
    hp, chunk = round_up(h, 128), min(levels, LEVEL_CHUNK)
    dt = 512
    while dt > 128 and (chunk + 4) * hp * dt > 9 << 20:
        dt //= 2
    cap = min(256, max(32, (1 << 18) // hp // 32 * 32))
    bt = round_up(pl.cdiv(b, pl.cdiv(b, cap)), 32)
    return bt, min(dt, round_up(d, 128))


def _encode_bundle_dyn_kernel(
    x_ref, dir_ref, o_ref, s_ref, oh_ref, *, h: int, shift: int, skip: int
):
    """x (bt, hp) i32, dir (hp, n_bits) u32 -> o (1, bt, dt) i32.

    o[b, d] = 2 * #{h : first <= S[h,d] < first + chunk, x[b,h] >= S[h,d]}
    over the levels [first, first + chunk) of this grid row, less `h`
    in the first row, so the rows sum to the hypervector.  At the first
    step of each D tile the (hp, dt) Sobol tile for points
    [skip + j*dt, skip + (j+1)*dt) is generated from the direction
    matrix (Gray-code XOR, then `shift` to the levels) into s_ref; at
    the first batch step of each level row its level one-hots [S == l]
    go to oh_ref (chunk, hp, dt) int8 and stay for the batch sweep.
    Every batch step contracts the thermometer [x >= l] with them on
    the MXU (int32 result, exact).  Padded features (x = -1, zero
    directions so S = 0) match no level.
    """
    j, g, i = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    chunk, dt = oh_ref.shape[0], oh_ref.shape[2]
    first = g * chunk

    def by_rows(body):  # 128 features at a time: temporaries stay small
        def step(r, carry):
            body(pl.ds(pl.multiple_of(r * 128, 128), 128))
            return carry

        jax.lax.fori_loop(0, s_ref.shape[0] // 128, step, 0)

    @pl.when((g == 0) & (i == 0))
    def _thresholds():
        def gen(rows):
            s_ref[rows, :] = _sobol_tile(
                dir_ref[rows, :], j * dt + skip, dt, shift, dir_ref.shape[1]
            )

        by_rows(gen)

    @pl.when(i == 0)
    def _one_hots():
        def split(rows):
            s = s_ref[rows, :]
            for t in range(chunk):
                oh_ref[t, rows, :] = (s == first + t).astype(jnp.int8)

        by_rows(split)

    x = x_ref[...]
    acc = jnp.zeros(o_ref.shape[1:], jnp.int32)
    for t in range(chunk):
        u = (x >= first + t).astype(jnp.int8)
        acc += jnp.dot(u, oh_ref[t], preferred_element_type=jnp.int32)
    o_ref[0] = 2 * acc - jnp.where(g == 0, h, 0)


def encode_bundle_dynamic_pallas(
    x_q: jax.Array,
    direction: jax.Array,
    d: int,
    levels: int,
    *,
    h: int,
    shift: int = 0,
    skip: int = 1,
    block_b: int,
    block_d: int,
    interpret: bool = False,
) -> jax.Array:
    """Fused encode+bundle with in-kernel Sobol generation, on the MXU.

    x_q: (B, Hp) int32, padded features -1; direction: (Hp, 32) uint
    direction integers, zero rows for padded features (raw 32-bit with
    ``shift = 32 - M``, or M-bit pre-quantized with ``shift = 0``), the
    generated thresholds lying in [0, levels); `d` = hypervector
    dimensionality (number of Sobol points generated), `skip` =
    leading points dropped (``sobol_skip``), `h` = real features.
    Requires Hp % 128 == 0 and B/D divisible by their blocks (ops.py
    pads and takes the blocks from `encode_tiles`).  HBM traffic is the
    inputs, O(Hp * 32) of directions and the (B, D) int32 hypervectors:
    neither the threshold table nor its one-hots leave VMEM.
    """
    b, hp = x_q.shape
    assert direction.shape[0] == hp
    assert b % block_b == 0 and hp % 128 == 0 and d % block_d == 0
    chunk = min(levels, LEVEL_CHUNK)
    assert levels % chunk == 0
    # gray(p) < 2**n for p < 2**n: higher direction bits never enter
    n_bits = min(direction.shape[1], (skip + d - 1).bit_length())

    out = pl.pallas_call(
        functools.partial(_encode_bundle_dyn_kernel, h=h, shift=shift, skip=skip),
        grid=(d // block_d, levels // chunk, b // block_b),
        in_specs=[
            pl.BlockSpec((block_b, hp), lambda j, g, i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.VMEM),  # whole, one buffer
        ],
        out_specs=pl.BlockSpec((1, block_b, block_d), lambda j, g, i: (g, i, j)),
        out_shape=jax.ShapeDtypeStruct((levels // chunk, b, d), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((hp, block_d), jnp.int32),
            pltpu.VMEM((chunk, hp, block_d), jnp.int8),
        ],
        interpret=interpret,
        name="encode_bundle_dynamic",
        metadata={"hdc_kernel": "encode_bundle_dynamic"},
    )(x_q.astype(jnp.int32), direction[:, :n_bits].astype(jnp.uint32))
    return out.sum(axis=0)


def fit_tiles(b: int, h: int, d: int) -> tuple[int, int]:
    """(bt, dt) of `fit_bundle_pallas` for B images of H features over
    D dimensions.

    The whole padded feature axis rides in every block, so a tile's
    rows are at most 512 and hold at most 2**19 elements (512 up to
    1024 features), a multiple of 128 (the lane axis of the transposed
    label indicator) and at least 128.  The batch splits into the
    fewest such tiles, so a ragged last step pads little; dt is the
    same cap, less only for a narrower D.
    """
    cap = min(512, max(128, (1 << 19) // round_up(h, 128) // 128 * 128))
    bt = round_up(pl.cdiv(b, pl.cdiv(b, cap)), 128)
    return bt, min(cap, round_up(d, 128))


def fit_digits(bp: int) -> int:
    """7-bit digits that hold a count of up to `bp` images, rounded up
    to an even number so the stacked digit rows fill int8 tiles."""
    return round_up(pl.cdiv(bp.bit_length(), 7), 2)


def _fit_bundle_kernel(x_ref, s_ref, oht_ref, o_ref, a_ref, *, chunk: int, digits: int):
    """x (bt, hp) i32, s (hp, dt), oht (cp, bt) bf16 -> o (1, cp, dt) i32.

    o[c, d] = #{(b of class c, h) : x[b,h] >= s[h,d]} over the levels
    [first, first + chunk) of this grid row (module docstring).  The
    first D tile's batch sweep folds the batch into a_ref (chunk, cp,
    hp) i32: A_l = oh^T @ [x >= l] (0/1 bf16 operands, f32 result,
    exact: at most bt per tile).  The last batch step of every D tile
    contracts A_l, split into `digits` 7-bit int8 digits stacked along
    the rows, with [s == l] on the MXU (int32 result, exact).
    Padded features (x = -1, s = the dtype's max) match no level.
    """
    j, i = pl.program_id(1), pl.program_id(2)
    first = pl.program_id(0) * chunk
    cp = a_ref.shape[1]

    @pl.when((j == 0) & (i == 0))
    def _zero():
        a_ref[...] = jnp.zeros_like(a_ref)

    @pl.when(j == 0)
    def _fold_batch():
        x, oht = x_ref[...], oht_ref[...]
        for t in range(chunk):
            u = (x >= first + t).astype(jnp.bfloat16)
            a_ref[t] += jnp.dot(oht, u, preferred_element_type=jnp.float32).astype(
                jnp.int32
            )

    @pl.when(i == pl.num_programs(2) - 1)
    def _contract_table():
        s = s_ref[...].astype(jnp.int32)
        acc = jnp.zeros((digits * cp, s.shape[1]), jnp.int32)
        for t in range(chunk):
            a = a_ref[t]
            lhs = jnp.concatenate([(a >> 7 * k) & 127 for k in range(digits)])
            rhs = (s == first + t).astype(jnp.int8)
            acc += jnp.dot(lhs.astype(jnp.int8), rhs, preferred_element_type=jnp.int32)
        o_ref[0] = sum(acc[k * cp : (k + 1) * cp] << 7 * k for k in range(digits))


def fit_bundle_pallas(
    x_q: jax.Array,
    sobol_q: jax.Array,
    onehot_t: jax.Array,
    levels: int,
    *,
    block_b: int,
    block_d: int,
    interpret: bool = False,
) -> jax.Array:
    """Per-class threshold counts over a table, the MXU half of a fit.

    x_q: (B, Hp) int32, sobol_q: (Hp, D) with thresholds in [0, levels)
    (int8 or int32; padding holds the dtype's max), onehot_t: (Cp, B)
    0/1 class indicator.  Requires Hp % 128 == 0 and B/D divisible by
    their blocks (ops.py pads, takes the blocks from `fit_tiles` and
    turns counts into class sums).  Hp rides whole in every block: up
    to 4096 features fit a v5e's VMEM.  Returns (Cp, D) int32 counts
    #{(b of class c, h) : x[b,h] >= S[h,d]}.
    """
    b, h = x_q.shape
    h2, d = sobol_q.shape
    cp = onehot_t.shape[0]
    assert h == h2 and onehot_t.shape[1] == b
    assert b % block_b == 0 and h % 128 == 0 and d % block_d == 0
    assert jnp.iinfo(sobol_q.dtype).max >= levels
    chunk = min(levels, LEVEL_CHUNK)
    assert levels % chunk == 0
    nb = b // block_b

    def batch_block(j, i):  # past the first D tile the batch stays put
        return jnp.where(j == 0, i, nb - 1)

    out = pl.pallas_call(
        functools.partial(_fit_bundle_kernel, chunk=chunk, digits=fit_digits(b)),
        grid=(levels // chunk, d // block_d, nb),
        in_specs=[
            pl.BlockSpec((block_b, h), lambda g, j, i: (batch_block(j, i), 0)),
            pl.BlockSpec((h, block_d), lambda g, j, i: (0, j)),
            pl.BlockSpec((cp, block_b), lambda g, j, i: (0, batch_block(j, i))),
        ],
        out_specs=pl.BlockSpec((1, cp, block_d), lambda g, j, i: (g, 0, j)),
        out_shape=jax.ShapeDtypeStruct((levels // chunk, cp, d), jnp.int32),
        scratch_shapes=[pltpu.VMEM((chunk, cp, h), jnp.int32)],
        interpret=interpret,
        name="fit_bundle",
        metadata={"hdc_kernel": "fit_bundle"},
    )(x_q.astype(jnp.int32), sobol_q, onehot_t.astype(jnp.bfloat16))
    return out.sum(axis=0)


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
