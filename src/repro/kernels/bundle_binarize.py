"""Class bundling with concurrent binarization (uHD contribution 5).

Accumulates image hypervectors into per-class sums and applies the
threshold *inside the kernel epilogue*, so the int32 accumulator never
takes an extra HBM round-trip — the bandwidth analogue of the paper's
TOB masking logic replacing a separate subtractor/comparator stage.

    sums[c, d] = sum_b onehot[c, b] * hv[b, d]      (MXU matmul)
    out[c, d]  = +1 if sums >= 0 else -1            (fused epilogue)

Grid (C/ct, D/dt, B/bt); B is the reduction axis; fp32 accumulation is
exact for counts < 2^24.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _bundle_kernel(lab_ref, hv_ref, out_ref, sum_ref, *, n_b: int, binarize: bool):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        sum_ref[...] = jnp.zeros_like(sum_ref)

    sum_ref[...] += jax.lax.dot(
        lab_ref[...], hv_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(k == n_b - 1)
    def _epilogue():
        s = sum_ref[...]
        if binarize:
            out_ref[...] = jnp.where(s >= 0, 1, -1).astype(out_ref.dtype)
        else:
            out_ref[...] = s.astype(out_ref.dtype)


def bundle_binarize_pallas(
    hvs: jax.Array,
    onehot_labels: jax.Array,
    *,
    binarize: bool = True,
    block_c: int = 8,
    block_d: int = 512,
    block_b: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """hvs: (B, D) int32; onehot_labels: (C, B) float/int {0,1}.

    Returns (C, D) int8 ±1 if binarize else (C, D) int32 raw sums.
    """
    b, d = hvs.shape
    c, b2 = onehot_labels.shape
    assert b == b2
    assert c % block_c == 0 and d % block_d == 0 and b % block_b == 0
    n_b = b // block_b

    out_dtype = jnp.int8 if binarize else jnp.int32
    return pl.pallas_call(
        functools.partial(_bundle_kernel, n_b=n_b, binarize=binarize),
        grid=(c // block_c, d // block_d, n_b),
        in_specs=[
            pl.BlockSpec((block_c, block_b), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_b, block_d), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((block_c, block_d), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((c, d), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_c, block_d), jnp.float32)],
        interpret=interpret,
        name="bundle_binarize",
        metadata={"hdc_kernel": "bundle_binarize"},
    )(onehot_labels.astype(jnp.float32), hvs.astype(jnp.float32))
