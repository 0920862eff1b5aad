"""MXU-unary encode kernel: threshold-compare-accumulate as binary matmul.

TPU adaptation of uHD contribution 3 (unary bit-streams).  The inclusive
thermometer code U (B, H*xi) and the one-hot threshold matrix O (H*xi, D)
are binary, so

    count_ge = U @ O        (exact in bf16; values <= H*xi < 2^24)
    hv       = 2*count - H  (fused epilogue)

runs on the 128x128 MXU at matmul throughput instead of the VPU.  This
is a classic fp32-accumulator Pallas matmul: grid (B/bt, D/dt, K/kt),
accumulator scratch persists across the K sweep, epilogue applied at the
last K step before the single HBM write-back (the paper's "concurrent
binarization" idea generalized to 'concurrent affine epilogue').
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _mxu_kernel(u_ref, o_ref, out_ref, *, h: int, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # Binary operands: the fp32-accumulated MXU dot is integer-exact.
    # The count accumulates in the (VMEM-resident) int32 output block.
    part = jax.lax.dot(u_ref[...], o_ref[...], preferred_element_type=jnp.float32)
    out_ref[...] += part.astype(jnp.int32)

    @pl.when(k == n_k - 1)
    def _epilogue():
        out_ref[...] = 2 * out_ref[...] - h


def encode_unary_mxu_pallas(
    u: jax.Array,
    onehot_s: jax.Array,
    h: int,
    *,
    block_b: int = 128,
    block_d: int = 128,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """u: (B, K) bf16 thermometer; onehot_s: (K, D) bf16 one-hot.

    Returns (B, D) int32 hypervectors.  Dims must divide the blocks (the
    ops.py wrapper pads with zero rows/cols, which contribute 0 to the
    count and are sliced away).
    """
    b, kdim = u.shape
    k2, d = onehot_s.shape
    assert kdim == k2
    assert b % block_b == 0 and d % block_d == 0 and kdim % block_k == 0
    n_k = kdim // block_k

    return pl.pallas_call(
        functools.partial(_mxu_kernel, h=h, n_k=n_k),
        grid=(b // block_b, d // block_d, n_k),
        in_specs=[
            pl.BlockSpec((block_b, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_d), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((block_b, block_d), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, d), jnp.int32),
        interpret=interpret,
        name="encode_unary_mxu",
        metadata={"hdc_kernel": "encode_unary_mxu"},
    )(u.astype(jnp.bfloat16), onehot_s.astype(jnp.bfloat16))
