"""Hypervector encoders: uHD (Sobol, position-free) and the baseline HDC.

uHD encoding (paper Fig. 2):  pixel/feature h of an image supplies the
quantized intensity x_h; Sobol dimension h supplies D quantized
thresholds S[h, :].  The level hypervector is

    L_h[d] = +1  if x_h >= S[h, d]  else  -1

and the (non-binary) image hypervector is sum_h L_h — **no position
hypervectors, no XOR binding, no multiplications** (contribution 2).

Baseline HDC (paper Fig. 1): per-pixel pseudo-random position vectors
P[h] and shared level vectors L[v] generated by threshold comparison
against pseudo-random numbers; image HV = sum_h P[h] * L[x_h] (bind =
elementwise multiply in ±1 == XOR in {0,1}).

Both encoders are expressed so the hot loop is a single matmul-shaped
contraction over (feature, level) — the MXU-friendly form of the paper's
unary trick (see kernels/encode_unary_mxu.py for the Pallas version):

    count_ge[b, d] = U(x)[b, h*xi] @ onehot(S)[h*xi, d]

where U is the thermometer code.  The pure-jnp implementations below are
the reference semantics; `repro.kernels.ops` provides the Pallas
implementations.  Each function here is registered as a named backend
in `repro.core.encoders` and selected via
`repro.core.registry.resolve_backend` (e.g. ``backend="pallas"``).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import unary


# ---------------------------------------------------------------------------
# Quantization (paper Fig. 3(a): M-bit data and Sobol scalars)
# ---------------------------------------------------------------------------


def quantize_images(images: jax.Array, levels: int, max_val: float = 255.0) -> jax.Array:
    """Quantize intensities in [0, max_val] to int32 levels in [0, levels].

    Note the inclusive upper level: an intensity of max_val must compare
    >= every threshold in [0, levels), matching x/max >= s for s in [0,1).
    """
    x = jnp.clip(images.astype(jnp.float32) / max_val, 0.0, 1.0)
    return jnp.floor(x * levels).astype(jnp.int32)


# ---------------------------------------------------------------------------
# uHD encoder
# ---------------------------------------------------------------------------


def uhd_encode(x_q: jax.Array, sobol_q: jax.Array) -> jax.Array:
    """Position-free Sobol encode+bundle. Reference (pure jnp) semantics.

    Args:
      x_q: (B, H) int32 quantized intensities in [0, levels].
      sobol_q: (H, D) int32 quantized Sobol thresholds in [0, levels).
    Returns:
      (B, D) int32 non-binary image hypervectors, values in [-H, H].
    """
    h = x_q.shape[-1]
    # count_ge[b, d] = #{h : x[b,h] >= S[h,d]};  hv = 2*count - H
    ge = x_q[:, :, None] >= sobol_q[None, :, :]  # (B, H, D) bool
    count = ge.sum(axis=1, dtype=jnp.int32)
    return 2 * count - h


def uhd_encode_blocked(x_q: jax.Array, sobol_q: jax.Array, block_d: int = 2048) -> jax.Array:
    """Memory-bounded uhd_encode: blocks the D axis so the (B, H, Dblk)
    compare intermediate stays small.  Same semantics as uhd_encode."""
    b, h = x_q.shape
    d = sobol_q.shape[-1]
    n_blocks = (d + block_d - 1) // block_d
    pad = n_blocks * block_d - d
    s = jnp.pad(sobol_q, ((0, 0), (0, pad)), constant_values=np.iinfo(np.int32).max)
    s = s.reshape(h, n_blocks, block_d)

    def one(carry, sblk):
        ge = x_q[:, :, None] >= sblk[None, :, :]
        return carry, 2 * ge.sum(axis=1, dtype=jnp.int32) - h

    _, out = jax.lax.scan(one, 0, jnp.moveaxis(s, 1, 0))
    return jnp.moveaxis(out, 0, 1).reshape(b, -1)[:, :d]


def uhd_encode_unary_matmul(x_q: jax.Array, sobol_q: jax.Array, levels: int) -> jax.Array:
    """The MXU-unary formulation (TPU adaptation of contribution 3).

    Thermometer-code the data *inclusively*: U[b, h, v] = (v <= x[b,h]),
    v in [0, levels)  (i.e. the unary stream of x+1).
    One-hot the Sobol thresholds: O[h, v, d] = (S[h,d] == v).
    Then  count_ge[b,d] = sum_{h,v} U[b,h,v] * O[h,v,d] = #{h: S <= x}
    — a single (B, H*levels) @ (H*levels, D) matmul over binary matrices
    (exact in bf16/fp32), which runs on the MXU instead of the VPU.
    """
    b, h = x_q.shape
    d = sobol_q.shape[-1]
    u = unary.to_thermometer(x_q + 1, levels).reshape(b, h * levels)
    onehot = jax.nn.one_hot(sobol_q, levels, axis=1, dtype=jnp.bfloat16)  # (H, levels, D)
    count = jax.lax.dot(
        u.astype(jnp.bfloat16),
        onehot.reshape(h * levels, d),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    return (2 * count - h).astype(jnp.int32)


def uhd_encode_dynamic(
    x_q: jax.Array, direction: jax.Array, d: int, *, skip: int = 1, block_d: int = 512
) -> jax.Array:
    """Table-free uHD encode+bundle: thresholds regenerated per D-tile.

    The paper's *dynamic* generation theme on the host datapath: instead
    of a materialized (H, D) threshold table, each D-tile of quantized
    Sobol thresholds is re-derived from the (H, N_BITS) quantized
    direction matrix (``repro.kernels.ref.sobol_tile`` Gray-code XOR),
    compared, bundled, and discarded — peak transient is (B, H, block_d)
    and the only persistent encoder state is O(H * N_BITS) bytes.
    Bit-identical to :func:`uhd_encode` over the table built with the
    same seed/levels/``sobol_skip``.

    Args:
      x_q: (B, H) int32 quantized intensities in [0, levels].
      direction: (H, N_BITS) M-bit quantized direction integers
        (``sobol.quantized_direction_matrix``).
      d: hypervector dimensionality (number of Sobol points).
      skip: leading Sobol points dropped (``cfg.sobol_skip``).
    """
    from repro.kernels import ref as kref  # pure-jnp building block

    b, h = x_q.shape
    x = x_q[:, :, None].astype(jnp.int32)
    dirs = direction.astype(jnp.uint32)
    n_blocks = -(-d // block_d)
    starts = jnp.uint32(skip) + jnp.arange(n_blocks, dtype=jnp.uint32) * jnp.uint32(
        block_d
    )

    def one(carry, d0):
        s = kref.sobol_tile(dirs, d0, block_d).astype(jnp.int32)
        ge = x >= s[None, :, :]
        return carry, 2 * ge.sum(axis=1, dtype=jnp.int32) - h

    _, out = jax.lax.scan(one, 0, starts)
    return jnp.moveaxis(out, 0, 1).reshape(b, -1)[:, :d]


def uhd_encode_via_unary_comparator(
    x_q: jax.Array, sobol_q: jax.Array, levels: int
) -> jax.Array:
    """Bit-exact functional simulation of the uHD datapath (Figs. 3-4):

    UST fetch -> unary comparator (AND/OR/reduce) -> ±1 -> bundle.
    Slow; used as a cross-oracle in tests to prove the fast paths compute
    exactly the circuit the paper builds.
    """
    ust = unary.unary_stream_table(levels)
    xs = unary.fetch_unary(x_q, ust)  # (B, H, W)
    ss = unary.fetch_unary(sobol_q, ust)  # (H, D, W)
    ge = unary.unary_ge(xs[:, :, None, :], ss[None, :, :, :], levels)  # (B, H, D)
    h = x_q.shape[-1]
    return 2 * ge.sum(axis=1, dtype=jnp.int32) - h


# ---------------------------------------------------------------------------
# Baseline HDC (position x level binding, pseudo-random)
# ---------------------------------------------------------------------------


def make_baseline_codebooks(
    key: jax.Array, n_features: int, d: int, levels: int
) -> tuple[jax.Array, jax.Array]:
    """Pseudo-random position and level hypervectors (paper Fig. 1(a)).

    P: (H, D) ±1 int8 — iid random (R vs t=0.5 comparison).
    L: (levels+1, D) ±1 int8 — level k flips against thresholds
       t_k = k*D/levels compared to R ~ U[0, D): correlated levels, the
       standard comparator-based generation the paper describes.
    """
    kp, kl = jax.random.split(key)
    p = jnp.where(jax.random.uniform(kp, (n_features, d)) > 0.5, -1, 1).astype(jnp.int8)
    r = jax.random.uniform(kl, (d,), minval=0.0, maxval=float(levels + 1))
    ks = jnp.arange(levels + 1, dtype=jnp.float32)[:, None]  # thresholds
    level = jnp.where(r[None, :] > ks, -1, 1).astype(jnp.int8)
    return p, level


def baseline_encode_naive(x_q: jax.Array, p: jax.Array, level: jax.Array) -> jax.Array:
    """Gather-based baseline reference: hv[b] = sum_h P[h] * L[x_q[b,h]].

    Direct transcription of paper Fig. 1 — the oracle the contracted
    matmul form below is tested against.  (B, H, D) transient.
    """
    bound = p[None, :, :].astype(jnp.int32) * level[x_q].astype(jnp.int32)
    return bound.sum(axis=1, dtype=jnp.int32)


def baseline_encode(x_q: jax.Array, p: jax.Array, level: jax.Array) -> jax.Array:
    """Baseline bind+bundle: hv[b] = sum_h P[h] * L[x_q[b,h]].

    Contracted as sum_v onehot(x)[b, h, v] * (P*L[v])[h, d] — one
    (B, H*V) @ (H*V, D) matmul, so baseline and uHD are compared on
    equally-optimized footing (fair Table I/IV comparison).
    """
    b, h = x_q.shape
    n_levels = level.shape[0]
    d = p.shape[-1]
    pl = (p[None, :, :] * level[:, None, :]).astype(jnp.bfloat16)  # (V, H, D)
    onehot = jax.nn.one_hot(x_q, n_levels, dtype=jnp.bfloat16)  # (B, H, V)
    out = jax.lax.dot(
        onehot.transpose(0, 2, 1).reshape(b, n_levels * h),
        pl.reshape(n_levels * h, d),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    return out.astype(jnp.int32)


# ---------------------------------------------------------------------------
# Bundling / binarization
# ---------------------------------------------------------------------------


def bundle_by_class(hvs: jax.Array, labels: jax.Array, n_classes: int) -> jax.Array:
    """Accumulate image hypervectors into per-class sums (paper 'bundling').

    (B, D) int32, (B,) int32 -> (C, D) int32.  Segment-sum via one-hot
    matmul, carried out **in int32** (``preferred_element_type``), so the
    class sums are exact for any batch size.  The previous float32
    einsum was exact only while ``B * max|hv|`` stayed inside float32's
    2^24 integer window — large-batch/production streams silently
    rounded (pinned by
    ``test_bundle_by_class_exact_beyond_float32_window``).  Still
    matmul-shaped: shards cleanly over the batch axes, and the
    cross-device reduction stays a single psum of (C, D).

    Jitted-path label contract: a label outside ``[0, n_classes)``
    one-hots to an all-zero row and is **dropped** from the sums.  The
    host-facing ``HDCModel`` training entry points call
    :func:`validate_labels` before tracing, so a dropped example can
    never silently diverge from the ``n_seen`` counter there.
    """
    onehot = (
        labels.astype(jnp.int32)[:, None]
        == jnp.arange(n_classes, dtype=jnp.int32)[None, :]
    ).astype(jnp.int32)  # (B, C)
    return jnp.einsum(
        "bc,bd->cd", onehot, hvs.astype(jnp.int32),
        preferred_element_type=jnp.int32,
    )


def retrain_update(
    hvs: jax.Array, labels: jax.Array, predicted: jax.Array, n_classes: int,
    max_abs: int,
) -> jax.Array:
    """The retraining fold of one batch (DESIGN.md §15): (C, D) int32

        sum_i (e_{y_i} - e_{yhat_i}) hv_i^T

    — each mispredicted image added to its true class and subtracted
    from the predicted one.  A correct row's signs cancel
    (e_y - e_y = 0), so no separate mispredict mask is needed.

    Exact on the MXU: the (B, C) signs are int8 in {-1, 0, 1}, and each
    hypervector, whose entries lie in [-max_abs, max_abs], is split into
    base-128 int8 digits (the low ones in [0, 127], the top one signed),
    one int8 x int8 -> int32 contraction per digit.  Two digits cover
    |hv| < 2^14; int32 wrap-around is the accumulator's own.
    """
    cls = jnp.arange(n_classes, dtype=jnp.int32)[None, :]
    signs = ((labels.astype(jnp.int32)[:, None] == cls).astype(jnp.int8)
             - (predicted.astype(jnp.int32)[:, None] == cls).astype(jnp.int8))
    n_digits = max(1, -(-int(max_abs).bit_length() // 7))
    rest = hvs.astype(jnp.int32)
    out = jnp.zeros((n_classes, hvs.shape[-1]), jnp.int32)
    for k in range(n_digits):
        digit = rest if k == n_digits - 1 else rest & 127
        part = jnp.einsum("bc,bd->cd", signs, digit.astype(jnp.int8),
                          preferred_element_type=jnp.int32)
        out = out + (part << (7 * k))
        rest = rest >> 7
    return out


@jax.jit
def _label_range(label_batches: tuple[jax.Array, ...]) -> jax.Array:
    flat = jnp.concatenate([jnp.ravel(y).astype(jnp.int32) for y in label_batches])
    return jnp.stack([flat.min(), flat.max()])


def validate_label_batches(label_batches, n_classes: int) -> None:
    """`validate_labels` over many batches with one device read: the
    smallest and largest label of them all, reduced in one jitted call.
    Only when that range is out of bounds are the batches read one by
    one, to name the bad values."""
    label_batches = tuple(jnp.asarray(y) for y in label_batches)
    if not any(y.size for y in label_batches):
        return
    lo, hi = (int(v) for v in np.asarray(_label_range(label_batches)))
    if lo < 0 or hi >= n_classes:
        for labels in label_batches:
            validate_labels(labels, n_classes)


def validate_labels(labels, n_classes: int) -> None:
    """Host-path label validation: raise loudly on labels outside
    ``[0, n_classes)`` instead of silently dropping them (the jitted
    drop contract documented in :func:`bundle_by_class`).

    A traced value cannot be inspected, so under jit this is a no-op —
    which is exactly why the public ``HDCModel`` methods call it on the
    concrete arrays *before* handing them to the jitted functions.
    """
    if isinstance(labels, jax.core.Tracer):
        return
    arr = np.asarray(labels)
    if arr.size == 0:
        return
    bad = arr[(arr < 0) | (arr >= n_classes)]
    if bad.size:
        raise ValueError(
            f"labels must be in [0, {n_classes}); got out-of-range values "
            f"{np.unique(bad)[:8].tolist()} — under jit such labels are "
            "silently dropped from class_sums while n_seen still counts "
            "them, so they are rejected at the API boundary"
        )


def binarize(hv: jax.Array) -> jax.Array:
    """Sign binarization; ties (sum==0) resolve to +1 (TOB reached)."""
    return jnp.where(hv >= 0, jnp.int8(1), jnp.int8(-1))
