"""Built-in encoders and their registered backends.

Three encoders ship with the repro, matching the paper:

  * ``"uhd"`` — position-free Sobol/unary encoding (contribution 2)
    over a materialized (H, D) threshold table, with five equivalent
    datapaths: ``naive`` (broadcast compare), ``blocked`` (D-tiled
    compare, bounded transient), ``unary_matmul`` (thermometer x
    one-hot binary GEMM on the MXU), ``pallas`` (fused Pallas
    encode+bundle kernel; interpret mode off-TPU), and
    ``unary_oracle`` (bit-exact simulation of the paper's UST +
    unary-comparator circuit — slow, the reference every other backend
    is tested against).
  * ``"uhd_dynamic"`` — the paper's headline *dynamic* generation: the
    same uHD encoding, but the codebook is only the (H, N_BITS)
    quantized Sobol direction matrix and thresholds are regenerated
    per D-tile at encode time (``ref`` pure-JAX datapath, ``pallas``
    fused in-VMEM generation).  Bit-identical hypervectors to ``uhd``
    from ~1000x less encoder state (DESIGN.md §7).
  * ``"baseline"`` — comparator-generated pseudo-random P x L
    bind+bundle (paper Fig. 1), with ``naive`` (gather + multiply
    reference) and ``unary_matmul`` (one-hot contraction) datapaths.

Registering a new encoder or datapath is purely additive — see
:mod:`repro.core.registry`; no dispatch code needs editing.
"""

from __future__ import annotations

import warnings
from typing import TYPE_CHECKING

import jax
import jax.numpy as jnp

from repro.core import encoding, sobol
from repro.core.registry import (
    EncoderBase,
    register_backend,
    register_encoder,
    register_encode_slice,
    register_fit_bundle,
    register_topk,
)

if TYPE_CHECKING:
    from repro.core.model import HDCConfig


def _import_kernel_ops():
    """Import hook for the Pallas probe (separate so tests can stub it)."""
    from repro.kernels import ops

    return ops


_PALLAS_PROBE_WARNED = False


def _pallas_available(platform: str) -> bool:
    """Pallas runs natively on TPU and in interpret mode elsewhere —
    usable anywhere the kernel package imports.

    Only a genuine ``ImportError`` (a missing optional dependency)
    disables the backend — and we warn once, so an ``auto`` resolution
    silently demoting to ``unary_matmul`` is at least visible.  Any
    other exception is a bug in the kernel package and propagates: a
    broken kernel must fail loudly, not quietly downgrade every TPU
    run to the matmul datapath.
    """
    global _PALLAS_PROBE_WARNED
    try:
        _import_kernel_ops()
    except ImportError as e:
        if not _PALLAS_PROBE_WARNED:
            _PALLAS_PROBE_WARNED = True
            warnings.warn(
                "Pallas backends disabled: repro.kernels.ops failed to "
                f"import ({e}); resolve_backend('auto') will fall back to "
                "the next datapath in the encoder's preference order",
                RuntimeWarning,
                stacklevel=2,
            )
        return False
    return True


# ---------------------------------------------------------------------------
# uHD: position-free Sobol/unary encoder
# ---------------------------------------------------------------------------


@register_encoder("uhd")
class UHDEncoder(EncoderBase):
    """Deterministic Sobol thresholds; no position HVs, no binding."""

    reference_backend = "unary_oracle"
    auto_order = {
        # On TPU the fused Pallas kernel is native; elsewhere interpret
        # mode is correct but slow, so the MXU-shaped matmul leads.
        "tpu": ("pallas", "unary_matmul", "blocked", "naive"),
        "default": ("unary_matmul", "blocked", "naive"),
    }
    # uHD hypervectors carry a per-example brightness common mode: class
    # sums must stay non-binarized and packing must row-center (the
    # policy rationale lives in DESIGN.md §5-§6).
    family = "uhd"
    default_class_binarize = "none"
    default_pack_center = "row"

    def build_codebooks(self, cfg: "HDCConfig") -> dict[str, jax.Array]:
        table = sobol.sobol_table_for_features(
            cfg.n_features, cfg.d, cfg.levels, seed=cfg.seed, skip=cfg.sobol_skip
        )
        # M-bit quantized thresholds are stored narrow (int8 here; the
        # paper's BRAM packs them at M=4 bits) — compute promotes to i32
        return {"sobol": jnp.asarray(table, self._sobol_dtype(cfg))}

    @staticmethod
    def _sobol_dtype(cfg: "HDCConfig"):
        return jnp.int8 if cfg.levels <= 127 else jnp.int32

    def codebook_specs(self, cfg: "HDCConfig") -> dict[str, jax.ShapeDtypeStruct]:
        # explicit: the Sobol table is generated host-side with numpy,
        # which eval_shape would execute for real
        return {
            "sobol": jax.ShapeDtypeStruct(
                (cfg.n_features, cfg.d), self._sobol_dtype(cfg)
            )
        }


@register_backend("uhd", "naive")
def _uhd_naive(cfg, books, x_q):
    """Broadcast-compare reference ((B, H, D) transient)."""
    return encoding.uhd_encode(x_q, books["sobol"])


@register_backend("uhd", "blocked")
def _uhd_blocked(cfg, books, x_q):
    """D-tiled compare: bounded (B, H, Dblk) transient."""
    return encoding.uhd_encode_blocked(x_q, books["sobol"])


@register_backend("uhd", "unary_matmul")
def _uhd_unary_matmul(cfg, books, x_q):
    """Thermometer x one-hot binary GEMM (MXU-unary formulation)."""
    return encoding.uhd_encode_unary_matmul(x_q, books["sobol"], cfg.levels)


@register_backend("uhd", "pallas", available=_pallas_available)
def _uhd_pallas(cfg, books, x_q):
    """Fused Pallas encode+bundle kernel (interpret mode off-TPU)."""
    from repro.kernels import ops  # local import: kernels are optional

    return ops.encode_bundle(x_q, books["sobol"])


@register_backend("uhd", "unary_oracle")
def _uhd_unary_oracle(cfg, books, x_q):
    """Bit-exact UST + unary-comparator circuit simulation (slow)."""
    return encoding.uhd_encode_via_unary_comparator(
        x_q, books["sobol"].astype(jnp.int32), cfg.levels
    )


# Fused training datapaths (DESIGN.md §9).  `d` and `point_offset` are
# ignored by the table forms: a D-sharded table arrives pre-sliced in
# `books["sobol"]`, which already fixes both the local width and the
# offset; only generator-backed encoders consume them.


@register_fit_bundle("uhd", "blocked")
def _uhd_blocked_fit_bundle(cfg, books, x_q, labels, *, d, point_offset):
    """Pure-JAX D-tile-scan fused training twin ((C, dt) per tile)."""
    from repro.kernels import ref as kref  # pure-jnp building block

    return kref.fit_bundle(x_q, books["sobol"], labels, cfg.n_classes)


@register_fit_bundle("uhd", "pallas")
def _uhd_pallas_fit_bundle(cfg, books, x_q, labels, *, d, point_offset):
    """Fused Pallas encode+bundle+class-sum kernel: the batch folded per
    class and level, then contracted with the table on the MXU over its
    `cfg.levels` threshold values."""
    from repro.kernels import ops  # local import: kernels are optional

    return ops.fit_bundle(x_q, books["sobol"], labels, cfg.n_classes, cfg.levels)


@register_topk("uhd", "pallas")
def _uhd_pallas_topk(q_words, c_words, d, k):
    """Streaming packed-Hamming top-k kernel (running k-best per tile)."""
    from repro.kernels import ops  # local import: kernels are optional

    return ops.hamming_topk(q_words, c_words, d, k)


# ---------------------------------------------------------------------------
# uHD dynamic: table-free Sobol generation (the paper's headline theme)
# ---------------------------------------------------------------------------


@register_encoder("uhd_dynamic")
class UHDDynamicEncoder(UHDEncoder):
    """Same uHD encoding, no (H, D) table: thresholds are regenerated
    from the quantized Sobol direction matrix at encode time.

    The codebook is ``{"direction": (H, N_BITS)}`` in the narrowest
    unsigned dtype holding ``levels - 1`` (``cfg.seed`` selects the
    direction-number draw, exactly like the table).  ``cfg.sobol_skip``
    is honoured at encode time — both backends start their Gray-code
    index at ``skip``, so hypervectors are bit-identical to every
    ``uhd`` table backend.  Encoder state shrinks from O(H * D) to
    O(H * N_BITS) bytes (~1000x at D = 8192), which is what makes very
    large D cheap to train, checkpoint, and serve.

    Inherits the uHD family policies (class sums stay non-binarized,
    packing row-centers), so a ``uhd`` checkpoint converted via
    ``HDCModel.convert("uhd_dynamic")`` predicts bit-identically.
    """

    reference_backend = "ref"
    auto_order = {
        # TPU-first: the fused kernel generates tiles in VMEM natively;
        # elsewhere the pure-JAX tile scan leads (interpret mode is slow).
        "tpu": ("pallas", "ref"),
        "default": ("ref", "pallas"),
    }
    # The codebook is a generator, not a table: D-sharded training hands
    # each shard its point_offset into the Sobol stream (DESIGN.md §9).
    dynamic_generator = True

    def build_codebooks(self, cfg: "HDCConfig") -> dict[str, jax.Array]:
        dirs = sobol.quantized_direction_matrix(
            cfg.n_features, cfg.levels, seed=cfg.seed
        )
        return {"direction": jnp.asarray(dirs)}

    def codebook_specs(self, cfg: "HDCConfig") -> dict[str, jax.ShapeDtypeStruct]:
        # explicit: direction numbers are generated host-side with numpy,
        # which eval_shape would execute for real (same as the table)
        return {
            "direction": jax.ShapeDtypeStruct(
                (cfg.n_features, sobol.N_BITS),
                jnp.dtype(sobol.quantized_direction_dtype(cfg.levels)),
            )
        }


@register_backend("uhd_dynamic", "ref")
def _uhd_dynamic_ref(cfg, books, x_q):
    """Pure-JAX per-D-tile Sobol regeneration (runs everywhere)."""
    return encoding.uhd_encode_dynamic(
        x_q, books["direction"], cfg.d, skip=cfg.sobol_skip
    )


@register_backend("uhd_dynamic", "pallas", available=_pallas_available)
def _uhd_dynamic_pallas(cfg, books, x_q):
    """Fused Pallas encode+bundle with in-VMEM Sobol generation."""
    from repro.kernels import ops  # local import: kernels are optional

    return ops.encode_bundle_dynamic(
        x_q, books["direction"], cfg.d, n_levels=cfg.levels, skip=cfg.sobol_skip
    )


@register_fit_bundle("uhd_dynamic", "ref")
def _uhd_dynamic_ref_fit_bundle(cfg, books, x_q, labels, *, d, point_offset):
    """Pure-JAX table-free fused training (tile-scan generation)."""
    from repro.kernels import ref as kref  # pure-jnp building block

    skip = cfg.sobol_skip if point_offset is None else cfg.sobol_skip + point_offset
    return kref.fit_bundle_dynamic(
        x_q, books["direction"], labels, cfg.n_classes, d, skip=skip
    )


@register_encode_slice("uhd_dynamic", "ref")
def _uhd_dynamic_ref_encode_slice(cfg, books, x_q, *, d, point_offset):
    """Pure-JAX D-slice generation for sharded packed predict: each
    shard Gray-codes only points [skip + offset, skip + offset + d).
    `point_offset` may be traced (``jax.lax.axis_index`` under
    shard_map) — the generator takes it as a runtime scalar.  The
    Pallas encode kernel bakes `skip` into the kernel closure, so it
    registers no slice path; "auto" dispatch lands here instead."""
    skip = cfg.sobol_skip if point_offset is None else cfg.sobol_skip + point_offset
    return encoding.uhd_encode_dynamic(x_q, books["direction"], d, skip=skip)


@register_fit_bundle("uhd_dynamic", "pallas")
def _uhd_dynamic_pallas_fit_bundle(cfg, books, x_q, labels, *, d, point_offset):
    """Fused Pallas training kernel with in-VMEM Sobol generation."""
    from repro.kernels import ops  # local import: kernels are optional

    skip = cfg.sobol_skip if point_offset is None else cfg.sobol_skip + point_offset
    return ops.fit_bundle_dynamic(
        x_q, books["direction"], labels, cfg.n_classes, d, skip=skip
    )


@register_topk("uhd_dynamic", "pallas")
def _uhd_dynamic_pallas_topk(q_words, c_words, d, k):
    """Streaming packed-Hamming top-k kernel (packed rows are
    encoder-agnostic, so this is the same kernel as the table form)."""
    from repro.kernels import ops  # local import: kernels are optional

    return ops.hamming_topk(q_words, c_words, d, k)


# ---------------------------------------------------------------------------
# Baseline HDC: pseudo-random P x L bind+bundle
# ---------------------------------------------------------------------------


@register_encoder("baseline")
class BaselineEncoder(EncoderBase):
    """Comparator-generated pseudo-random position/level codebooks."""

    reference_backend = "naive"
    auto_order = {"default": ("unary_matmul", "naive")}

    def build_codebooks(self, cfg: "HDCConfig") -> dict[str, jax.Array]:
        # `seed` selects the pseudo-random draw — the paper's iteration
        # index i maps to seed=i.
        key = jax.random.PRNGKey(cfg.seed)
        p, level = encoding.make_baseline_codebooks(
            key, cfg.n_features, cfg.d, cfg.levels
        )
        return {"p": p, "level": level}


@register_backend("baseline", "naive")
def _baseline_naive(cfg, books, x_q):
    """Gather + elementwise bind reference ((B, H, D) transient)."""
    return encoding.baseline_encode_naive(x_q, books["p"], books["level"])


@register_backend("baseline", "unary_matmul")
def _baseline_unary_matmul(cfg, books, x_q):
    """One-hot contracted bind+bundle: a single (B, HV) @ (HV, D) GEMM."""
    return encoding.baseline_encode(x_q, books["p"], books["level"])
