"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sobol, unary
from repro.kernels import ops, ref

RNG = np.random.default_rng(11)


def _case(b, h, d, levels=16, dtype=jnp.int32):
    x = jnp.asarray(RNG.integers(0, levels + 1, (b, h)), dtype)
    s = jnp.asarray(sobol.sobol_table_for_features(h, d, levels), dtype)
    return x, s


@pytest.mark.parametrize(
    "b,h,d",
    [(1, 17, 64), (8, 112, 512), (12, 100, 700), (5, 784, 1024), (16, 33, 96)],
)
@pytest.mark.parametrize("dtype", [jnp.int32, jnp.int8])
def test_encode_bundle_kernel(b, h, d, dtype):
    x, s = _case(b, h, d, dtype=jnp.int32)
    want = ref.encode_bundle(x, s)
    got = ops.encode_bundle(x.astype(dtype), s)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("b,h,d", [(4, 30, 200), (8, 112, 512), (3, 784, 256)])
def test_encode_unary_mxu_kernel(b, h, d):
    x, s = _case(b, h, d)
    want = ref.encode_bundle(x, s)
    got = ops.encode_unary_mxu(x, s, 16)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize(
    "b,h,d,levels,skip",
    [
        (4, 50, 512, 16, 1),     # H padded to the 112 tile
        (8, 112, 1024, 16, 1),   # exact grid
        (8, 112, 1024, 16, 7),   # nonzero sobol_skip must match the table
        (3, 113, 640, 2, 1),     # H % tile == 1, D % tile != 0, 1-bit levels
        (5, 100, 576, 256, 3),   # 8-bit quantization + skip
        (1, 50, 512, 16, 1),     # one image in a 32-row batch tile
        (37, 60, 640, 16, 1),    # B not a multiple of the batch tile
        (300, 100, 1280, 16, 1),  # two batch tiles x three D tiles
        (300, 40, 1100, 2, 1000),  # both, 1-bit levels, a skip past 2**9
        (40, 60, 640, 256, 5),   # sixteen level rows of 16
        (260, 30, 700, 256, 2),  # level rows x batch tiles x D tiles
    ],
)
def test_encode_bundle_dynamic_kernel(b, h, d, levels, skip):
    """In-kernel Sobol generation == table-based encode, bit-exact,
    including nonzero skip and padded H (the padded features, -1
    against all-zero direction rows, must match no level), across the
    kernel's batch, D and level-row tiles."""
    x = jnp.asarray(RNG.integers(0, levels + 1, (b, h)), jnp.int32)
    # pin the x_q == 0 edge: a whole real row at the minimum intensity
    # still compares correctly against padded threshold-0 rows; and a
    # row at the maximum level L, above every threshold
    x = x.at[0].set(0).at[-1].set(levels)
    s = jnp.asarray(sobol.sobol_table_for_features(h, d, levels, skip=skip), jnp.int32)
    want = ref.encode_bundle(x, s)
    dirs = jnp.asarray(sobol.direction_matrix(h).astype(np.uint32))
    got = ops.encode_bundle_dynamic(x, dirs, d, levels=levels, skip=skip)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # pre-quantized direction numbers (the uhd_dynamic codebook): exact
    # because right-shift distributes over XOR
    qdirs = jnp.asarray(sobol.quantized_direction_matrix(h, levels))
    got_q = ops.encode_bundle_dynamic(x, qdirs, d, n_levels=levels, skip=skip)
    np.testing.assert_array_equal(np.asarray(got_q), np.asarray(want))


def test_encode_bundle_dynamic_needs_the_level_count():
    """Pre-quantized directions do not say how many levels the kernel
    contracts: the caller must."""
    qdirs = jnp.asarray(sobol.quantized_direction_matrix(20, 16))
    x = jnp.zeros((2, 20), jnp.int32)
    with pytest.raises(ValueError, match="n_levels"):
        ops.encode_bundle_dynamic(x, qdirs, 256)


def _reader_pattern(metric):
    """The `OP_PATTERN` literal of a benchmark reader, read from its file."""
    import ast
    from pathlib import Path

    path = Path(__file__).resolve().parents[1] / "bench" / "metrics" / f"{metric}.py"
    for node in ast.parse(path.read_text()).body:
        if isinstance(node, ast.Assign) and any(
                getattr(t, "id", None) == "OP_PATTERN" for t in node.targets):
            return ast.literal_eval(node.value)
    raise AssertionError(f"{path} defines no OP_PATTERN")


@pytest.mark.parametrize("metric", ["search.encode_ms", "retrain.encode_roofline"])
def test_encode_kernel_carries_the_name_the_readers_key_on(metric):
    """Every Pallas call that `ops.encode_bundle_dynamic` makes carries
    metadata that the reader of the encode finds in the custom call's
    text, where the kernel holds it as compact JSON."""
    import json
    import re

    jaxpr = jax.make_jaxpr(lambda x, d: ops.encode_bundle_dynamic(x, d, 640, n_levels=32))(
        jnp.zeros((40, 50), jnp.int32), jnp.zeros((50, 32), jnp.uint8))
    calls = [e.params["metadata"] for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert calls
    pattern = _reader_pattern(metric)
    for metadata in calls:
        text = f"kernel_metadata={json.dumps(dict(metadata), separators=(',', ':'))}"
        assert re.search(pattern, text), text


def test_sobol_tile_ref_matches_generator():
    dirs = jnp.asarray(sobol.direction_matrix(16).astype(np.uint32))
    tile = ref.sobol_tile(dirs, jnp.uint32(1), 64)  # skip=1 convention
    want = sobol.sobol_integers(16, 64, skip=1).T >> np.uint64(32 - sobol.N_BITS)
    np.testing.assert_array_equal(np.asarray(tile, np.uint64), want.astype(np.uint64))


@pytest.mark.parametrize("b,c,d", [(10, 10, 512), (64, 3, 300), (7, 12, 1024)])
@pytest.mark.parametrize("binarize", [True, False])
def test_bundle_binarize_kernel(b, c, d, binarize):
    hvs = jnp.asarray(RNG.integers(-50, 50, (b, d)), jnp.int32)
    labels = jnp.asarray(RNG.integers(0, c, (b,)), jnp.int32)
    onehot = jax.nn.one_hot(labels, c).T
    got = ops.bundle_binarize(hvs, labels, c, binarize=binarize)
    if binarize:
        want = ref.bundle_binarize(hvs, onehot)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    else:
        want = jnp.einsum("cb,bd->cd", onehot, hvs.astype(jnp.float32)).astype(jnp.int32)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("b,c,d", [(4, 10, 256), (130, 11, 800), (1, 1, 32)])
def test_hamming_packed_kernel(b, c, d):
    q = jnp.asarray(RNG.integers(-3, 4, (b, d)), jnp.int32)
    cl = jnp.asarray(RNG.integers(-3, 4, (c, d)), jnp.int32)
    qw, cw = unary.pack_hypervector(q), unary.pack_hypervector(cl)
    want = ref.hamming_packed(qw, cw, d)
    got = ops.hamming_packed(qw, cw, d)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # cross-check against the +-1 integer dot
    qv = np.where(np.asarray(q) >= 0, 1, -1)
    cv = np.where(np.asarray(cl) >= 0, 1, -1)
    np.testing.assert_array_equal(np.asarray(got), qv @ cv.T)


@pytest.mark.parametrize("b,c", [(5, 10), (1, 3), (129, 9), (128, 8)])
def test_hamming_packed_pallas_arbitrary_grid(b, c):
    """The kernel itself pads B/C to the block grid (serving needs
    request batches and class counts that don't divide the blocks)."""
    from repro.kernels.hamming_packed import hamming_packed_pallas

    d = 96
    q = jnp.asarray(RNG.integers(-3, 4, (b, d)), jnp.int32)
    cl = jnp.asarray(RNG.integers(-3, 4, (c, d)), jnp.int32)
    qw, cw = unary.pack_hypervector(q), unary.pack_hypervector(cl)
    got = hamming_packed_pallas(qw, cw, d, interpret=True)
    assert got.shape == (b, c)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref.hamming_packed(qw, cw, d)))


def test_packed_similarity_random_d_sweep():
    """Seeded sweep of the serving-path property (also a hypothesis test
    in tests/test_unary.py): packed XOR+popcount == ±1 integer dot for
    random D including D % 32 != 0, on both similarity impls."""
    from repro.core import metrics

    rng = np.random.default_rng(5)
    for _ in range(10):
        b, c = int(rng.integers(1, 7)), int(rng.integers(1, 12))
        d = int(rng.integers(1, 100))  # hits non-multiples of 32
        q = rng.integers(-7, 8, (b, d))
        cl = rng.integers(-7, 8, (c, d))
        qw = unary.pack_hypervector(jnp.asarray(q, jnp.int32))
        cw = unary.pack_hypervector(jnp.asarray(cl, jnp.int32))
        want = np.where(q >= 0, 1, -1) @ np.where(cl >= 0, 1, -1).T
        np.testing.assert_array_equal(
            np.asarray(metrics.hamming_similarity_packed(qw, cw, d)), want
        )
        np.testing.assert_array_equal(np.asarray(ops.hamming_packed(qw, cw, d)), want)


def test_kernel_in_model_path():
    """HDCConfig(backend='pallas') routes encoding through the kernel."""
    from repro.core import HDCConfig, HDCModel

    cfg = HDCConfig(n_features=49, n_classes=4, d=256, backend="pallas")
    model = HDCModel.create(cfg)
    x = jnp.asarray(RNG.uniform(0, 255, (6, 49)), jnp.float32)
    got = model.encode(x)
    want = model.encode(x, backend="naive")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
