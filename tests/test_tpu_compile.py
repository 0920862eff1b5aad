"""Main-path Pallas kernels compile for a described TPU v5e chip.

Interpret mode (every other kernel test) cannot see the TPU's block
rules — the last two block dims must be multiples of (8, 128) or the
array's own — nor the scoped VMEM limit.  The TPU compiler is
installed even where no chip is attached, so each kernel is compiled
here at the paper's widths (H=784, C=10, D=8192, 16 levels) and at the
store and shard sizes the system serves.  Nothing runs: these tests
say a kernel compiles natively, not that it is right or fast.  Every
kernel, main path or not, is also lowered once to check the name it
carries into the device trace.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

H, C, D, L = 784, 10, 8192, 16
W = D // 32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip cannot be read back from the
    # persistent cache without one, so keep it out of the cache
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


# name -> (fn, operand shapes/dtypes); every call pins interpret=False,
# because ops' own default sees the CPU here
KERNELS = {
    "encode_bundle_B64": (
        lambda x, s: ops.encode_bundle(x, s, interpret=False),
        [((64, H), jnp.int32), ((H, D), jnp.int8)],
    ),
    "encode_bundle_dynamic_B64": (
        lambda x, dr: ops.encode_bundle_dynamic(x, dr, D, n_levels=L, interpret=False),
        [((64, H), jnp.int32), ((H, 32), jnp.uint8)],
    ),
    # the retraining benchmark's steps encode 2048 images, and the epoch's
    # last 608, then scan the 10 class words for each
    "encode_bundle_dynamic_B2048": (
        lambda x, dr: ops.encode_bundle_dynamic(x, dr, D, n_levels=L, interpret=False),
        [((2048, H), jnp.int32), ((H, 32), jnp.uint8)],
    ),
    "encode_bundle_dynamic_B608": (
        lambda x, dr: ops.encode_bundle_dynamic(x, dr, D, n_levels=L, interpret=False),
        [((608, H), jnp.int32), ((H, 32), jnp.uint8)],
    ),
    # the paper's other width, and 3072 features: the tiles shrink so
    # the level one-hots and the whole feature axis fit VMEM; 256
    # levels: sixteen grid rows
    "encode_bundle_dynamic_H1024": (
        lambda x, dr: ops.encode_bundle_dynamic(x, dr, D, n_levels=L, interpret=False),
        [((2048, 1024), jnp.int32), ((1024, 32), jnp.uint8)],
    ),
    "encode_bundle_dynamic_H3072": (
        lambda x, dr: ops.encode_bundle_dynamic(x, dr, D, n_levels=L, interpret=False),
        [((2048, 3072), jnp.int32), ((3072, 32), jnp.uint8)],
    ),
    "encode_bundle_dynamic_B2048_L256": (
        lambda x, dr: ops.encode_bundle_dynamic(x, dr, D, n_levels=256, interpret=False),
        [((2048, H), jnp.int32), ((H, 32), jnp.uint8)],
    ),
    "fit_bundle_B256": (
        lambda x, s, y: ops.fit_bundle(x, s, y, C, L, interpret=False),
        [((256, H), jnp.int32), ((H, D), jnp.int8), ((256,), jnp.int32)],
    ),
    # the fit benchmark's steps: 2048 images, and the epoch's last 608
    "fit_bundle_B2048": (
        lambda x, s, y: ops.fit_bundle(x, s, y, C, L, interpret=False),
        [((2048, H), jnp.int32), ((H, D), jnp.int8), ((2048,), jnp.int32)],
    ),
    "fit_bundle_B608": (
        lambda x, s, y: ops.fit_bundle(x, s, y, C, L, interpret=False),
        [((608, H), jnp.int32), ((H, D), jnp.int8), ((608,), jnp.int32)],
    ),
    # 3072 features: the tiles shrink so the whole feature axis fits VMEM
    "fit_bundle_H3072": (
        lambda x, s, y: ops.fit_bundle(x, s, y, C, L, interpret=False),
        [((2048, 3072), jnp.int32), ((3072, D), jnp.int8), ((2048,), jnp.int32)],
    ),
    # 256 levels: sixteen grid rows of 16 levels each, over the int32 table
    "fit_bundle_B256_L256": (
        lambda x, s, y: ops.fit_bundle(x, s, y, C, 256, interpret=False),
        [((256, H), jnp.int32), ((H, D), jnp.int32), ((256,), jnp.int32)],
    ),
    "fit_bundle_dynamic_B256": (
        lambda x, dr, y: ops.fit_bundle_dynamic(x, dr, y, C, D, interpret=False),
        [((256, H), jnp.int32), ((H, 32), jnp.uint8), ((256,), jnp.int32)],
    ),
    "hamming_packed_C10": (
        lambda q, c: ops.hamming_packed(q, c, D, interpret=False),
        [((64, W), jnp.uint32), ((C, W), jnp.uint32)],
    ),
    # one shard of a four-chip D-sharded engine: d_local=2048, W=64
    "hamming_packed_W64": (
        lambda q, c: ops.hamming_packed(q, c, D // 4, interpret=False),
        [((64, W // 4), jnp.uint32), ((C, W // 4), jnp.uint32)],
    ),
    "hamming_topk_C10_k1": (
        lambda q, c: ops.hamming_topk(q, c, D, 1, interpret=False),
        [((64, W), jnp.uint32), ((C, W), jnp.uint32)],
    ),
    "hamming_topk_C10_k1_B2048": (
        lambda q, c: ops.hamming_topk(q, c, D, 1, interpret=False),
        [((2048, W), jnp.uint32), ((C, W), jnp.uint32)],
    ),
    # a 1 GiB store: the working set must not grow with C
    "hamming_topk_C1M_k10": (
        lambda q, c: ops.hamming_topk(q, c, D, 10, interpret=False),
        [((64, W), jnp.uint32), ((1 << 20, W), jnp.uint32)],
    ),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, operands = KERNELS[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in operands]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), "no native kernel in the program"


# every Pallas kernel by the name it carries (``hdc_kernel`` in the custom
# call's kernel_metadata, which the device trace's op text holds): the
# main-path entries above, and the two kernels off the main path
NAMED = {
    "encode_bundle": KERNELS["encode_bundle_B64"],
    "encode_bundle_dynamic": KERNELS["encode_bundle_dynamic_B64"],
    "fit_bundle": KERNELS["fit_bundle_B256"],
    "fit_bundle_dynamic": KERNELS["fit_bundle_dynamic_B256"],
    "hamming_packed": KERNELS["hamming_packed_C10"],
    "hamming_topk": KERNELS["hamming_topk_C10_k1"],
    "encode_unary_mxu": (
        lambda x, s: ops.encode_unary_mxu(x, s, 16, interpret=False),
        [((64, H), jnp.int32), ((H, D), jnp.int32)],
    ),
    "bundle_binarize": (
        lambda hv, y: ops.bundle_binarize(hv, y, C, interpret=False),
        [((256, D), jnp.int32), ((256,), jnp.int32)],
    ),
}


@pytest.mark.parametrize("name", sorted(NAMED))
def test_kernel_carries_its_name(one_chip, name):
    fn, operands = NAMED[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in operands]
    text = jax.jit(fn).lower(*args).as_text(dialect="hlo")
    named = re.findall(r'kernel_metadata=\{\s*"hdc_kernel":"(\w+)"\s*\}', text)
    assert named and set(named) == {name}, named
