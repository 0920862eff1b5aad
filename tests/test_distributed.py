"""Distribution substrate: sharding rules, checkpoint manager, compression,
roofline parsing, and an 8-device dry-run in a subprocess."""

import json
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import roofline
from repro.checkpoint import CheckpointManager
from repro.distributed.sharding import ShardingRules
from jax.sharding import PartitionSpec as P

SRC = str(Path(__file__).resolve().parents[1] / "src")


# --- sharding rules ---------------------------------------------------------


class _FakeMesh:
    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)


def test_param_spec_tp_and_fallback():
    rules = ShardingRules()
    mesh = _FakeMesh({"data": 16, "model": 16})
    # heads divide -> heads sharded
    spec = rules.param_spec((3072, 16, 256), ("embed", "heads", "head_dim"), mesh)
    assert tuple(spec) == (None, "model", None)
    # 10 heads don't divide 16 -> falls back to head_dim
    spec = rules.param_spec((2560, 10, 256), ("embed", "heads", "head_dim"), mesh)
    assert tuple(spec) == (None, None, "model")
    # nothing divides -> replicated
    spec = rules.param_spec((7, 5), ("embed", "mlp"), mesh)
    assert tuple(spec) == (None, None)


def test_param_spec_fsdp_extra_axis():
    rules = ShardingRules(fsdp=True, fsdp_min_bytes=1024)
    mesh = _FakeMesh({"data": 16, "model": 16})
    spec = rules.param_spec((8192, 64, 128), ("embed", "heads", "head_dim"), mesh)
    assert tuple(spec) == ("data", "model", None)  # largest free dim -> data


def test_state_spec_batch_axis():
    rules = ShardingRules()
    mesh = _FakeMesh({"pod": 2, "data": 16, "model": 16})
    spec = rules.param_spec(
        (128, 32768, 8, 128), ("batch", None, "kv_heads", "head_dim"), mesh
    )
    assert tuple(spec)[0] == ("pod", "data")
    # batch=1 can't shard -> dropped
    spec = rules.param_spec((1, 8, 128), ("batch", "kv_heads", "head_dim"), mesh)
    assert tuple(spec)[0] is None


def test_constrain_is_identity_without_mesh():
    from repro.distributed.sharding import constrain, set_current_mesh

    set_current_mesh(None)
    x = jnp.ones((4, 4))
    assert constrain(x, P("data", None)) is x


# --- checkpoint manager -------------------------------------------------------


def test_checkpoint_roundtrip_and_retention(tmp_path):
    mgr = CheckpointManager(tmp_path, keep_n=2)
    tree = {"a": jnp.arange(6).reshape(2, 3), "b": {"c": jnp.ones(4, jnp.bfloat16)}}
    for step in (1, 2, 3):
        mgr.save(step, jax.tree.map(lambda x: x * step, tree))
    assert mgr.all_steps() == [2, 3]  # retention GC'd step 1
    got = mgr.restore(3, tree)
    np.testing.assert_array_equal(np.asarray(got["a"]), np.arange(6).reshape(2, 3) * 3)
    assert got["b"]["c"].dtype == jnp.bfloat16


def test_checkpoint_async_and_atomicity(tmp_path):
    mgr = CheckpointManager(tmp_path)
    tree = {"w": jnp.ones((128, 128))}
    mgr.save(7, tree, blocking=False)
    mgr.wait()
    assert mgr.latest_step() == 7
    # a stale tmp dir must be invisible to restore
    (tmp_path / "step_000000009.tmp").mkdir()
    assert mgr.latest_step() == 7


def test_checkpoint_missing_leaf_raises(tmp_path):
    mgr = CheckpointManager(tmp_path)
    mgr.save(1, {"a": jnp.ones(3)})
    with pytest.raises(KeyError):
        mgr.restore(1, {"b": jnp.ones(3)})


def test_checkpoint_shape_mismatch_raises(tmp_path):
    mgr = CheckpointManager(tmp_path)
    mgr.save(1, {"a": jnp.ones(3)})
    with pytest.raises(ValueError):
        mgr.restore(1, {"a": jnp.ones(4)})


# --- compression ----------------------------------------------------------------


def test_int8_quantization_roundtrip_error_bound():
    from repro.distributed import compress

    rng = np.random.default_rng(0)
    v = jnp.asarray(rng.standard_normal(1000), jnp.float32)
    scale = jnp.max(jnp.abs(v))
    q = compress.quantize_int8(v, scale)
    deq = compress.dequantize_int8(q, scale)
    assert float(jnp.abs(v - deq).max()) <= float(scale) / 127.0


def test_sign_compression_packed_roundtrip():
    from repro.distributed import compress

    rng = np.random.default_rng(1)
    v = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)
    packed, scale = compress.sign_compress_packed(v)
    back = compress.sign_decompress_packed(packed, scale, (8, 16))
    assert np.array_equal(np.sign(np.asarray(back)), np.sign(np.asarray(v)))


def test_error_feedback_converges_on_quadratic():
    """EF-compressed 'all-reduce' SGD reaches the optimum of a quadratic
    (single worker degenerate case exercises the EF algebra)."""
    from repro.distributed import compress

    target = jnp.asarray([1.0, -2.0, 0.5, 3.0])
    x = jnp.zeros(4)
    err = jnp.zeros(4)
    for _ in range(300):
        g = x - target
        v = g + err
        scale = jnp.max(jnp.abs(v)) + 1e-12
        q = compress.quantize_int8(v, scale)
        ghat = compress.dequantize_int8(q, scale)
        err = v - ghat
        x = x - 0.1 * ghat
    assert float(jnp.abs(x - target).max()) < 1e-2


def test_compressed_grad_sync_multidevice_subprocess():
    """shard_map hierarchical compressed sync on an 8-device host mesh."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.distributed import compress
        from repro.launch.mesh import _make_mesh
        mesh = _make_mesh((2, 4), ("pod", "data"))
        grads = {"w": jnp.arange(8.0).reshape(8, 1) + 1.0}
        errors = {"w": jnp.zeros((8, 1))}
        def sync(g, e):
            return compress.compressed_grad_sync(g, e)
        out, err = jax.jit(jax.shard_map(
            sync, mesh=mesh,
            in_specs=(P(("pod", "data")), P(("pod", "data"))),
            out_specs=(P(("pod", "data")), P(("pod", "data"))),
        ))(grads, errors)
        import numpy as np
        got = np.asarray(out["w"]).ravel()
        want = np.full(8, np.mean(np.arange(8.0) + 1.0))
        assert np.allclose(got, want, atol=0.05), (got, want)
        print("OK")
    """)
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env={"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"},
    )
    assert "OK" in r.stdout, r.stderr[-2000:]


# --- roofline parsing ---------------------------------------------------------


def test_collective_bytes_parser():
    hlo = """
      %p0 = f32[64,256]{1,0} parameter(0)
      %dot.1 = f32[64,256]{1,0} dot(%p0, %p0)
      %all-reduce = f32[64,256]{1,0} all-reduce(%dot.1), replica_groups={}
      %ag = (f32[8,4]{1,0}, f32[32,4]{1,0}) all-gather-start(%small), dimensions={0}
      %small = f32[8,4]{1,0} parameter(1)
      %done = f32[32,4]{1,0} all-gather-done(%ag)
    """
    out = roofline.collective_bytes(hlo)
    counts = out.pop("_counts")
    assert out["all-reduce"] == 64 * 256 * 4
    assert out["all-gather"] == 8 * 4 * 4  # operand bytes of the -start
    assert counts["all-reduce"] == 1 and counts["all-gather"] == 1


def test_roofline_terms_dominance():
    t = roofline.RooflineTerms(197e12, 819e9 * 2, 0.0)  # 1s compute, 2s memory
    assert t.dominant == "memory"
    assert t.bound_s == pytest.approx(2.0)


# --- 8-device multi-pod mini dry-run ------------------------------------------


def test_mini_multipod_dryrun_subprocess():
    """Lower+compile a smoke config train step on a (2,2,2) pod mesh —
    the multi-pod path end-to-end, sized for CI."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, dataclasses
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_smoke_config
        from repro.distributed.sharding import ShardingRules, set_current_mesh, abstract_params
        from repro.launch.specs import abstract_opt_state
        from repro.training.step import make_train_step
        from repro.optim import OptimizerConfig
        from repro.launch.mesh import _make_mesh
        mesh = _make_mesh((2, 2, 2), ("pod", "data", "model"))
        set_current_mesh(mesh)
        cfg = dataclasses.replace(get_smoke_config("qwen3-0.6b"), remat=True)
        rules = ShardingRules()
        params = abstract_params(cfg, mesh, rules)
        opt = abstract_opt_state(cfg, mesh, rules)
        batch = {"tokens": jax.ShapeDtypeStruct(
            (8, 64), jnp.int32,
            sharding=NamedSharding(mesh, P(("pod", "data"), None)))}
        step = make_train_step(cfg, OptimizerConfig())
        with mesh:
            compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
                params, opt, batch, jax.ShapeDtypeStruct((), jnp.int32)).compile()
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):  # older jax: one dict per device
            ca = ca[0]
        assert ca["flops"] > 0
        assert compiled.memory_analysis().temp_size_in_bytes > 0
        print("OK", int(ca["flops"]))
    """)
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env={"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"},
    )
    assert "OK" in r.stdout, r.stderr[-2000:]


def test_token_pipeline_deterministic():
    from repro.data.tokens import TokenPipeline

    p = TokenPipeline(vocab_size=100, seq_len=16, global_batch=8, seed=3)
    a, b = p.batch_at(5), p.batch_at(5)
    assert np.array_equal(np.asarray(a["tokens"]), np.asarray(b["tokens"]))
    c = p.batch_at(6)
    assert not np.array_equal(np.asarray(a["tokens"]), np.asarray(c["tokens"]))
    h0 = p.host_batch_at(5, 0, 2)["tokens"]
    h1 = p.host_batch_at(5, 1, 2)["tokens"]
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(h0), np.asarray(h1)]), np.asarray(a["tokens"])
    )
