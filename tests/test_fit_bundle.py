"""The fused table-free training datapath (DESIGN.md §9).

Covers: the backend-vs-oracle matrix for `fit_bundle` (both encoders,
both fused datapaths each, D % tile != 0, nonzero sobol_skip), routing
through `partial_fit`, the integer-exact `bundle_by_class` fix, loud
out-of-range-label handling, the n_seen split counter at the int32
boundary, buffer donation for streaming training, shard_map-vs-single
device equivalence on an 8-device CPU mesh, and per-host checkpoint
shards through CheckpointManager.
"""

import dataclasses
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import HDCConfig, HDCModel, encoding, get_encoder, registry
from repro.core import hdc_model as hm
from repro.checkpoint.manager import CheckpointManager

SRC = str(Path(__file__).resolve().parents[1] / "src")
RNG = np.random.default_rng(11)


def _cfg(**kw):
    base = dict(n_features=24, n_classes=4, d=128, levels=16)
    base.update(kw)
    return HDCConfig(**base)


def _data(cfg, n=20):
    x = jnp.asarray(RNG.uniform(0, 255, (n, cfg.n_features)), jnp.float32)
    y = jnp.asarray(RNG.integers(0, cfg.n_classes, (n,)), jnp.int32)
    return x, y


# ---------------------------------------------------------------------------
# fused fit_bundle: backend-vs-oracle matrix
# ---------------------------------------------------------------------------

FUSED = [("uhd", "blocked"), ("uhd", "pallas"),
         ("uhd_dynamic", "ref"), ("uhd_dynamic", "pallas")]


def test_fused_datapaths_are_registered():
    table = registry.backend_table()
    for encoder, backend in FUSED:
        assert table[encoder][backend].fit_bundle is not None, (encoder, backend)
    # unfused backends stay unfused (fallback-covered)
    assert table["uhd"]["naive"].fit_bundle is None
    assert table["baseline"]["naive"].fit_bundle is None
    assert get_encoder("uhd_dynamic").has_fit_bundle("ref", "cpu")
    assert not get_encoder("uhd").has_fit_bundle("naive", "cpu")


@pytest.mark.parametrize("encoder,backend", FUSED)
@pytest.mark.parametrize(
    "d,skip,levels,n,h",
    [
        pytest.param(96, 1, 16, 22, 24, id="96-1-16"),
        pytest.param(700, 5, 16, 22, 24, id="700-5-16"),
        pytest.param(128, 3, 256, 22, 24, id="128-3-256"),
        # batches off the fit kernel's row tile: the fit benchmark's
        # last step of an epoch (608 images), and a single image
        pytest.param(96, 1, 16, 608, 24, id="96-1-16-b608"),
        pytest.param(96, 1, 16, 1, 24, id="96-1-16-b1"),
        # one shard of a four-chip D-sharded fit (d_local = 2048)
        pytest.param(2048, 1, 16, 22, 24, id="2048-1-16"),
        # the paper's MNIST width, H = 784 padded to 896
        pytest.param(256, 1, 16, 22, 784, id="256-1-16-h784"),
    ],
)
def test_fit_bundle_matches_encode_then_bundle_oracle(
    encoder, backend, d, skip, levels, n, h
):
    """Acceptance: fused class sums bit-identical to the
    encode-then-bundle_by_class oracle, across D % tile != 0, nonzero
    sobol_skip, ragged and single-image batches and padded H, for every
    fused datapath of both encoders.  The batch holds an all-dark and
    an all-bright image, set on the quantized levels (x_q at 0 and at
    `levels`), and at 16 levels the thresholds span 0 to 15: the edge
    levels of the MXU contraction."""
    cfg = _cfg(
        d=d, sobol_skip=skip, levels=levels, n_features=h, encoder=encoder,
        backend=backend,
    )
    model = HDCModel.create(cfg)
    enc = get_encoder(encoder)
    x, y = _data(cfg, n=n)
    x_q = encoding.quantize_images(jnp.asarray(x), cfg.levels, cfg.max_intensity)
    x_q = x_q.at[0].set(0).at[-1].set(levels)  # one image: all bright
    assert int(x_q.min()) == (0 if n > 1 else levels) and int(x_q.max()) == levels
    if levels == 16:  # 128 points leave some of 256 levels unused
        table = get_encoder("uhd").build_codebooks(cfg)["sobol"]
        assert int(table.min()) == 0 and int(table.max()) == levels - 1
    # oracle: the encoder's reference oracle datapath, then exact bundling
    hvs = enc.encode(cfg, model.codebooks, x_q, backend=enc.reference_backend)
    oracle = encoding.bundle_by_class(hvs, y, cfg.n_classes)
    fused = enc.fit_bundle(cfg, model.codebooks, x_q, y, backend=backend)
    np.testing.assert_array_equal(
        np.asarray(fused), np.asarray(oracle),
        err_msg=f"{encoder}/{backend} d={d} skip={skip} levels={levels} n={n} h={h}",
    )
    # and through the public training entry point, on the raw images
    hvs = model.encode(x, backend=enc.reference_backend)
    trained = model.fit(x, y)
    np.testing.assert_array_equal(
        np.asarray(trained.class_sums),
        np.asarray(encoding.bundle_by_class(hvs, y, cfg.n_classes)),
    )


def test_partial_fit_routes_through_fused_datapath(monkeypatch):
    """partial_fit dispatches to the backend's registered fit_bundle (not
    the encode-then-bundle fallback) when one is advertised."""
    cfg = _cfg(d=736, encoder="uhd_dynamic", backend="ref")  # unseen d: fresh trace
    calls = []
    spec = registry._BACKENDS["uhd_dynamic"]["ref"]
    orig = spec.fit_bundle

    def probe(*a, **kw):
        calls.append(1)
        return orig(*a, **kw)

    monkeypatch.setitem(
        registry._BACKENDS["uhd_dynamic"], "ref",
        dataclasses.replace(spec, fit_bundle=probe),
    )
    model = HDCModel.create(cfg)
    x, y = _data(cfg)
    fused = model.partial_fit(x, y)
    assert calls, "fit_bundle was not dispatched"
    # fallback (no fused registration) produces bit-identical sums
    monkeypatch.setitem(
        registry._BACKENDS["uhd_dynamic"], "ref",
        dataclasses.replace(spec, fit_bundle=None),
    )
    cfg2 = _cfg(d=737, encoder="uhd_dynamic", backend="ref")  # fresh trace again
    model2 = HDCModel.create(cfg2)
    unfused = model2.partial_fit(x, y)
    np.testing.assert_array_equal(
        np.asarray(fused.class_sums[:, :736]),
        np.asarray(unfused.class_sums[:, :736]),
    )


# ---------------------------------------------------------------------------
# bundle_by_class: integer exactness + label contract
# ---------------------------------------------------------------------------


def test_bundle_by_class_exact_beyond_float32_window():
    """Class sums crossing float32's 2^24 integer window stay exact.

    The sum 2^24 + 101 is odd and > 2^24, so it is not representable in
    float32 — the old float32 einsum was off by >= 1 here for *every*
    accumulation order.  The batch shape is what a large-batch
    production stream hits once B * max|hv| crosses 2^24.
    """
    hvs = jnp.concatenate(
        [jnp.full((1, 3), 2**24, jnp.int32), jnp.ones((101, 3), jnp.int32)]
    )
    labels = jnp.zeros((102,), jnp.int32)
    out = np.asarray(encoding.bundle_by_class(hvs, labels, 2))
    np.testing.assert_array_equal(out[0], np.full(3, 2**24 + 101))
    np.testing.assert_array_equal(out[1], 0)
    # float32 demonstrably cannot express the target
    assert int(np.float32(2**24) + np.float32(101)) != 2**24 + 101


def test_bundle_by_class_random_matches_numpy():
    hvs = jnp.asarray(RNG.integers(-50, 50, (64, 17)), jnp.int32)
    labels = jnp.asarray(RNG.integers(0, 5, (64,)), jnp.int32)
    want = np.stack(
        [np.asarray(hvs)[np.asarray(labels) == c].sum(0) for c in range(5)]
    )
    np.testing.assert_array_equal(
        np.asarray(encoding.bundle_by_class(hvs, labels, 5)), want
    )


@pytest.mark.parametrize("bad", [-1, 4, 99])
def test_out_of_range_labels_raise_on_host_path(bad):
    cfg = _cfg()
    model = HDCModel.create(cfg)
    x, y = _data(cfg, n=6)
    y = y.at[3].set(bad)
    with pytest.raises(ValueError, match="out-of-range"):
        model.partial_fit(x, y)
    with pytest.raises(ValueError, match="out-of-range"):
        model.fit(x, y)
    with pytest.raises(ValueError, match="out-of-range"):
        model.fit_batches([(x, y)])
    with pytest.raises(ValueError, match="out-of-range"):
        hm.partial_fit_sharded(
            model, x, y,
            mesh=jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("model",)),
        )


def test_jitted_path_drop_contract_documented_and_pinned():
    """Inside jit labels cannot be validated; the contract is that an
    out-of-range label one-hots to zero and is dropped from the sums
    (while n_seen still counts it) — pinned so the documented behaviour
    cannot drift."""
    cfg = _cfg()
    model = HDCModel.create(cfg)
    x, y_ok = _data(cfg, n=6)
    y_bad = y_ok.at[0].set(cfg.n_classes)  # out of range
    direct = hm.partial_fit(model, jnp.asarray(x), y_bad)  # module fn: no host check
    oracle = hm.partial_fit(model, jnp.asarray(x[1:]), y_ok[1:])
    np.testing.assert_array_equal(
        np.asarray(direct.class_sums), np.asarray(oracle.class_sums)
    )
    assert direct.n_examples == 6  # ...but the counter disagrees: why the
    # public methods validate on the host before tracing


# ---------------------------------------------------------------------------
# n_seen split counter
# ---------------------------------------------------------------------------


def test_n_seen_exact_across_int32_boundary(tmp_path):
    cfg = _cfg()
    books = get_encoder(cfg.encoder).build_codebooks(cfg)
    x, y = _data(cfg, n=16)
    m = HDCModel.from_parts(cfg, books, n_seen=2**31 - 8).partial_fit(x, y)
    assert m.n_examples == 2**31 + 8  # int32 would have wrapped negative
    m32 = HDCModel.from_parts(cfg, books, n_seen=2**32 - 4).partial_fit(x[:8], y[:8])
    assert m32.n_examples == 2**32 + 4  # uint32 scalar would have wrapped too
    # checkpoint round-trip preserves the full-width counter
    m32.save(tmp_path / "ckpt", step=1)
    assert HDCModel.load(tmp_path / "ckpt").n_examples == 2**32 + 4
    assert m32.reset().n_examples == 0
    # legacy scalar values still construct
    assert HDCModel.from_parts(cfg, books, n_seen=jnp.asarray(7)).n_examples == 7
    with pytest.raises(ValueError, match="n_seen"):
        HDCModel.from_parts(cfg, books, n_seen=-1)


# ---------------------------------------------------------------------------
# buffer donation
# ---------------------------------------------------------------------------


def test_donated_streaming_matches_undonated():
    cfg = _cfg(d=192)
    x, y = _data(cfg, n=30)
    undonated = HDCModel.create(cfg)
    for i in range(0, 30, 7):
        undonated = undonated.partial_fit(x[i : i + 7], y[i : i + 7])
    donated = HDCModel.create(cfg).fit_batches(
        (x[i : i + 7], y[i : i + 7]) for i in range(0, 30, 7)
    )
    np.testing.assert_array_equal(
        np.asarray(donated.class_sums), np.asarray(undonated.class_sums)
    )
    assert donated.n_examples == undonated.n_examples == 30


def test_donation_consumes_state_but_never_codebooks():
    cfg = _cfg(d=192)
    model = HDCModel.create(cfg)
    x, y = _data(cfg)
    old_sums, old_books = model.class_sums, dict(model.codebooks)
    out = model.partial_fit(x, y, donate=True)
    # the (C, D) accumulator was updated in place (old buffer consumed)...
    assert old_sums.is_deleted()
    # ...while the shared codebooks stay live and untouched
    for k, v in old_books.items():
        assert not v.is_deleted(), k
        assert out.codebooks[k] is v
    # fit_batches never consumes the model it was called on
    model2 = HDCModel.create(cfg)
    model2.fit_batches([(x, y)])
    assert not model2.class_sums.is_deleted()
    _ = model2.partial_fit(x, y)  # still usable


# ---------------------------------------------------------------------------
# shard_map partial_fit: 8-device CPU mesh == single device, bit-for-bit
# ---------------------------------------------------------------------------


def test_shard_map_partial_fit_matches_single_device_subprocess():
    """(2, 2, 2) pod/data/model mesh: batch psum + D-slice generation
    (uhd_dynamic runs its Gray-code generator per D-slice) must match
    the single-device path exactly, for both encoders, over two
    accumulation steps, at D % tile != 0 and nonzero sobol_skip."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import HDCConfig, HDCModel, partial_fit_sharded
        from repro.core import hdc_model as hm
        from repro.launch.mesh import _make_mesh
        rng = np.random.default_rng(5)
        mesh = _make_mesh((2, 2, 2), ("pod", "data", "model"))
        for encoder in ("uhd", "uhd_dynamic"):
            cfg = HDCConfig(n_features=24, n_classes=4, d=700, levels=16,
                            sobol_skip=3, encoder=encoder)
            x = jnp.asarray(rng.uniform(0, 255, (32, 24)), jnp.float32)
            y = jnp.asarray(rng.integers(0, 4, (32,)), jnp.int32)
            single = hm.partial_fit(hm.partial_fit(HDCModel.create(cfg), x, y),
                                    x[:8], y[:8])
            sharded = HDCModel.create(cfg).shard(mesh)
            sharded = partial_fit_sharded(sharded, x, y, mesh=mesh)
            sharded = partial_fit_sharded(sharded, x[:8], y[:8], mesh=mesh)
            np.testing.assert_array_equal(np.asarray(sharded.class_sums),
                                          np.asarray(single.class_sums), err_msg=encoder)
            assert sharded.n_examples == single.n_examples == 40
            # class sums really are D-partitioned over the model axis
            spec = sharded.class_sums.sharding.spec
            assert tuple(spec) == (None, "model"), spec
        # indivisible global batch is refused loudly
        try:
            partial_fit_sharded(HDCModel.create(cfg).shard(mesh), x[:30], y[:30], mesh=mesh)
            raise SystemExit("indivisible batch not rejected")
        except ValueError:
            pass
        print("OK")
    """)
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env={"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"},
    )
    assert "OK" in r.stdout, r.stderr[-2000:]


# ---------------------------------------------------------------------------
# per-host checkpoint shards
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("encoder", ["uhd", "uhd_dynamic"])
def test_per_host_checkpoint_shards_roundtrip(tmp_path, encoder):
    """Each virtual host writes its D-slice through
    CheckpointManager.save_shard; after finalize_shards the stitched
    checkpoint restores bit-identically through the ordinary
    HDCModel.load."""
    cfg = _cfg(d=704, encoder=encoder)
    x, y = _data(cfg, n=20)
    model = HDCModel.create(cfg).fit(x, y)
    for pi in range(4):
        model.save_shard(tmp_path / "ckpt", step=3, process_index=pi, process_count=4)
    CheckpointManager(tmp_path / "ckpt").finalize_shards(3)
    restored = HDCModel.load(tmp_path / "ckpt")
    assert restored.cfg == cfg and restored.n_examples == 20
    np.testing.assert_array_equal(
        np.asarray(restored.class_sums), np.asarray(model.class_sums)
    )
    for k in model.codebooks:
        np.testing.assert_array_equal(
            np.asarray(restored.codebooks[k]), np.asarray(model.codebooks[k]), k
        )
    np.testing.assert_array_equal(
        np.asarray(restored.predict(x)), np.asarray(model.predict(x))
    )


def test_legacy_scalar_n_seen_checkpoint_still_loads(tmp_path):
    """Checkpoints written before the split counter stored n_seen as a
    () int32 scalar; load must adapt its restore template and normalize
    instead of failing the shape check."""
    cfg = _cfg()
    x, y = _data(cfg)
    model = HDCModel.create(cfg).fit(x, y)
    mgr = CheckpointManager(tmp_path / "ckpt")
    legacy_state = dict(model._state_tree(), n_seen=jnp.asarray(20, jnp.int32))
    raw_cfg = dataclasses.asdict(cfg)
    raw_cfg.pop("use_kernels", None)
    raw_cfg.pop("encode_impl", None)
    mgr.save(0, legacy_state, extra={"hdc_config": raw_cfg})
    restored = HDCModel.load(tmp_path / "ckpt")
    assert restored.n_examples == 20
    assert restored.n_seen.shape == (2,)
    np.testing.assert_array_equal(
        np.asarray(restored.class_sums), np.asarray(model.class_sums)
    )


def test_aborted_shard_attempt_cannot_tear_next_save(tmp_path):
    """Shard files staged by an aborted earlier attempt must never
    satisfy finalize's completeness check for a later attempt: host 0's
    save_shard clears the stale staging dir first."""
    cfg = _cfg(d=128)
    x, y = _data(cfg)
    run1 = HDCModel.create(cfg).fit(x, y)
    # attempt 1: all shards staged, but the job dies before finalize
    for pi in range(2):
        run1.save_shard(tmp_path / "ckpt", step=0, process_index=pi, process_count=2)
    # attempt 2 (after more training): host 0 writes, host 1 crashes
    run2 = run1.partial_fit(x, y)
    run2.save_shard(tmp_path / "ckpt", step=0, process_index=0, process_count=2)
    mgr = CheckpointManager(tmp_path / "ckpt")
    with pytest.raises(FileNotFoundError, match="missing shard"):
        mgr.finalize_shards(0)  # run-1's host-1 file is gone, not reused
    # completing attempt 2 publishes attempt-2 data only
    run2.save_shard(tmp_path / "ckpt", step=0, process_index=1, process_count=2)
    mgr.finalize_shards(0)
    restored = HDCModel.load(tmp_path / "ckpt")
    np.testing.assert_array_equal(
        np.asarray(restored.class_sums), np.asarray(run2.class_sums)
    )


def test_incomplete_shard_set_refuses_to_publish(tmp_path):
    cfg = _cfg(d=128)
    x, y = _data(cfg)
    model = HDCModel.create(cfg).fit(x, y)
    model.save_shard(tmp_path / "ckpt", step=0, process_index=0, process_count=2)
    mgr = CheckpointManager(tmp_path / "ckpt")
    with pytest.raises(FileNotFoundError, match="missing shard"):
        mgr.finalize_shards(0)
    assert mgr.all_steps() == []  # nothing published
    model.save_shard(tmp_path / "ckpt", step=0, process_index=1, process_count=2)
    mgr.finalize_shards(0)
    assert mgr.all_steps() == [0]
    with pytest.raises(ValueError, match="shards"):
        model.save_shard(tmp_path / "ckpt", step=1, process_index=0, process_count=3)
