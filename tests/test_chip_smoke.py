"""`chip_smoke.py` rehearsed on the CPU at a tiny size.

The smoke itself refuses to run anywhere but a TPU; these tests pin
that refusal and drive its phases here (Pallas in interpret mode, a
forced four-device host mesh for the cross-chip phases), so a change
that breaks the smoke's control flow fails before it costs chip time.
"""

import importlib.util
import os
import subprocess
import sys
import textwrap
from pathlib import Path


ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"
TINY = dict(d=256, n_train=64, fit_batch=32, n_requests=16, serve_batch=8,
            store_rows=64, top_k=5, n_queries=4)


def _load(monkeypatch):
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    monkeypatch.setitem(sys.modules, "chip_smoke", mod)  # dataclasses need it
    spec.loader.exec_module(mod)
    return mod


def _run(cwd, script, *args):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, str(script), *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )


def test_refuses_without_tpu():
    r = _run(ROOT, SCRIPT)
    assert r.returncode not in (0, None), r.stdout
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr, r.stderr[-2000:]


def test_refuses_outside_a_checkout(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    alone.write_text(SCRIPT.read_text())
    r = _run(tmp_path, alone)
    assert r.returncode == 2 and not r.stdout, (r.stdout, r.stderr)


def test_one_chip_phases_tiny(tmp_path, monkeypatch):
    from repro.data import load_dataset

    cs = _load(monkeypatch)
    sizes = cs.Sizes(**TINY)
    ds = load_dataset("synth_mnist", n_train=sizes.n_train, n_test=sizes.n_requests)
    models = cs.phase_fit(ds, sizes, backend="pallas")
    assert set(models) == {"uhd", "uhd_dynamic"}
    cs.phase_serve(ds, models, sizes, tmp_path, impl="pallas")
    cs.phase_search(ds, models, sizes, impl="pallas")


def test_four_chip_phases_tiny_subprocess(tmp_path):
    code = textwrap.dedent(f"""
        import importlib.util, sys
        from pathlib import Path
        import jax
        spec = importlib.util.spec_from_file_location("chip_smoke", {str(SCRIPT)!r})
        cs = sys.modules["chip_smoke"] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(cs)
        from repro.data import load_dataset
        sizes = cs.Sizes(**{TINY!r})
        ds = load_dataset("synth_mnist", n_train=sizes.n_train, n_test=sizes.n_requests)
        models = cs.phase_fit(ds, sizes)
        devices = jax.devices()
        assert len(devices) == 4, devices
        cs.phase_pool(ds, models, sizes, Path({str(tmp_path)!r}), devices)
        cs.phase_sharded(ds, models, sizes, devices)
        cs.phase_fit_sharded(ds, models, sizes, devices)
        print("OK")
    """)
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": str(ROOT / "src")}
    r = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0 and r.stdout.strip().endswith("OK"), r.stderr[-3000:]
    for phase in ("pool:", "sharded:", "fit_sharded:"):
        assert phase in r.stdout, r.stdout


def test_compile_cache_placed_from_outside_else_fixed_in_checkout(monkeypatch, tmp_path):
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before  # nothing set in code
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        fixed = str(ROOT / ".jax_cache")
        assert enable_compile_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
