"""Shared fixture of the benchmark's CPU tests: a runner that drives a
whole run on the CPU at a tiny size, without the chip check and without
JAX's persistent compilation cache."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench_tiny import ROOT, TINY

SEED = 2**40 + 3
CHILD = Path(__file__).resolve().parent / "tiny_cell.py"


@pytest.fixture
def run_tiny(monkeypatch):
    """run_tiny(workload, trace=False, seconds=1.0, control=False, **kw) ->
    result dict.  A cell asking for more chips than this process has
    devices runs in a child process on that many virtual CPU devices;
    ``child=False`` keeps it here, its replicas sharing this process's
    devices (a fault planted by monkeypatching needs that)."""
    import jax

    from bench import harness

    import tiny_cell

    monkeypatch.setattr(harness, "enable_compile_cache", lambda root: "off")

    def go(workload, *, trace=False, seconds=1.0, seed=SEED, control=False,
           root=ROOT, bench=None, overrides=None, child=None):
        kw = dict(seed=seed, seconds=seconds, trace=trace, root=str(root),
                  bench=None if bench is None else str(bench),
                  overrides=overrides or TINY[workload], control=control)
        chips = harness.find_cell(harness.load_benchmark(Path(root)), workload)["chips"]
        if child is None:
            child = chips > jax.device_count()
        if not child:
            return tiny_cell.run(workload, **kw)
        flags = f"{os.environ.get('XLA_FLAGS', '')} " \
                f"--xla_force_host_platform_device_count={chips}"
        env = {**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": flags.strip()}
        r = subprocess.run([sys.executable, str(CHILD), json.dumps(dict(kw, workload=workload))],
                           env=env, capture_output=True, text=True, timeout=300)
        assert r.returncode == 0, r.stderr[-4000:]
        return json.loads(r.stdout.strip().splitlines()[-1])

    return go
