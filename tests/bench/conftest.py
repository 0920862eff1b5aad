"""Shared fixture of the benchmark's CPU tests: a runner that drives a
whole run on the CPU at a tiny size, without the chip check and without
JAX's persistent compilation cache."""

import pytest

from bench_tiny import TINY


@pytest.fixture
def run_tiny(monkeypatch):
    """run_tiny(workload, trace=False, seconds=1.0, **kw) -> result dict."""
    from bench import harness

    monkeypatch.setattr(harness, "enable_compile_cache", lambda root: "off")

    def go(workload, *, trace=False, seconds=1.0, seed=2**40 + 3, **kw):
        kw.setdefault("overrides", TINY[workload])
        return harness.run_cell(workload=workload, seed=seed, seconds=seconds,
                                trace=trace, require_tpu=False, **kw)

    return go
