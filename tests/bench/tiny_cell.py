"""Run one cell at its tiny size on the CPU, without the chip check and
without JAX's persistent compilation cache, and return the result line.

The benchmark's CPU tests call `run` in their own process.  A cell that
asks for more chips than that process has devices runs here as a script,
in a child whose ``XLA_FLAGS`` give the CPU as many virtual devices
(``--xla_force_host_platform_device_count``):

    python tests/bench/tiny_cell.py '{"workload": "...", "seed": 5, ...}'

prints the result line as its last line of standard output.
"""

import contextlib
import json
import sys
from pathlib import Path

from bench_tiny import ROOT


def run(workload, *, seed, seconds=1.0, trace=False, root=ROOT, bench=None,
        overrides=None, control=False):
    """One run; with ``control`` the cell's timed entry is replaced by the
    lower-precision reference (`bench/control.py`)."""
    from bench import control as controls
    from bench import harness

    bench = Path(bench) if bench is not None else harness.BENCH
    swap = (controls.swapped(workload, seed, root=Path(root), bench=bench,
                             overrides=overrides)
            if control else contextlib.nullcontext())
    with swap:
        return harness.run_cell(workload=workload, seed=seed, seconds=seconds,
                                trace=trace, require_tpu=False, root=Path(root),
                                bench=bench, overrides=overrides)


def main(argv) -> int:
    from bench import harness

    harness.enable_compile_cache = lambda root: "off"
    out = run(**json.loads(argv[1]))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv))
