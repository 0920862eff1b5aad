#!/usr/bin/env python3
"""The control of each cell's comparison: the plain reference put in
the program's place, one step of precision below the configuration's
(3-bit intensities and thresholds where it states 4), driven through a
whole run of the cell and judged by the cell's own check.

    python3 bench/control.py --workload search_dyn_store1m --seeds 1,2,3

Run it on the chip, at the cell's own sizes, by hand: each run must
come out not correct (the benchmark's runs never run it).  It prints
one JSON line per seed with ``correct`` and the checks.  What stands in
the program's place is the entry the cell's window drives, named by the
cell's driver (``control(cfg, traffic, seed)`` in
``bench/drivers/<driver>.py``, a list of (owner, attribute, stand-in)):
the fit step, `search_packed`, `ServingEngine.predict`.

The program's own lowering of each entry still answers the preflight's
look for compiled kernels; only the calls are replaced.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import math
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness  # noqa: E402
from bench.reference import Reference  # noqa: E402


def lower_reference(cfg: dict, seed: int) -> Reference:
    return Reference(cfg, seed, bits=int(math.log2(cfg["levels"])) - 1)


class Stand:
    """Stands in a jitted entry's place: a call runs `fn`, `lower`
    lowers the entry it replaces."""

    def __init__(self, real, fn):
        self.real, self.fn = real, fn

    def __call__(self, *args, **kwargs):
        return self.fn(*args, **kwargs)

    def lower(self, *args, **kwargs):
        return self.real.lower(*args, **kwargs)


@contextlib.contextmanager
def swapped(workload: str, seed: int, *, root: Path = harness.ROOT,
            bench: Path = harness.BENCH, overrides: dict | None = None):
    """The cell's timed entry replaced by the lower reference, for one seed."""
    _, _, cfg, traffic = harness.resolve(workload, root, bench, overrides)
    if str(root / "src") not in sys.path:
        sys.path.insert(0, str(root / "src"))
    driver = harness.load_module(harness.driver_file(bench, traffic["driver"]),
                                 f"bench_driver_{traffic['driver']}")
    swaps = driver.control(cfg, traffic, seed)
    saved = [(owner, name, getattr(owner, name)) for owner, name, _ in swaps]
    try:
        for owner, name, value in swaps:
            setattr(owner, name, value)
        yield
    finally:
        for owner, name, value in saved:
            setattr(owner, name, value)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="the window (default: run_seconds)")
    args = ap.parse_args(argv)
    seconds = args.seconds or harness.load_benchmark()["run_seconds"]
    for seed in (int(s) for s in args.seeds.split(",")):
        with swapped(args.workload, seed):
            out = harness.run_cell(workload=args.workload, seed=seed, seconds=seconds,
                                   trace=False)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": out["correct"], "checks": out["checks"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
