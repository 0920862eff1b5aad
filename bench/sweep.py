#!/usr/bin/env python3
"""Find the knee of an open-loop serving cell: the highest offered rate
at which no request is shed and the backlog does not grow.

    python3 bench/sweep.py --workload predict_dyn_http_poisson \\
        --rates 500,1000,2000 --seconds 4 --seed 7

Run it on the chip once, by hand; the cell's traffic file then fixes
its rate (about 4/5 of the knee).  Each rate is one window of the
cell's own set-up, in one process.  The backlog grows when the last
fifth of the requests waits more than twice as long as the first fifth
(plus 5 ms).
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness  # noqa: E402


def one_rate(workload: str, rate: float, seconds: float, seed: int) -> dict:
    benchmark, cell, cfg, traffic = harness.resolve(workload)
    traffic["rate_per_s"] = rate
    driver = harness.load_module(harness.driver_file(harness.BENCH, traffic["driver"]),
                                f"bench_driver_{traffic['driver']}")
    run = harness.Run(benchmark=benchmark, cell=cell, cfg=cfg, traffic=traffic,
                      seed=seed, seconds=seconds, trace=False)
    harness.describe_device(run, require_tpu=True)
    try:
        driver.setup(run)
        res = driver.window(run)
    finally:
        driver.release(run)
    lat = driver.latency_ms(res)
    n = len(lat)
    first, last = lat[: n // 5], lat[-n // 5 :]
    out = {
        "rate": rate,
        "requests": n,
        "ok": int((res["status"] == 200).sum()),
        "shed": int((res["status"] == 429).sum()),
        "p50_ms": float(np.percentile(lat, 50)),
        "p99_ms": float(np.percentile(lat, 99)),
        "first_fifth_p50_ms": float(np.median(first)),
        "last_fifth_p50_ms": float(np.median(last)),
        "achieved_per_s": n / (float(np.nanmax(res["done"])) - float(res["t0"])),
        "batch_fill": (run.counters["n_slots"] - run.counters["n_padded"])
        / max(1, run.counters["n_slots"]),
    }
    out["sustained"] = (out["shed"] == 0 and out["ok"] == n
                        and out["last_fifth_p50_ms"] <= 2 * out["first_fifth_p50_ms"] + 5)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(harness.ROOT / "src"))
    harness.enable_compile_cache(harness.ROOT)
    knee = None
    for rate in (float(r) for r in args.rates.split(",")):
        out = one_rate(args.workload, rate, args.seconds, args.seed)
        print(json.dumps(out), flush=True)
        if out["sustained"]:
            knee = rate
    print(json.dumps({"knee_per_s": knee}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
