"""Helpers shared by the per-layer metric readers in ``bench/metrics``."""

from __future__ import annotations


def idle_percent(run) -> float | None:
    """Share of the traced window in which no operation ran on the
    cell's chips (1 - union of device op intervals / window), averaged
    over the chips; None without a trace."""
    red = run.reduction
    if red is None:
        return None
    chips = list(range(run.n_chips))
    return 100.0 * (1.0 - red.busy_mean_s(chips) / red.window_s)


def span_ms(run, stage: str, p: float) -> float | None:
    """p-th percentile (ms) of one serving stage's span over the window."""
    hist = run.spans.get(stage)
    if hist is None:
        return None
    v = hist.percentile(p)
    return None if v is None else 1e3 * v
