"""fit_mfu: the whole fit step's share of the chip's int8 peak.

2 * H * D operations per image (a compare and an accumulate per image,
feature and dimension), times the images folded into class sums in
the traced window (whole epochs), over the traced window's length
(the ``bench.window`` span on the profiler's clock) and the int8 peak.
"""


def read(run):
    red, images = run.reduction, run.work.get("traced_images")
    if red is None or run.peaks is None or not images:
        return None
    ops = 2.0 * run.cfg["n_features"] * run.cfg["d"] * images
    return 100.0 * ops / red.window_s / run.peaks["int8_ops_per_s"]
