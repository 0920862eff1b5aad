"""retrain.host_step_ms: median host time (ms) of one retraining step,
from the program's ``hdc.retrain.step`` spans
(`HDCModel.retrain_batches`: the served class words of the step's
starting sums and the donated dispatch) in the profiler trace, each
clipped to the traced window.  The device runs while the host does
this; once the step's kernels are faster than the host's step, this
sets fit_images_per_s."""

import statistics

SPAN = "hdc.retrain.step"


def read(run):
    red = run.reduction
    if red is None:
        return None
    durations = []
    for e in red.host:
        if e.name == SPAN:
            a, b = max(e.start, red.t0), min(e.end, red.t1)
            if b > a:
                durations.append(b - a)
    return 1e3 * statistics.median(durations) if durations else None
