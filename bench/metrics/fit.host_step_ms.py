"""fit.host_step_ms: median host time (ms) of one fit step, from the
program's ``hdc.fit.step`` spans (`HDCModel.fit_batches`: label
validation, the stateless view and the donated dispatch) in the
profiler trace, each clipped to the traced window.  The device runs
while the host does this; once the kernel is faster than the host's
step, this sets fit_images_per_s."""

import statistics

SPAN = "hdc.fit.step"


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
