"""http.batch_fill: share (%) of the device steps' slots that carried a
request, not padding (`ServingMetrics.observe_batch` counters, window
deltas)."""


def read(run):
    slots = run.counters.get("n_slots")
    if not slots:
        return None
    return 100.0 * (slots - run.counters["n_padded"]) / slots
