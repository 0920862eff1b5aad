"""pool.device_span_ms: median of the engines' ``device`` span (the
``hdc.engine.step`` span around `ServingEngine.predict`: host wall time
of one step, transfer and the results' copy to the host included),
merged over the pool's replicas, over the window's requests.  It is
host time, not device time."""

from bench.readers import span_ms


def read(run):
    return span_ms(run, "device", 50)
