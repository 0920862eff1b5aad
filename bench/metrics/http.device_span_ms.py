"""http.device_span_ms: median of the engine's ``device`` span
(`serving.batcher` around `ServingEngine.predict`: host wall time of one
step, transfer and `block_until_ready` included), over the window's
requests.  It is host time, not device time."""

from bench.readers import span_ms


def read(run):
    return span_ms(run, "device", 50)
