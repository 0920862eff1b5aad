"""http.write_p99_ms: 99th percentile of the transport's response-write
span (`transport.server`, stage ``write``: labels ready -> bytes
flushed), over the window's requests only (histogram difference)."""

from bench.readers import span_ms


def read(run):
    return span_ms(run, "write", 99)
