"""pool.queue_p99_ms: 99th percentile of the batchers' queue span
(`serving.batcher`, stage ``queue``: submit -> taken into a device
step), merged over the pool's replicas, over the window's requests
only."""

from bench.readers import span_ms


def read(run):
    return span_ms(run, "queue", 99)
