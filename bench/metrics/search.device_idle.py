"""search.device_idle: share (%) of the traced window in which the device ran
nothing (1 - union of device op intervals / window), averaged over the
cell's chips; from the profiler trace.  Moves search_queries_per_s."""

from bench.readers import idle_percent


def read(run):
    return idle_percent(run)
