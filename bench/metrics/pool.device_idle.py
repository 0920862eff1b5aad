"""pool.device_idle: share (%) of the traced window in which a chip ran
nothing (1 - union of device op intervals / window), averaged over the
pool's chips; from the profiler trace.  Moves predict_images_per_s."""

from bench.readers import idle_percent


def read(run):
    return idle_percent(run)
