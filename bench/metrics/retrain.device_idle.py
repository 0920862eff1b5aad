"""retrain.device_idle: share (%) of the traced window in which the device
ran nothing (1 - union of device op intervals / window); from the
profiler trace.  Moves fit_images_per_s in the retraining cell."""

from bench.readers import idle_percent


def read(run):
    return idle_percent(run)
