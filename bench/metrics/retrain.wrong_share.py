"""retrain.wrong_share: share (%) of the images retrained in the window
that the step mispredicted, and so folded into the class sums: the
program's own per-step counts (`HDCModel.retrain_batches`), summed
over the window's epochs.  The fold is the part of a step's work that
depends on the data; fit_images_per_s is judged at this share."""


def read(run):
    counts = run.counters.get("step_mispredicts")
    images = run.work.get("images")
    if not counts or not images:
        return None
    return 100.0 * sum(int(c.sum()) for c in counts) / images
