"""retrain_mfu: the whole retraining step's share of the chip's int8 peak.

The step's algorithmic work for B images, counted from the
configuration's shapes (H features, C classes, D dimensions, W = D/32
packed words), not from the implementation:

  * encode: a compare and an accumulate per (image, feature,
    dimension), 2 * B * H * D;
  * packed distance: an XOR and a popcount-accumulate per (image,
    class, word), 2 * B * C * W;
  * update: a multiply and an accumulate per (class, image,
    dimension) of the (C, B) x (B, D) signed fold, 2 * C * B * D.

Every term is linear in B, so the work of the traced window is the
images it retrained (whole epochs) times the work per image; over the
traced window's length (the ``bench.window`` span on the profiler's
clock) and the int8 peak.
"""


def ops_per_step(cfg: dict, batch: int) -> float:
    h, c, d = cfg["n_features"], cfg["n_classes"], cfg["d"]
    words = -(-d // 32)
    return 2.0 * batch * h * d + 2.0 * batch * c * words + 2.0 * c * batch * d


def read(run):
    red, images = run.reduction, run.work.get("traced_images")
    if red is None or run.peaks is None or not images:
        return None
    ops = ops_per_step(run.cfg, images)
    return 100.0 * ops / red.window_s / run.peaks["int8_ops_per_s"]
