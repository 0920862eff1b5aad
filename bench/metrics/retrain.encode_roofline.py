"""retrain.encode_roofline: the retraining step's encode kernel's share of
its roofline.

Kernel time is the device time of the ops that carry ``hdc_kernel``
``encode_bundle_dynamic`` (the name in the custom call's
``kernel_metadata``) inside the jitted retrain step programs
(``jit__retrain_step_donated``), over the traced window.  The traced
window holds whole epochs (the driver closes it between epochs), so
the steps it ran follow from the traffic: each epoch is
``n_train // batch`` steps of ``batch`` images and one of the rest.
The least time the chip could take to encode a step of B images is
the larger of its operations over the int8 peak and its bytes over the
HBM peak, both counted from the configuration's shapes, not from one
kernel's padding or tiling:

  * operations: a compare and an accumulate per (image, feature,
    dimension), 2 * B * H * D (H unpadded);
  * bytes: the quantized inputs read (int32), the (H, 32) direction
    matrix once (one byte an entry), and the (B, D) int32 hypervectors
    written, which the step's predict and update read.
"""

OP_PATTERN = r"hdc_kernel\W+encode_bundle_dynamic\b"
MODULE_PATTERN = r"^jit__retrain_step"


def encode_ops_per_step(cfg: dict, batch: int) -> float:
    return 2.0 * batch * cfg["n_features"] * cfg["d"]


def encode_bytes_per_step(cfg: dict, batch: int) -> float:
    h, d = cfg["n_features"], cfg["d"]
    return 4.0 * batch * h + 32.0 * h + 4.0 * batch * d


def step_sizes(n_train: int, batch: int) -> list[int]:
    """Images per step of one epoch."""
    return [min(batch, n_train - i) for i in range(0, n_train, batch)]


def least_s_per_epoch(cfg: dict, traffic: dict, peaks: dict) -> float:
    return sum(max(encode_ops_per_step(cfg, b) / peaks["int8_ops_per_s"],
                   encode_bytes_per_step(cfg, b) / peaks["hbm_bytes_per_s"])
               for b in step_sizes(traffic["n_train"], traffic["batch"]))


def read(run):
    red, images = run.reduction, run.work.get("traced_images")
    if red is None or run.peaks is None or not images:
        return None
    kernel = red.kernel_s(OP_PATTERN, MODULE_PATTERN, [0])
    if kernel <= 0:
        return None
    epochs = images / run.traffic["n_train"]
    return 100.0 * epochs * least_s_per_epoch(run.cfg, run.traffic, run.peaks) / kernel
