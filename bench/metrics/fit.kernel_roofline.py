"""fit.kernel_roofline: the fit kernels' share of their roofline.

Kernel time is the device time of every Pallas kernel inside the jitted
fit step programs (``jit__partial_fit*``), over the traced window.  The
traced window holds whole epochs (the driver closes it between
epochs), so the steps it ran follow from the traffic: each epoch is
``n_train // batch`` steps of ``batch`` images and one of the rest.
The least time the chip could take for a step of B images is the
larger of its operations over the int8 peak and its bytes over the HBM
peak.  Both count the algorithm's work at the configuration's shapes,
not one kernel's padding, tiling or split into programs:

  * operations: a compare and an accumulate per (image, feature,
    dimension), 2 * H * D per image (H unpadded);
  * bytes: the quantized inputs (int32), the labels (int32), the
    threshold table once at its stored width (int8 for "uhd"; the
    (H, 32) direction matrix for "uhd_dynamic"), and the (C, D) int32
    class sums read and written.
"""

OP_PATTERN = r'custom_call_target="tpu_custom_call"'
MODULE_PATTERN = r"^jit__partial_fit"


def ops_per_step(cfg: dict, batch: int) -> float:
    return 2.0 * cfg["n_features"] * cfg["d"] * batch


def bytes_per_step(cfg: dict, batch: int) -> float:
    h, d, c = cfg["n_features"], cfg["d"], cfg["n_classes"]
    state = h * d * 1 if cfg["encoder"] == "uhd" else h * 32 * 1
    return 4.0 * batch * h + 4.0 * batch + state + 2 * 4.0 * c * d


def step_sizes(n_train: int, batch: int) -> list[int]:
    """Images per step of one epoch."""
    return [min(batch, n_train - i) for i in range(0, n_train, batch)]


def least_s_per_epoch(cfg: dict, traffic: dict, peaks: dict) -> float:
    return sum(max(ops_per_step(cfg, b) / peaks["int8_ops_per_s"],
                   bytes_per_step(cfg, b) / peaks["hbm_bytes_per_s"])
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
