"""search.topk_roofline: `hamming_topk`'s share of its roofline.

Kernel time is the device time of the streaming top-k Pallas kernel
(the custom call with the (B, k) int32 pair as its output) inside the
jitted search step, over the traced window.  The traced window holds
whole calls (the driver closes it between calls), so the calls come
from the driver's count.  Per call of B queries over C rows of W
words, the least time is the larger of

  * bytes over the HBM peak: the store once (C * W * 4), the queries
    (B * W * 4) and the outputs (2 * B * k * 4);
  * operations over the int8 peak: B * C * W word pairs
    (XOR + popcount + add counted as one, so the bound is generous).
"""

OP_PATTERN = r'^\S+ = \(s32\[\d+,\d+\]\{[^}]*\}, s32\[\d+,\d+\]\{[^}]*\}\) custom-call\(u32\['
MODULE_PATTERN = r"^jit_search_packed"


def bytes_per_call(b: int, c: int, w: int, k: int) -> float:
    return 4.0 * (c * w + b * w + 2 * b * k)


def ops_per_call(b: int, c: int, w: int) -> float:
    return float(b) * c * w


def read(run):
    red, calls = run.reduction, run.work.get("traced_calls")
    if red is None or run.peaks is None or not calls:
        return None
    kernel = red.kernel_s(OP_PATTERN, MODULE_PATTERN, [0])
    if kernel <= 0:
        return None
    b, c, k = run.traffic["batch"], run.traffic["store_rows"], run.traffic["k"]
    w = -(-run.cfg["d"] // 32)
    least = max(bytes_per_call(b, c, w, k) / run.peaks["hbm_bytes_per_s"],
                ops_per_call(b, c, w) / run.peaks["int8_ops_per_s"])
    return 100.0 * least * calls / kernel
