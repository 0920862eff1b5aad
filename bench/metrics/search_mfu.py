"""search_mfu: the whole search step's share of the chip's peak.

Per call of B queries: the larger of the encode's operations over the
int8 peak (2 * H * D per query) and the store's bytes over the HBM
peak (C * W * 4, read once), times the calls completed in the traced
window over its length.
"""


def read(run):
    red, calls = run.reduction, run.work.get("traced_calls")
    if red is None or run.peaks is None or not calls:
        return None
    cfg, t = run.cfg, run.traffic
    words = -(-cfg["d"] // 32)
    per_call = max(
        2.0 * cfg["n_features"] * cfg["d"] * t["batch"] / run.peaks["int8_ops_per_s"],
        4.0 * t["store_rows"] * words / run.peaks["hbm_bytes_per_s"],
    )
    return 100.0 * per_call * calls / red.window_s
