"""search.encode_ms: device time (ms) of the query encode per search
call: the ops that carry ``hdc_kernel`` ``encode_bundle_dynamic`` (the
kernel's name in its custom call's ``kernel_metadata``) inside the
jitted search step, over the traced window, divided by the calls made
there (``traced_calls``; the window holds whole calls).  Moves
search_queries_per_s: the encode runs before `hamming_topk`'s scan of
the store, on the same chip."""

OP_PATTERN = r"hdc_kernel\W+encode_bundle_dynamic\b"
MODULE_PATTERN = r"^jit_search_packed"


def read(run):
    red, calls = run.reduction, run.work.get("traced_calls")
    if red is None or not calls:
        return None
    kernel = red.kernel_s(OP_PATTERN, MODULE_PATTERN, [0])
    if kernel <= 0:
        return None
    return 1e3 * kernel / calls
