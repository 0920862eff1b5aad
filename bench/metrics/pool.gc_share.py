"""pool.gc_share: share (%) of the traced window in which the server's
interpreter ran a garbage collection, from the program's ``python.gc``
spans (a `gc.callbacks` hook that `HdcHttpServer` installs), each
clipped to the window.  The collector holds the interpreter lock, so
the transport loop and every replica's drain thread wait while it runs.
None where the trace holds no ``python.gc`` span at all."""

SPAN = "python.gc"


def read(run):
    red = run.reduction
    if red is None:
        return None
    spans = [e for e in red.host if e.name == SPAN]
    if not spans:
        return None
    inside = sum(max(0.0, min(e.end, red.t1) - max(e.start, red.t0)) for e in spans)
    return 100.0 * inside / red.window_s
