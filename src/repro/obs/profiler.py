"""Program spans on the profiler's clock, and opt-in jax.profiler traces.

`span` is the cheap, always-on half — a context manager that names a
stretch of host work in the `jax.profiler` trace (a
``TraceAnnotation``, so it shares one clock with the device's ops)
and keeps its wall time for the stage histograms:

    with span("hdc.engine.step") as sp:
        labels = engine.predict(batch)   # returns host numpy: synced
    metrics.observe_stage("device", sp.elapsed_s)

Names take the form ``hdc.<layer>.<what>``; ``python.gc`` (from
`install_gc_span`) marks the interpreter's garbage collections.  With
no trace running a span costs about a microsecond.

`profile_capture` is the heavyweight, opt-in half: a bounded
`jax.profiler` trace window written to a directory (viewable with
TensorBoard / Perfetto), guarded behind ``POST /v1/debug/profile``
which is disabled by default on `HdcHttpServer`.
"""

from __future__ import annotations

import gc
import threading
import time

GC_SPAN = "python.gc"

_capture_lock = threading.Lock()


def _annotation(name: str):
    # imported on use: the rest of repro.obs stays free of JAX
    from jax.profiler import TraceAnnotation

    ann = TraceAnnotation(name)
    ann.__enter__()
    return ann


class span:
    """Context manager: a ``TraceAnnotation`` named `name` around the
    block, and ``elapsed_s``, the block's wall time (`perf_counter`)."""

    __slots__ = ("name", "elapsed_s", "_t0", "_ann")

    def __init__(self, name: str):
        self.name = name
        self.elapsed_s = 0.0

    def __enter__(self) -> "span":
        self._ann = _annotation(self.name)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.elapsed_s = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)


class _GcSpan:
    """``python.gc`` spans from a `gc.callbacks` hook.  The collector is
    one per process, so the hook is too: installs are counted and the
    hook leaves `gc.callbacks` with the last `remove`."""

    def __init__(self):
        self._lock = threading.Lock()
        self._users = 0
        self._open = None  # the annotation of the collection under way

    def __call__(self, phase: str, info: dict) -> None:
        # a collection runs to its end on the thread that started it,
        # holding the interpreter lock, and never nests
        if phase == "start":
            self._open = _annotation(GC_SPAN)
        elif self._open is not None:
            ann, self._open = self._open, None
            ann.__exit__(None, None, None)

    def install(self) -> None:
        with self._lock:
            self._users += 1
            if self._users == 1:
                gc.callbacks.append(self)

    def remove(self) -> None:
        with self._lock:
            if self._users == 0:
                return
            self._users -= 1
            if self._users == 0:
                gc.callbacks.remove(self)


_gc_span = _GcSpan()
install_gc_span = _gc_span.install
remove_gc_span = _gc_span.remove


def profile_capture(out_dir: str, ms: float) -> str:
    """Capture a ``jax.profiler`` trace for ``ms`` milliseconds into
    ``out_dir``; returns the directory.  One capture at a time —
    concurrent calls raise RuntimeError instead of corrupting the
    trace."""
    import jax

    if not _capture_lock.acquire(blocking=False):
        raise RuntimeError("a profile capture is already in progress")
    try:
        jax.profiler.start_trace(str(out_dir))
        time.sleep(max(0.0, float(ms)) / 1e3)
        jax.profiler.stop_trace()
    finally:
        _capture_lock.release()
    return str(out_dir)
