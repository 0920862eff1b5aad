"""Reduction from a profiler trace to the numbers the metrics read.

A trace is flattened to `Event`s (plane, line, name, start, duration in
seconds, all on the profiler's one clock), so the reduction can be
tested on a small recorded trace without a chip.  The measured window
is the host span ``bench.window`` that the harness writes around it.

  * busy: the union of the intervals in which an operation ran on a
    device ("XLA Ops" line of a ``/device:TPU:<n>`` plane), clipped to
    the window; idle is the rest of the window.
  * kernel time: the summed device time of the ops whose HLO text
    matches a pattern, optionally only inside programs ("XLA Modules")
    whose name matches another.
  * idle gaps: each stretch of the window in which a device ran
    nothing, attributed to the most specific host span that covers it
    (the host event overlapping it most, the shortest among equals).
"""

from __future__ import annotations

import bisect
import dataclasses
import gzip
import json
import re
from collections import defaultdict
from pathlib import Path

WINDOW_SPAN = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
NO_SPAN = "(no host span)"
SHORT_S = 0.01  # host spans up to this long are found by binary search


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start: float
    dur: float

    @property
    def end(self) -> float:
        return self.start + self.dur


def load_xplane(path: str | Path) -> list[Event]:
    """Every event of an ``.xplane.pb`` file, times in seconds."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                out.append(Event(plane.name, line.name, e.name,
                                 e.start_ns * 1e-9, e.duration_ns * 1e-9))
    return out


def find_xplane(trace_dir: str | Path) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def save_events(events: list[Event], path: str | Path) -> None:
    rows = [[e.plane, e.line, e.name, e.start, e.dur] for e in events]
    with gzip.open(path, "wt") as f:
        json.dump(rows, f)


def load_events(path: str | Path) -> list[Event]:
    with gzip.open(path, "rt") as f:
        return [Event(*row) for row in json.load(f)]


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _clip(a: float, b: float, lo: float, hi: float) -> tuple[float, float] | None:
    a, b = max(a, lo), min(b, hi)
    return (a, b) if b > a else None


def _label(module: str, op: str) -> str:
    """Short name of a device op: program, instruction, custom-call mark."""
    prog = re.sub(r"\(\d+\)$", "", module)
    m = re.match(r"%?([^\s=]+)\s*=", op)
    inst = m.group(1) if m else op[:60]
    kernel = " [tpu_custom_call]" if 'custom_call_target="tpu_custom_call"' in op else ""
    return f"{prog}/{inst}{kernel}"[:160]


class Reduction:
    """The traced window of one run."""

    def __init__(self, events: list[Event], window: tuple[float, float] | None = None):
        if window is None:
            spans = [e for e in events if e.name == WINDOW_SPAN and HOST_PLANE in e.plane]
            if not spans:
                raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
            span = max(spans, key=lambda e: e.dur)
            window = (span.start, span.end)
        self.t0, self.t1 = window
        self.ops: dict[int, list[Event]] = defaultdict(list)
        self.modules: dict[int, list[Event]] = defaultdict(list)
        self.host: list[Event] = []
        self._mod_starts: dict[int, list[float]] = {}
        for e in events:
            m = DEVICE_PLANE.match(e.plane)
            if m:
                if _clip(e.start, e.end, self.t0, self.t1) is None:
                    continue
                if e.line == OPS_LINE:
                    self.ops[int(m.group(1))].append(e)
                elif e.line == MODULES_LINE:
                    self.modules[int(m.group(1))].append(e)
            elif HOST_PLANE in e.plane and e.name != WINDOW_SPAN:
                self.host.append(e)

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def devices(self) -> list[int]:
        return sorted(self.ops)

    def busy_intervals(self, device: int) -> list[tuple[float, float]]:
        spans = [_clip(e.start, e.end, self.t0, self.t1) for e in self.ops.get(device, [])]
        return _union([s for s in spans if s is not None])

    def busy_s(self, device: int) -> float:
        return sum(b - a for a, b in self.busy_intervals(device))

    def busy_mean_s(self, devices: list[int]) -> float:
        return sum(self.busy_s(d) for d in devices) / len(devices)

    def _module_of(self, device: int, op: Event) -> str:
        if device not in self._mod_starts:
            mods = sorted(self.modules.get(device, []), key=lambda e: e.start)
            self.modules[device] = mods
            self._mod_starts[device] = [m.start for m in mods]
        i = bisect.bisect_right(self._mod_starts[device], op.start) - 1
        if i >= 0:
            mod = self.modules[device][i]
            if op.end <= mod.end + 1e-9:
                return mod.name
        return ""

    def kernel_events(self, op_pattern: str, module_pattern: str | None = None,
                      devices: list[int] | None = None) -> list[tuple[int, Event]]:
        """Matching device ops that overlap the window."""
        op_re = re.compile(op_pattern)
        mod_re = re.compile(module_pattern) if module_pattern else None
        out = []
        for dev in devices if devices is not None else self.devices():
            for e in self.ops.get(dev, []):
                if not op_re.search(e.name):
                    continue
                if mod_re is not None and not mod_re.search(self._module_of(dev, e)):
                    continue
                out.append((dev, e))
        return out

    def kernel_s(self, op_pattern: str, module_pattern: str | None = None,
                 devices: list[int] | None = None) -> float:
        """Device time of matching ops inside the window, summed."""
        total = 0.0
        for _, e in self.kernel_events(op_pattern, module_pattern, devices):
            span = _clip(e.start, e.end, self.t0, self.t1)
            total += span[1] - span[0]
        return total

    def top_ops(self, n: int = 10) -> list[list]:
        """Device ops that took most time in the window, by label."""
        agg: dict[str, float] = defaultdict(float)
        for dev, evs in self.ops.items():
            for e in evs:
                span = _clip(e.start, e.end, self.t0, self.t1)
                agg[_label(self._module_of(dev, e), e.name)] += span[1] - span[0]
        return [[k, v] for k, v in sorted(agg.items(), key=lambda kv: -kv[1])[:n]]

    def gaps(self, device: int) -> list[tuple[float, float]]:
        out, t = [], self.t0
        for a, b in self.busy_intervals(device):
            if a > t:
                out.append((t, a))
            t = max(t, b)
        if self.t1 > t:
            out.append((t, self.t1))
        return out

    def idle_gaps(self, devices: list[int], n: int = 10) -> list[list]:
        """Idle time of the devices, summed by the host span covering it."""
        import numpy as np

        short = sorted((e for e in self.host if e.dur <= SHORT_S), key=lambda e: e.start)
        host = short + [e for e in self.host if e.dur > SHORT_S]
        names = [e.name for e in host]
        start = np.array([e.start for e in host])
        end = np.array([e.end for e in host])
        dur = end - start
        n_short = len(short)
        idx_long = np.arange(n_short, len(host))
        agg: dict[str, float] = defaultdict(float)
        for dev in devices:
            for a, b in self.gaps(dev):
                lo = int(np.searchsorted(start[:n_short], a - SHORT_S, "left"))
                hi = int(np.searchsorted(start[:n_short], b, "right"))
                cand = np.concatenate([np.arange(lo, hi), idx_long])
                over = np.minimum(b, end[cand]) - np.maximum(a, start[cand])
                best = NO_SPAN
                if cand.size and over.max() > 0:
                    top = cand[over >= over.max()]
                    best = names[top[np.argmin(dur[top])]]
                agg[best] += (b - a) / len(devices)
        return [[k, v] for k, v in sorted(agg.items(), key=lambda kv: -kv[1])[:n]]
