"""Reduce a JAX profiler trace (``.xplane.pb``) to device intervals.

The profiler writes one plane per TPU (``/device:TPU:<i>``) whose
``XLA Ops`` line holds one event per operation that ran, named by its
HLO instruction (``%sojourn_enum.1 = (...) custom-call(...)``), and a
host plane whose ``python3`` thread line holds the harness's
``TraceAnnotation`` spans.  Times are nanoseconds on one clock.

:class:`Trace` keeps the operations that ran inside the traced window
(the harness's ``bench.window`` span) and answers what the per-layer
readers ask: how long the device was busy (the union of its operation
intervals, averaged over chips), how long one kernel ran, and which host
span each idle gap fell in.
"""

from __future__ import annotations

import glob
import os
import re

WINDOW_SPAN = "bench.window"
_OP_NAME = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)?(?: = |$)")


def op_name(event_name: str) -> str:
    """``'%sojourn_enum.1 = (f32[...]) custom-call(...)'`` -> ``'sojourn_enum'``."""
    m = _OP_NAME.match(event_name)
    return m.group(1) if m else event_name.split(" ", 1)[0]


def find(log_dir: str) -> str:
    """The one ``.xplane.pb`` file the profiler wrote under ``log_dir``."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, found {paths}")
    return paths[0]


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


class Trace:
    """Device operations and host spans of one traced window."""

    def __init__(self, path: str):
        from jax.profiler import ProfileData

        data = ProfileData.from_file(path)
        self.host_spans = []  # (start_ns, end_ns, name)
        device_ops = {}  # plane name -> [(start_ns, end_ns, op name)]
        for plane in data.planes:
            if plane.name.startswith("/device:TPU:"):
                ops = device_ops.setdefault(plane.name, [])
                for line in plane.lines:
                    if line.name == "XLA Ops":
                        ops.extend((e.start_ns, e.end_ns, op_name(e.name)) for e in line.events)
            elif plane.name == "/host:CPU":
                for line in plane.lines:
                    if line.name.startswith("python"):
                        self.host_spans.extend((e.start_ns, e.end_ns, e.name) for e in line.events)
        windows = [(s, e) for s, e, name in self.host_spans if name == WINDOW_SPAN]
        if len(windows) != 1:
            raise ValueError(f"expected one {WINDOW_SPAN!r} span, found {len(windows)}")
        self.start_ns, self.end_ns = windows[0]
        self.device_ops = {
            plane: [(max(s, self.start_ns), min(e, self.end_ns), n) for s, e, n in ops
                    if e > self.start_ns and s < self.end_ns]
            for plane, ops in sorted(device_ops.items())
        }

    @property
    def window_s(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9

    @property
    def n_devices(self) -> int:
        return len(self.device_ops)

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.device_ops:
            return 0.0
        total = sum(
            sum(e - s for s, e in _union((s, e) for s, e, _ in ops))
            for ops in self.device_ops.values()
        )
        return total * 1e-9 / len(self.device_ops)

    def op_seconds(self) -> dict[str, float]:
        """Device seconds per operation name, summed over the devices."""
        out: dict[str, float] = {}
        for ops in self.device_ops.values():
            for s, e, name in ops:
                out[name] = out.get(name, 0.0) + (e - s) * 1e-9
        return out

    def idle_gaps(self) -> dict[str, float]:
        """Idle seconds of the first device by the innermost host span open
        at each gap's midpoint (``idle`` when none is)."""
        if not self.device_ops:
            return {}
        ops = next(iter(self.device_ops.values()))
        busy = _union((s, e) for s, e, _ in ops)
        edges = [self.start_ns] + [x for iv in busy for x in iv] + [self.end_ns]
        # Spans of one thread nest, so a stack swept along the gaps in time
        # order holds the spans open at each point, the innermost on top.
        spans = sorted((sp for sp in self.host_spans if sp[2] != WINDOW_SPAN),
                       key=lambda sp: (sp[0], -sp[1]))
        stack, i = [], 0
        out: dict[str, float] = {}
        for s, e in zip(edges[::2], edges[1::2]):
            if e <= s:
                continue
            mid = (s + e) / 2
            while i < len(spans) and spans[i][0] <= mid:
                while stack and stack[-1][1] <= spans[i][0]:
                    stack.pop()
                stack.append(spans[i])
                i += 1
            while stack and stack[-1][1] <= mid:
                stack.pop()
            label = stack[-1][2] if stack else "idle"
            out[label] = out.get(label, 0.0) + (e - s) * 1e-9
        return out
