"""What the per-layer readers ask of the program's own spans.

The program's profiling spans (``repro.obs.profiling``) reach a reader
two ways: as the registry's inclusive seconds per span name
(``ctx.spans``, without the ``prof.`` prefix), and, under the profiler,
as ``prof.<name>`` annotations among the trace's host spans
(``ctx.trace.host_spans``), on the clock of the device's operations.
A program without a span gives ``None`` here, never an error.
"""

from __future__ import annotations


def ms_per_trial(ctx, match) -> float | None:
    """Milliseconds a trial of the spans whose names ``match`` accepts."""
    seconds = [s for name, s in ctx.spans.items() if match(name)]
    if not seconds or not ctx.trials:
        return None
    return sum(seconds) / ctx.trials * 1e3


def phase_ms_per_trial(ctx, phase: str) -> float | None:
    """Milliseconds a trial of one phase of both ops on the chip path
    (``op_phase.<static|dynamic>.pallas.<phase>``)."""
    return ms_per_trial(
        ctx, lambda name: name.startswith("op_phase.") and name.endswith(f".pallas.{phase}"))


def union(intervals) -> list[tuple[int, int]]:
    """Sorted disjoint intervals covering the same points."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def intersect(a, b) -> list[tuple[int, int]]:
    """Points in both of two sorted disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def seconds(intervals) -> float:
    return sum(e - s for s, e in intervals) * 1e-9


def idle(trace) -> list[tuple[int, int]] | None:
    """The traced window's intervals in which the first device ran nothing."""
    if not trace.device_ops:
        return None
    ops = next(iter(trace.device_ops.values()))
    edges = [trace.start_ns]
    for s, e in union((s, e) for s, e, _ in ops):
        edges += [s, e]
    edges.append(trace.end_ns)
    return [(s, e) for s, e in zip(edges[::2], edges[1::2]) if s < e]


def covered(trace, prefix: str) -> list[tuple[int, int]]:
    """The union of the trace's host spans named ``prof.<prefix>...``."""
    name = "prof." + prefix
    return union((s, e) for s, e, n in trace.host_spans if n.startswith(name))


def idle_ms_per_trial(ctx, inside: str, outside: str | None = None) -> float | None:
    """Milliseconds a trial in which the first device was idle while a
    ``prof.<inside>`` span was open and no ``prof.<outside>`` span was."""
    gaps = idle(ctx.trace)
    within = covered(ctx.trace, inside)
    if gaps is None or not within or not ctx.trials:
        return None
    total = seconds(intersect(gaps, within))
    if outside is not None:
        total -= seconds(intersect(intersect(gaps, within), covered(ctx.trace, outside)))
    return total / ctx.trials * 1e3
