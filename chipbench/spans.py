"""Device-idle time put down to the program's own host spans.

The program marks the layer boundaries of its served path with host
spans on the profiler's clock (``repro.core.tracing.HOST_SPANS``):
``coordinator.event`` holds one event of the coordinator's loop, and
``backend.execute`` one backend call inside it.  For each interval in
which no op ran on the device (``xplane.idle_gaps``), the reader counts
the part of it that the host spent inside the spans named.  Events match
on their name up to any ``#``; overlapping events of one name count
once.  Where the trace holds no event of a name it returns None: a
program without the spans reads nothing.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

from chipbench import xplane

Intervals = List[Tuple[float, float]]


def named(trace: xplane.Trace, name: str) -> Intervals:
    """The union of the host events called ``name``."""
    return xplane.union(e for e in trace.host
                        if e.name.split("#", 1)[0] == name)


def intersect(a: Intervals, b: Intervals) -> Intervals:
    """Intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if lo < hi:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a: Intervals, b: Intervals) -> Intervals:
    """``a`` less ``b``, both sorted lists of disjoint intervals."""
    out, j = [], 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > lo:
                out.append((lo, b[k][0]))
            lo = max(lo, b[k][1])
            k += 1
        if lo < hi:
            out.append((lo, hi))
    return out


def idle_in(trace: xplane.Trace, dev: xplane.Device, name: str,
            outside: Sequence[str] = ()) -> Optional[float]:
    """Device-idle seconds in which the host was inside a span ``name``
    and inside none of ``outside``; None when no span ``name`` is in the
    trace."""
    spans = named(trace, name)
    if not spans:
        return None
    idle = intersect(xplane.idle_gaps(dev), spans)
    for other in outside:
        idle = subtract(idle, named(trace, other))
    return sum(b - a for a, b in idle)


def idle_ms_per_segment(r, name: str, outside: Sequence[str] = ()
                        ) -> Optional[float]:
    """:func:`idle_in` of the readings' trace, in ms per segment dispatch
    of the traced window."""
    dev, seg = r.device(), r.segment_dispatches()
    if dev is None or not seg:
        return None
    s = idle_in(r.trace, dev, name, outside)
    return None if s is None else 1e3 * s / len(seg)
