"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the
benchmark reports: device busy time, per-program and per-op device time,
the idle gaps between programs, and what the host was doing in them.

Device planes are ``/device:TPU:<n>``.  On each, the ``XLA Modules`` line
holds one event per program execution (named ``<jit name>(<id>)``) and
the ``XLA Ops`` line one event per operation, named by its HLO text
(``%mha.5 = bf16[48,4429,64]... custom-call(...)``); a loop's event
encloses the events of its body.  Busy time is the union of the op
intervals; an op belongs to the program whose execution contains it, and
its self time leaves out the ops it encloses.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


@dataclasses.dataclass
class Event:
    name: str
    start: float           # seconds on the trace clock
    end: float
    program: str = ""      # the program (module) an op ran in
    self_s: float = 0.0    # duration less the ops it encloses

    @property
    def seconds(self) -> float:
        return self.end - self.start

    @property
    def op(self) -> str:
        """``%fusion.280 = bf16[2,4096,1536]{...} fusion(...)`` ->
        ``fusion.280 bf16[2,4096,1536]``."""
        m = re.match(r"%?([\w.\-]+) = (\S+?)(\{|\s|$)", self.name)
        return f"{m.group(1)} {m.group(2)}" if m else self.name[:80]


@dataclasses.dataclass
class Device:
    name: str
    programs: List[Event]
    ops: List[Event]

    def busy_intervals(self) -> List[Tuple[float, float]]:
        return union(self.ops or self.programs)

    @property
    def busy_s(self) -> float:
        return sum(b - a for a, b in self.busy_intervals())


@dataclasses.dataclass
class Trace:
    devices: List[Device]
    host: List[Event]

    @property
    def busy_s(self) -> float:
        """Device busy seconds, averaged over the chips in the trace."""
        return sum(d.busy_s for d in self.devices) / len(self.devices)


def program_name(event_name: str) -> str:
    """``jit_run(123)`` -> ``jit_run``."""
    return re.sub(r"\(\d+\)$", "", event_name.strip())


def union(events: Iterable) -> List[Tuple[float, float]]:
    spans = sorted((e.start, e.end) if isinstance(e, Event) else tuple(e)
                   for e in events)
    out: List[List[float]] = []
    for a, b in spans:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _events(line) -> List[Event]:
    return [Event(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
            for e in line.events]


def _assign_programs(programs: List[Event], ops: List[Event]) -> None:
    starts = [p.start for p in programs]
    for op in ops:
        i = bisect.bisect_right(starts, op.start) - 1
        if i >= 0 and op.end <= programs[i].end + 1e-9:
            op.program = programs[i].name


def _self_times(ops: List[Event]) -> None:
    """Self time of each op, its enclosed ops (a loop's body) left out;
    ``ops`` sorted by start."""
    stack: List[Event] = []
    for op in ops:
        op.self_s = op.seconds
        while stack and stack[-1].end <= op.start:
            stack.pop()
        if stack and op.end <= stack[-1].end + 1e-12:
            stack[-1].self_s -= op.seconds
        stack.append(op)


def from_profile(pd) -> Trace:
    """A ``jax.profiler.ProfileData`` reduced to device and host events."""
    devices, host = [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            if MODULES_LINE not in lines or OPS_LINE not in lines:
                continue
            programs = sorted(_events(lines[MODULES_LINE]), key=lambda e: e.start)
            for p in programs:
                p.name = program_name(p.name)
            ops = sorted(_events(lines[OPS_LINE]), key=lambda e: e.start)
            _assign_programs(programs, ops)
            _self_times(ops)
            devices.append(Device(plane.name, programs, ops))
        elif plane.name == HOST_PLANE:
            for ln in plane.lines:
                host.extend(e for e in _events(ln) if e.end > e.start)
    if not devices:
        raise ValueError("trace holds no TPU device plane with XLA ops")
    return Trace(sorted(devices, key=lambda d: d.name), host)


def load(log_dir: str) -> Trace:
    """The newest ``.xplane.pb`` under a profiler log directory."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return from_profile(ProfileData.from_file(max(paths, key=os.path.getmtime)))


# ----------------------------------------------------------------- readings

def program_seconds(dev: Device, program: str) -> Tuple[float, int]:
    """Device seconds and executions of one program."""
    runs = [p for p in dev.programs if p.name == program]
    return sum(p.seconds for p in runs), len(runs)


def op_seconds(dev: Device, op_pattern: str, program: Optional[str] = None
               ) -> Tuple[float, int]:
    """Device seconds and count of the ops whose HLO text matches
    ``op_pattern`` (a regular expression, searched), inside ``program``
    if given."""
    rx = re.compile(op_pattern)
    ops = [o for o in dev.ops if rx.search(o.name)
           and (program is None or o.program == program)]
    return sum(o.seconds for o in ops), len(ops)


def idle_between(dev: Device, program: str) -> List[float]:
    """For each pair of consecutive executions of ``program``, the device
    seconds between them in which no op ran."""
    runs = [p for p in dev.programs if p.name == program]
    busy = dev.busy_intervals()
    out = []
    for a, b in zip(runs, runs[1:]):
        lo, hi = a.end, b.start
        covered = sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in busy)
        out.append(max(0.0, (hi - lo) - covered))
    return out


def idle_gaps(dev: Device) -> List[Tuple[float, float]]:
    """Intervals between consecutive busy intervals."""
    busy = dev.busy_intervals()
    return [(a[1], b[0]) for a, b in zip(busy, busy[1:]) if b[0] > a[1]]


def top_ops(dev: Device, n: int = 10) -> List[List]:
    """The ``n`` ops that took most device self time, summed over their
    executions, as ``[<program>/<op> <output type>, seconds]``."""
    total: Dict[str, float] = {}
    for o in dev.ops:
        key = f"{o.program}/{o.op}"
        total[key] = total.get(key, 0.0) + o.self_s
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def named_gaps(trace: Trace, dev: Device, n: int = 10) -> List[List]:
    """The ``n`` longest idle gaps of a device, each named by the
    shortest host event that covers its midpoint (``host:idle`` when none
    does), as ``[name, seconds]``."""
    host = trace.host
    out = []
    for a, b in sorted(idle_gaps(dev), key=lambda g: g[0] - g[1])[:n]:
        mid = 0.5 * (a + b)
        cover = [e for e in host if e.start <= mid <= e.end]
        name = min(cover, key=lambda e: e.seconds).name if cover else "host:idle"
        out.append([name[:120], b - a])
    return out
