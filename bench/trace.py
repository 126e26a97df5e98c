"""From a profiler trace (``.xplane.pb``) to device busy time, idle gaps
named by what the host was doing, and the device operations that took the
most time.

``events(path)`` flattens the trace into plain rows, which
``reduce_trace`` turns into numbers; the split lets a recorded excerpt
(``bench/testdata/``) check the reduction without a chip.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

# the device line whose events are single operations, and the line whose
# events are whole compiled programs
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# host spans the harness writes around the serving loop's hooks
HOST_SPANS = ("admit", "dispatch", "sample", "scan")


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def op_name(text: str) -> str:
    """An HLO instruction's name from the trace's text of it
    (``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``)."""
    return text.split(" = ", 1)[0].lstrip("%")


def events(path: str) -> List[Event]:
    """Device operations and modules, and the harness's host spans."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name in (OPS_LINE, MODULES_LINE):
                keep = lambda e: True
            elif plane.name.startswith("/host:"):
                keep = lambda e: e.name in HOST_SPANS
            else:
                continue
            for e in line.events:
                if keep(e):
                    name = op_name(e.name) if line.name == OPS_LINE else e.name
                    out.append(Event(plane.name, line.name, name,
                                     float(e.start_ns), float(e.duration_ns)))
    return out


def _self_times(ops: Sequence[Event]) -> Dict[str, float]:
    """Nanoseconds by operation name, each less the time of the operations
    nested in it (a ``while`` holds its body's operations)."""
    out: Dict[str, float] = {}
    stack: List[List] = []                 # [end_ns, name, child_ns]
    for e in sorted(ops, key=lambda e: (e.start_ns, -e.dur_ns)):
        while stack and stack[-1][0] <= e.start_ns:
            end, name, child = stack.pop()
            out[name] = out.get(name, 0.0) - child
        if stack:
            stack[-1][2] += min(e.dur_ns, stack[-1][0] - e.start_ns)
        out[e.name] = out.get(e.name, 0.0) + e.dur_ns
        stack.append([e.start_ns + e.dur_ns, e.name, 0.0])
    for end, name, child in stack:
        out[name] = out.get(name, 0.0) - child
    return out


def _union(intervals: Sequence[Tuple[float, float]]
           ) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


@dataclasses.dataclass
class TraceSummary:
    busy_s: float                 # union of device op intervals, mean/chip
    module_s: Dict[str, float]    # device time by compiled program name
    device_ops: List[Tuple[str, float]]   # top operations by self time
    idle_gaps: List[Tuple[str, float]]    # longest gaps, by host span
    n_devices: int


def reduce_trace(evs: Sequence[Event], *, top: int = 10) -> TraceSummary:
    devices = sorted({e.plane for e in evs if e.line == OPS_LINE})
    if not devices:
        raise ValueError("the trace holds no device operations")
    busy = 0.0
    gaps: List[Tuple[float, float]] = []
    by_op: Dict[str, float] = {}
    for dev in devices:
        ops = [e for e in evs if e.plane == dev and e.line == OPS_LINE]
        merged = _union([(e.start_ns, e.start_ns + e.dur_ns) for e in ops])
        busy += sum(e - s for s, e in merged)
        gaps += [(merged[i][1], merged[i + 1][0])
                 for i in range(len(merged) - 1)]
        for name, ns in _self_times(ops).items():
            by_op[name] = by_op.get(name, 0.0) + ns
    module_s: Dict[str, float] = {}
    for e in evs:
        if e.line == MODULES_LINE:
            module_s[e.name] = module_s.get(e.name, 0.0) + e.dur_ns * 1e-9
    host = sorted((e.start_ns, e.start_ns + e.dur_ns, e.name) for e in evs
                  if e.line not in (OPS_LINE, MODULES_LINE))

    def doing(s: float, e: float) -> str:
        """The host span that covers most of [s, e)."""
        best, name = 0.0, "between_hooks"
        for hs, he, hn in host:
            if hs >= e:
                break
            cover = min(he, e) - max(hs, s)
            if cover > best:
                best, name = cover, hn
        return name

    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    return TraceSummary(
        busy_s=busy * 1e-9 / len(devices),
        module_s=module_s,
        device_ops=[(n, d * 1e-9) for n, d in ops],
        idle_gaps=[(doing(s, e), (e - s) * 1e-9) for s, e in longest],
        n_devices=len(devices))


def module_time(summary: TraceSummary, prefix: str) -> Optional[float]:
    """Device seconds of the compiled programs whose name starts with
    ``prefix`` (``jit_burst`` is the serving step's burst), or None."""
    hits = [s for n, s in summary.module_s.items() if n.startswith(prefix)]
    return sum(hits) if hits else None
