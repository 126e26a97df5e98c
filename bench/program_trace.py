"""What the program's own spans and scopes say: device time by model-step
scope and device idle time by program span, from a profiler trace, and
the engine loop's readings from the program's tracer.

The program names the parts of its serving step with ``jax.named_scope``
(``models/transformer.py``, ``serving/engine_loop._fused_step``), which
XLA keeps as each instruction's ``op_name`` in the compiled program (the
trace itself names only the instruction); and its live spans
(``repro.obs.Tracer.scope``) land in the profiler's trace as host events
with their args, on the device's clock.  ``scoped_events`` reads both out
of a trace, given the step's compiled programs; ``device_scopes`` and
``idle_by_span`` reduce them.
A recorded excerpt (``bench/testdata/program_trace_excerpt.json``) checks
the reduction without a chip.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from typing import Dict, List, Optional, Sequence, Tuple

from .trace import (MODULES_LINE, OPS_LINE, Event, _self_times, _union,
                    op_name)

# the program's live spans (repro.obs.Tracer.scope) and its model-step
# scopes (jax.named_scope)
PROGRAM_SPANS = ("iteration", "admit", "burst", "sync", "emit")
PROGRAM_SCOPES = ("embed", "layer_scan", "attention", "mlp", "head",
                  "feed_sample", "weight_cast")
ATTENTION_PARTS = ("kv_write", "core")
UNSCOPED = "(unscoped)"
NO_SPAN = "(none)"
# the compiled program the serving step runs (XLA Modules names it
# ``jit_burst(<id>)``)
STEP_MODULE = "jit_burst"


@dataclasses.dataclass(frozen=True)
class Span:
    """A program span in the trace: its name (``sync`` spans keep their
    kind: ``sync.drain``, ``sync.completion``) and its args."""
    name: str
    start_ns: float
    dur_ns: float
    args: Tuple[Tuple[str, str], ...] = ()

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def scope_of(path: str) -> str:
    """The program scope an operation's ``op_name`` path names: a cast of
    a parameter counts as ``weight_cast`` wherever it sits; otherwise the
    innermost program scope (a block inside ``layer_scan`` is its block),
    with ``attention`` split into its parts."""
    parts = path.split("/")
    if "weight_cast" in parts:
        return "weight_cast"
    scope = UNSCOPED
    for i, p in enumerate(parts):
        if p in PROGRAM_SCOPES:
            scope = p
            nxt = parts[i + 1] if i + 1 < len(parts) else None
            if p == "attention" and nxt in ATTENTION_PARTS:
                scope = f"attention/{nxt}"
    return scope


_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = \(?(\w+)")
_CALL = re.compile(r"\s([a-z][\w\-]*)\((%[\w.\-]+)?")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_COMPUTATION = re.compile(r"^\s*(ENTRY )?%?([\w.\-]+) .*\{\s*$")
# operations that pass one value on (an async copy, a relayout, a cast, a
# tuple element)
_MOVES = ("copy-start", "copy-done", "bitcast", "copy", "convert",
          "transpose", "reshape", "get-tuple-element")


def op_paths_from_hlo(text: str) -> Dict[str, str]:
    """Instruction name -> ``op_name`` (``""`` where it has none) from a
    compiled program's HLO text (``compiled.as_text()``), whose
    instruction names are the trace's.

    Operations the compiler adds or rewrites can lose their ``op_name``;
    three rules give it back.  A fusion takes that of its fused
    computation's root, or else of the last instruction in it that has
    one.  A relayout, convert or move of one value (a copy, a bitcast, a
    tuple element) takes that of the value it moves.  A ``convert`` (or a
    converting ``copy``) of one of the program's parameters, which the
    compiler hoists out of the layer scan, is ``weight_cast``."""
    paths: Dict[str, str] = {}
    calls: Dict[str, str] = {}
    inner: Dict[str, str] = {}           # computation -> op_name inside it
    moved: Dict[str, str] = {}           # no op_name -> the value it moves
    params: Dict[str, str] = {}          # entry parameter -> element type
    origin: Dict[str, str] = {}          # entry value -> the parameter moved
    casts: List[str] = []
    comp, entry = None, False
    for line in text.splitlines():
        m = _INSTR.match(line)
        if not m:
            c = _COMPUTATION.match(line)
            if c:
                entry, comp = bool(c.group(1)), c.group(2)
            continue
        name, etype = m.group(1), m.group(2)
        op = _OP_NAME.search(line)
        callee = _CALLS.search(line)
        call = _CALL.search(line, m.end())
        opcode, arg = ((call.group(1), (call.group(2) or "").lstrip("%"))
                       if call else ("", ""))
        paths[name] = op.group(1) if op else ""
        if op and comp is not None:
            inner[comp] = op.group(1)
        elif callee:
            calls[name] = callee.group(1)
        elif opcode in _MOVES and arg:
            moved[name] = arg
        if not entry:
            continue
        if opcode == "parameter":
            params[name] = etype
        elif arg in params or arg in origin:
            src = origin.get(arg, arg)
            if opcode == "convert" or (opcode == "copy"
                                       and etype != params[src]):
                casts.append(name)
            elif opcode in _MOVES:
                origin[name] = src
    for name, callee in calls.items():
        if callee in inner:
            paths[name] = inner[callee]
    for name, arg in moved.items():      # operands come first in the text
        paths[name] = paths.get(arg, "")
    for name in casts:
        if scope_of(paths[name]) == UNSCOPED:
            paths[name] = "weight_cast"
    return paths


def scoped_events(profile, programs: Sequence[Dict[str, str]]
                  ) -> Tuple[List[Event], List[Span]]:
    """Device operations, each named by its program scope (``Event.name``
    is the scope), and the program's spans on the host, from a
    ``jax.profiler.ProfileData`` (``ProfileData.from_file`` of the
    ``.xplane.pb``).

    The trace names an operation by its HLO instruction only, so its
    ``op_name`` comes from ``programs``: ``op_paths_from_hlo`` of each
    compiled program the step runs (one per burst length; their
    instruction names differ).  Each step program in the trace is read
    with the one that names the most of its operations.  Operations of
    other programs (the host's uploads between bursts) are
    ``(unscoped)``."""
    ops: List[Event] = []
    spans: List[Span] = []
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            mods = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                          for e in (lines[MODULES_LINE].events
                                    if MODULES_LINE in lines else ())
                          if e.name.startswith(STEP_MODULE))
            starts = [m[0] for m in mods]
            rows, seen = [], {}
            for e in (lines[OPS_LINE].events if OPS_LINE in lines else ()):
                i = bisect.bisect_right(starts, e.start_ns) - 1
                module = (mods[i][2] if i >= 0 and e.start_ns < mods[i][1]
                          else None)
                name = op_name(e.name)
                rows.append((module, name, float(e.start_ns),
                             float(e.duration_ns)))
                if module is not None:
                    seen.setdefault(module, set()).add(name)
            table = {m: max(programs, key=lambda p: len(names & p.keys()))
                     for m, names in seen.items()} if programs else {}
            for module, name, start, dur in rows:
                p = table[module].get(name, "") if module in table else ""
                ops.append(Event(plane.name, OPS_LINE, scope_of(p), start,
                                 dur))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in PROGRAM_SPANS:
                        args = tuple((k, str(v)) for k, v in e.stats)
                        spans.append(span(e.name, float(e.start_ns),
                                          float(e.duration_ns), args))
    return ops, spans


def span(name: str, start_ns: float, dur_ns: float,
         args: Tuple[Tuple[str, str], ...] = ()) -> Span:
    """A program span, ``sync`` named with its kind."""
    kind = dict(args).get("kind")
    if name == "sync" and kind:
        name = f"sync.{kind}"
    return Span(name, start_ns, dur_ns, args)


def device_scopes(ops: Sequence[Event]) -> Dict[str, float]:
    """Device self seconds by program scope (``ops`` from
    ``scoped_events``), mean over devices.  The keys without ``/`` and
    ``(unscoped)`` add up to the busy time; ``attention/kv_write`` and
    ``attention/core`` are parts of ``attention``, whose own key holds the
    whole."""
    devices = sorted({e.plane for e in ops})
    out: Dict[str, float] = {}
    for dev in devices:
        for name, ns in _self_times([e for e in ops
                                     if e.plane == dev]).items():
            out[name] = out.get(name, 0.0) + ns * 1e-9 / len(devices)
    parts = sum(v for k, v in out.items() if k.startswith("attention/"))
    if parts or "attention" in out:
        out["attention"] = out.get("attention", 0.0) + parts
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def idle_by_span(ops: Sequence[Event], spans: Sequence[Span],
                 window_ns: Optional[Tuple[float, float]] = None
                 ) -> Dict[str, float]:
    """Device idle seconds in ``window_ns`` (default: from the first to
    the last event of the trace), split by the innermost program span over
    each part; parts under none go to ``(none)``.  One device."""
    if window_ns is None:
        edges = ([(e.start_ns, e.start_ns + e.dur_ns) for e in ops]
                 + [(s.start_ns, s.end_ns) for s in spans])
        window_ns = (min(a for a, _ in edges), max(b for _, b in edges))
    w0, w1 = window_ns
    busy = _union([(max(e.start_ns, w0), min(e.start_ns + e.dur_ns, w1))
                   for e in ops if e.start_ns < w1
                   and e.start_ns + e.dur_ns > w0])
    idle, t = [], w0
    for s, e in busy:
        if s > t:
            idle.append((t, s))
        t = max(t, e)
    if t < w1:
        idle.append((t, w1))
    # sweep the idle parts against the spans: at each boundary the
    # innermost open span is the one that opened last
    marks = sorted({w0, w1, *(x for s in spans for x in (s.start_ns,
                                                         s.end_ns)
                              if w0 < x < w1),
                    *(x for part in idle for x in part)})
    by_start = sorted(spans, key=lambda s: (s.start_ns, -s.dur_ns))
    starts = [s.start_ns for s in by_start]
    out: Dict[str, float] = {}
    for a, b in idle:
        i = bisect.bisect_left(marks, a)
        while i + 1 < len(marks) and marks[i] < b:
            lo, hi = marks[i], marks[i + 1]
            mid = (lo + hi) / 2
            inner = NO_SPAN
            for j in range(bisect.bisect_right(starts, mid) - 1, -1, -1):
                if by_start[j].end_ns > mid:
                    inner = by_start[j].name
                    break
            out[inner] = out.get(inner, 0.0) + (hi - lo) * 1e-9
            i += 1
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def burst_steps(spans: Sequence[Span]) -> int:
    """Engine steps of the ``burst`` spans in the trace."""
    return sum(int(dict(s.args).get("steps", 0)) for s in spans
               if s.name == "burst")


# ---- readings of the program's tracer (repro.obs.Tracer) ----------------

def _in(window: Tuple[float, float], t: float) -> bool:
    return window[0] <= t < window[1]


def prompt_tokens_per_step(tracer, window: Tuple[float, float]
                           ) -> Optional[float]:
    """Prompt tokens per engine step that carried any, over the bursts
    that started in ``window`` (the driver's clock): the sum of their
    ``prefill_tokens`` over the sum of their ``prefill_steps``.  None
    where the tracer's ring dropped events or no step carried a prompt
    token."""
    if tracer.n_dropped:
        return None
    bursts = [s.args for s in tracer.spans("burst") if _in(window, s.ts)]
    steps = sum(a["prefill_steps"] for a in bursts)
    return sum(a["prefill_tokens"] for a in bursts) / steps if steps else None


def host_turnaround_ms(tracer, window: Tuple[float, float]
                       ) -> Optional[float]:
    """The host's share of each burst boundary, in milliseconds: the mean,
    over the bursts that ended in ``window``, of the burst's end (the
    host has dispatched it) minus the end of the last ``sync`` after the
    previous burst (the host had the device's results).  Boundaries with
    no sync are skipped; None where the ring dropped events or none is
    left."""
    if tracer.n_dropped:
        return None
    marks = sorted([(s.ts + s.dur, s.name) for s in tracer.spans()
                    if s.name in ("burst", "sync")])
    out, last_sync = [], None
    for t, name in marks:
        if name == "sync":
            last_sync = t
        else:
            if last_sync is not None and _in(window, t):
                out.append(t - last_sync)
            last_sync = None
    return sum(out) / len(out) * 1e3 if out else None


def attention_ms(scopes: Dict[str, float], steps: int) -> Optional[float]:
    """Device self time under the ``attention`` scope per engine step
    traced, in milliseconds."""
    if not steps or "attention" not in scopes:
        return None
    return scopes["attention"] / steps * 1e3
