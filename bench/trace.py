"""Reduce a JAX profiler trace to the numbers the benchmark reports.

A trace is read into plain data first (``load``): a list of planes, each
``{"name": str, "lines": {line_name: [(event_name, start_ns, dur_ns)]}}``.
The reduction (``reduce_trace``) works on that form only, so a test can
feed it a trace written by hand.

* The window is the host span named ``bench.window`` that the harness
  opens when the trace starts and closes before it stops.
* A device plane is one named ``/device:<kind>:<n>`` other than the host
  CPU; the profiler's own ``/device:CUSTOM:...`` planes hold no device
  ops. Its ops are the events of its ``XLA Ops`` line (every line where
  it has none), named by their HLO text cut to ``OP_NAME_CHARS``.
* Busy time is the union of the op intervals inside the window; idle is
  the rest of the window.
* Executable time is read from the ``XLA Modules`` line: the events whose
  name starts with a given prefix (the bucket step's is ``jit_step``),
  counted where they start inside the window. The ops that run inside
  those calls are also totalled on their own, so that an op's time a
  step is their total over the calls, with no part of a call cut off at
  the window's edges.
* An idle gap of device 0 is named after the host span of the harness
  (``acquire``, ``report``, ...) that overlaps it most, and ``engine host``
  where none does.
* A kernel's time (``kernel_s``) is read from such totals by the HLO
  instruction's name, the text before `` = ``: a Pallas kernel's
  instruction carries the kernel's name (``%flash_mha_bwd_dq_... = ...``).
"""
from __future__ import annotations

import bisect
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]          # (name, start_ns, dur_ns)

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
UNLABELLED = "engine host"
OP_NAME_CHARS = 160


def load(path: str) -> List[dict]:
    """Planes of an ``.xplane.pb`` file as plain data."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines: Dict[str, List[Event]] = {}
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (e.name, float(e.start_ns), float(e.duration_ns))
                for e in line.events)
        planes.append({"name": plane.name, "lines": lines})
    return planes


def is_device(name: str) -> bool:
    """``/device:TPU:0`` and the like; not the host, and not the
    profiler's own planes such as ``/device:CUSTOM:Megascale Trace``."""
    parts = name.split(":")
    return (len(parts) == 3 and parts[0] == "/device"
            and parts[1] not in ("CPU", "CUSTOM") and parts[2].isdigit())


def union(intervals: Sequence[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: Sequence[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """The parts of ``[lo, hi]`` that the disjoint sorted ``busy`` leaves."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def _window(planes) -> Tuple[float, float]:
    for plane in planes:
        if is_device(plane["name"]):
            continue
        for events in plane["lines"].values():
            for name, s, d in events:
                if name == WINDOW_SPAN:
                    return s, s + d
    raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")


def _ops(plane) -> List[Event]:
    lines = plane["lines"]
    if OPS_LINE in lines:
        return lines[OPS_LINE]
    return [e for name, events in lines.items() if name != MODULES_LINE
            for e in events]


def _label(gap, spans) -> str:
    s, e = gap
    best, best_overlap = UNLABELLED, 0.0
    for name, ss, se in spans:
        overlap = min(e, se) - max(s, ss)
        if overlap > best_overlap:
            best, best_overlap = name, overlap
    return best


def _most_first(ns: Dict[str, float], n: int) -> List[Tuple[str, float]]:
    """``(name, seconds)`` averaged over ``n`` devices, most first."""
    return sorted(((k, v * 1e-9 / n) for k, v in ns.items()),
                  key=lambda x: -x[1])


def reduce_trace(planes: List[dict], step_prefix: str = "jit_step",
                 host_spans: Sequence[str] = ()) -> dict:
    """``busy_s`` and ``window_s`` (busy averaged over the device planes),
    ``step_calls`` and ``step_s`` (the step executable's calls and device
    seconds, averaged over devices), ``ops`` (op name -> device seconds
    averaged over devices, most first), ``step_ops`` (the same, of the ops
    inside those step calls, wherever they fall) and ``gaps`` (device 0's
    idle gaps, longest first, as ``(label, seconds)``)."""
    lo, hi = _window(planes)
    devices = sorted((p for p in planes if is_device(p["name"])),
                     key=lambda p: p["name"])
    if not devices:
        raise ValueError("no device plane in the trace")
    spans = [(name, s, s + d) for p in planes if not is_device(p["name"])
             for events in p["lines"].values() for name, s, d in events
             if name in host_spans]
    busy_total, step_calls, step_ns = 0.0, 0, 0.0
    op_ns: Dict[str, float] = {}
    step_op_ns: Dict[str, float] = {}
    idle = []
    for k, plane in enumerate(devices):
        ops = _ops(plane)
        busy = union(clip([(s, s + d) for _, s, d in ops], lo, hi))
        busy_total += sum(e - s for s, e in busy)
        calls = []
        for name, s, d in plane["lines"].get(MODULES_LINE, []):
            if name.startswith(step_prefix) and lo <= s < hi:
                step_calls += 1
                step_ns += d
                calls.append((s, s + d))
        calls.sort()
        starts = [s for s, _ in calls]
        for name, s, d in ops:
            name = name[:OP_NAME_CHARS]
            if lo <= s < hi:
                op_ns[name] = op_ns.get(name, 0.0) + d
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s < calls[i][1]:
                step_op_ns[name] = step_op_ns.get(name, 0.0) + d
        if k == 0:
            idle = gaps(busy, lo, hi)
    n = len(devices)
    labelled = sorted(((_label(g, spans), (g[1] - g[0]) * 1e-9)
                       for g in idle), key=lambda x: -x[1])
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_total * 1e-9 / n,
        "devices": n,
        "step_calls": step_calls / n,
        "step_s": step_ns * 1e-9 / n,
        "ops": _most_first(op_ns, n),
        "step_ops": _most_first(step_op_ns, n),
        "gaps": labelled,
    }


def instruction(op: str) -> str:
    """The HLO instruction's name in an op's name, which is its HLO text
    (``%fusion.3 = bf16[...] fusion(...)`` -> ``fusion.3``)."""
    return op.split(" = ", 1)[0].lstrip("%")


def kernel_s(ops: Sequence[Tuple[str, float]], names: Sequence[str]
             ) -> Optional[float]:
    """Device seconds of the ops (``(name, seconds)``, as ``reduce_trace``
    gives them) whose instruction name holds any of ``names``; None where
    no op does."""
    found = [s for op, s in ops if any(n in instruction(op) for n in names)]
    return sum(found) if found else None
