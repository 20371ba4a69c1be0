"""Reduce a JAX profiler trace to the numbers the benchmark reports.

Input: the ``.xplane.pb`` that ``jax.profiler.stop_trace`` writes.  The
traced window is the host span named ``trace_window`` that the harness
wraps around the traced part of the run; every device and host event is
clipped to it.  All times are on the profiler's common clock.

* busy: the union of the intervals in which an operation ran on a
  device (its ``XLA Ops`` line, or ``XLA Modules`` where a device plane
  has no op line), averaged over the devices that ran anything;
* device operations: time per operation (its HLO instruction name and
  result shape where the trace names ops by their HLO text);
* idle gaps: the window less the busy union on the first device, each
  gap's time split among the harness's host spans (``generate``,
  ``dispatch``, ``collect``, ``wait``) that overlap it, the rest
  ``other``.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

HOST_SPANS = ("generate", "dispatch", "collect", "wait")
WINDOW_SPAN = "trace_window"
_INSTANCE = re.compile(r"\.\d+$")
_SHAPE = re.compile(r"\b([a-z]+\d*|pred)\[([\d,]*)\]")


@dataclasses.dataclass
class OpEvent:
    name: str
    start_ns: float
    dur_ns: float           # clipped to the window
    whole: bool             # the op ran entirely inside the window


@dataclasses.dataclass
class Trace:
    window_ns: Tuple[float, float]
    window_s: float
    busy_s: float
    ops: List[OpEvent]                       # first device, in the window
    host: List[Tuple[str, float, float]]     # (span, start_ns, end_ns)
    device_ops: List[List]                   # [[name, seconds]], top 10
    idle_gaps: List[List]                    # [[host activity, seconds]]


def op_name(name: str) -> str:
    """An op event's short name.  TPU traces name an op by its HLO text,
    ``%fusion.597 = s32[1166400]{...} fusion(...), kind=...``: keep the
    instruction's name and the start of its result shape."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return _INSTANCE.sub("", name)
    return f"{head.lstrip('%')} {rest[:48]}"


def hlo_shapes(text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """Every array shape ``dtype[d0,d1,...]`` in an HLO text, in order."""
    return [(m.group(1), tuple(int(d) for d in m.group(2).split(",") if d))
            for m in _SHAPE.finditer(text)]


def union_ns(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge [start, end) intervals into disjoint, sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(s: float, e: float, w: Tuple[float, float]) -> Optional[Tuple[float, float]]:
    s, e = max(s, w[0]), min(e, w[1])
    return (s, e) if e > s else None


def gaps_by_activity(busy: List[Tuple[float, float]],
                     window: Tuple[float, float],
                     host: List[Tuple[str, float, float]]) -> Dict[str, float]:
    """Seconds of device idle time spent under each host activity."""
    gaps, t = [], window[0]
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if window[1] > t:
        gaps.append((t, window[1]))
    spans = sorted((s, e, n) for n, s, e in host if n in HOST_SPANS)
    out: Dict[str, float] = {}
    for gs, ge in gaps:
        covered = 0.0
        for s, e, n in spans:
            if s >= ge:
                break
            ov = min(e, ge) - max(s, gs)
            if ov > 0:
                out[n] = out.get(n, 0.0) + ov * 1e-9
                covered += ov
        rest = (ge - gs) - covered
        if rest > 0:
            out["other"] = out.get("other", 0.0) + rest * 1e-9
    return out


def _device_planes(data):
    return [p for p in data.planes if p.name.startswith("/device:")
            and any(len(list(ln.events)) for ln in p.lines)]


def _op_line(plane):
    lines = {ln.name: ln for ln in plane.lines}
    return lines.get("XLA Ops") or lines.get("XLA Modules")


def reduce_xspace(data) -> Trace:
    """The reduction of one loaded ``jax.profiler.ProfileData``."""
    host = []
    for p in data.planes:
        if not p.name.startswith("/host:"):
            continue
        for ln in p.lines:
            for ev in ln.events:
                if ev.name in HOST_SPANS or ev.name == WINDOW_SPAN:
                    host.append((ev.name, float(ev.start_ns),
                                 float(ev.start_ns + ev.duration_ns)))
    wins = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
    if not wins:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} host span")
    window = (min(s for s, _ in wins), max(e for _, e in wins))
    planes = _device_planes(data)
    if not planes:
        raise ValueError("trace has no device plane with events")
    busy_per_dev, first_busy, ops = [], None, []
    for i, p in enumerate(planes):
        line = _op_line(p)
        if line is None:
            continue
        iv = []
        for ev in line.events:
            c = clip(float(ev.start_ns), float(ev.start_ns + ev.duration_ns),
                     window)
            if c is None:
                continue
            iv.append(c)
            if i == 0:
                ops.append(OpEvent(ev.name, c[0], c[1] - c[0],
                                   c[1] - c[0] == ev.duration_ns))
        merged = union_ns(iv)
        busy_per_dev.append(sum(e - s for s, e in merged) * 1e-9)
        if i == 0:
            first_busy = merged
    fam: Dict[str, float] = {}
    for ev in ops:
        fam[op_name(ev.name)] = fam.get(op_name(ev.name), 0.0) \
            + ev.dur_ns * 1e-9
    idle = gaps_by_activity(first_busy or [], window, host)
    return Trace(
        window_ns=window, window_s=(window[1] - window[0]) * 1e-9,
        busy_s=sum(busy_per_dev) / max(len(busy_per_dev), 1),
        ops=ops, host=host,
        device_ops=[[n, s] for n, s in sorted(fam.items(),
                                               key=lambda kv: -kv[1])[:10]],
        idle_gaps=[[n, s] for n, s in sorted(idle.items(),
                                              key=lambda kv: -kv[1])[:10]])


def reduce_dir(trace_dir: str) -> Trace:
    """Reduce the one trace ``jax.profiler`` wrote under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"want one .xplane.pb under {trace_dir}, "
                         f"found {len(paths)}")
    return reduce_xspace(ProfileData.from_file(paths[0]))
