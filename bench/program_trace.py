"""Reduce a JAX profiler trace with the program's own names.

``trace_reduce`` keeps the device's ops and the harness's host spans.
This module reads the rest of the ``.xplane.pb``: the first device's
``XLA Modules`` events (one per heartbeat, named ``jit_beat_full``,
``jit_beat_delta`` or ``jit_beat_delta_join`` by ``SharedDBEngine``) and
the engine's ``beat.*`` host spans.  The traced window is the harness's
``trace_window`` span, as in ``trace_reduce``.

* stage time: with the engine's op-to-stage map
  (``SharedDBEngine.trace_export()``: {module: {HLO instruction:
  "<stage>/<detail>"}}), every op of every whole ``jit_beat_*``
  execution gives its self time (its duration less the union of the ops
  nested in it, so a ``while`` and its body are not counted twice) to
  its instruction's stage; totals are averaged over those executions.
  An execution is whole when it lies inside the window and more than
  ``EDGE_NS`` inside the device's capture (its first op's start to its
  last op's end): the profiler cuts the events that straddle the
  capture's edges to the capture (to within nanoseconds of rounding),
  so such an event looks whole but is not;
* cover: the share of those executions' self time that maps to a stage;
* idle gaps by program span: the window less the first device's busy
  union, each piece of a gap given to the innermost ``beat.*`` span
  over it, the rest to ``other``.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

from trace_reduce import (WINDOW_SPAN, _device_planes, _op_line, clip,
                          union_ns)

BEAT_MODULE = "jit_beat_"
PROGRAM_SPAN = "beat."
EDGE_NS = 1000.0
#: the per-layer groups of the heartbeat's stages (``lowering.STAGES``)
STAGE_GROUPS = {"apply_dev_ms": ("apply", "partition"),
                "scan_dev_ms": ("scan", "fused_delta"),
                "join_dev_ms": ("join",),
                "post_dev_ms": ("sort", "group", "route")}


@dataclasses.dataclass
class StageSplit:
    beats: int                      # whole beat executions
    stage_ms: Dict[str, float]      # stage -> device ms per beat
    scope_ms: Dict[str, float]      # "<stage>/<detail>" -> ms per beat
    busy_ms: float                  # all self time, ms per beat
    cover: float                    # % of busy_ms that maps to a stage

    def groups(self) -> Dict[str, float]:
        """The per-layer sums of ``STAGE_GROUPS`` and ``stage_cover``."""
        out = {g: sum(self.stage_ms.get(s, 0.0) for s in stages)
               for g, stages in STAGE_GROUPS.items()}
        out["stage_cover"] = self.cover
        return out


def window_ns(data) -> Tuple[float, float]:
    wins = [(float(ev.start_ns), float(ev.start_ns + ev.duration_ns))
            for p in data.planes if p.name.startswith("/host:")
            for ln in p.lines for ev in ln.events if ev.name == WINDOW_SPAN]
    if not wins:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} host span")
    return min(s for s, _ in wins), max(e for _, e in wins)


def instruction(op_text: str) -> str:
    """The HLO instruction name of an op event (``%fusion.5 = ...``)."""
    head, sep, _ = op_text.partition(" = ")
    return head.lstrip("%") if sep else op_text


def self_times(ops: List[Tuple[float, float, str]]) -> List[Tuple[str, float]]:
    """(name, self ns) of each op ``(start, end, name)``: its duration
    less the union of the ops nested inside it."""
    out, stack = [], []     # stack: [start, end, name, nested intervals]
    ops = sorted(ops, key=lambda o: (o[0], -o[1]))

    def close(top):
        nested = sum(e - s for s, e in union_ns(top[3]))
        out.append((top[2], top[1] - top[0] - nested))
    for s, e, name in ops:
        while stack and stack[-1][1] <= s:
            close(stack.pop())
        if stack and e <= stack[-1][1]:
            stack[-1][3].append((s, e))
        stack.append([s, e, name, []])
    while stack:
        close(stack.pop())
    return out


def stage_split(data, export: Dict[str, Dict[str, str]]
                ) -> Optional[StageSplit]:
    """Per-stage device time of the heartbeats wholly inside the window,
    or None where the trace holds no such ``jit_beat_*`` execution."""
    window = window_ns(data)
    planes = _device_planes(data)
    if not planes:
        return None
    lines = {ln.name: ln for ln in planes[0].lines}
    if "XLA Modules" not in lines or _op_line(planes[0]) is None:
        return None
    ops = sorted((float(ev.start_ns), float(ev.start_ns + ev.duration_ns),
                  instruction(ev.name)) for ev in _op_line(planes[0]).events)
    if not ops:
        return None
    capture = (ops[0][0] + EDGE_NS, max(e for _, e, _ in ops) - EDGE_NS)
    execs = []
    for ev in lines["XLA Modules"].events:
        s, e = float(ev.start_ns), float(ev.start_ns + ev.duration_ns)
        module = ev.name.split("(", 1)[0]
        if module.startswith(BEAT_MODULE) and window[0] <= s \
                and e <= window[1] and capture[0] < s and e < capture[1]:
            execs.append((s, e, module))
    if not execs:
        return None
    scope_ns: Dict[str, float] = {}
    busy = mapped = 0.0
    i = 0
    for s, e, module in sorted(execs):
        while i < len(ops) and ops[i][0] < s:
            i += 1
        mine = []
        while i < len(ops) and ops[i][0] < e:
            mine.append(ops[i])
            i += 1
        stages = export.get(module, {})
        for name, ns in self_times(mine):
            busy += ns
            scope = stages.get(name)
            if scope is not None:
                mapped += ns
                scope_ns[scope] = scope_ns.get(scope, 0.0) + ns
    n = len(execs)
    scope_ms = {k: v * 1e-6 / n for k, v in scope_ns.items()}
    stage_ms: Dict[str, float] = {}
    for k, v in scope_ms.items():
        stage = k.split("/", 1)[0]
        stage_ms[stage] = stage_ms.get(stage, 0.0) + v
    return StageSplit(beats=n, stage_ms=stage_ms, scope_ms=scope_ms,
                      busy_ms=busy * 1e-6 / n,
                      cover=100.0 * mapped / busy if busy > 0 else 0.0)


def idle_by_program_span(data) -> Dict[str, float]:
    """Seconds of the first device's idle time in the window under each
    innermost ``beat.*`` host span, the rest under ``other``."""
    window = window_ns(data)
    planes = _device_planes(data)
    line = _op_line(planes[0]) if planes else None
    busy = []
    for ev in (line.events if line is not None else ()):
        c = clip(float(ev.start_ns), float(ev.start_ns + ev.duration_ns),
                 window)
        if c is not None:
            busy.append(c)
    busy = union_ns(busy)
    spans = sorted((float(ev.start_ns), float(ev.start_ns + ev.duration_ns),
                    ev.name)
                   for p in data.planes if p.name.startswith("/host:")
                   for ln in p.lines for ev in ln.events
                   if ev.name.startswith(PROGRAM_SPAN))
    gaps, t = [], window[0]
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if window[1] > t:
        gaps.append((t, window[1]))
    out: Dict[str, float] = {}
    for gs, ge in gaps:
        over = [sp for sp in spans if sp[0] < ge and sp[1] > gs]
        cuts = sorted({gs, ge} | {x for sp in over for x in sp[:2]
                                  if gs < x < ge})
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            inner = [sp for sp in over if sp[0] <= mid < sp[1]]
            # spans of one thread nest: the innermost starts last
            name = max(inner)[2] if inner else "other"
            out[name] = out.get(name, 0.0) + (b - a) * 1e-9
    return out
