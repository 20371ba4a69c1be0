"""Reduce a JAX profiler trace with the program's own names.

Reads what ``trace_reduce`` keeps of the ``.xplane.pb`` (a ``Trace``):
the first device's ops in the traced window, its ``XLA Modules`` events
(one per heartbeat, named ``jit_beat_full``, ``jit_beat_delta`` or
``jit_beat_delta_join`` by ``SharedDBEngine``) and the engine's
``beat.*`` host spans.

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

from trace_reduce import idle_ns, union_ns

BEAT_MODULE = "jit_beat_"
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


def stage_split(trace, export: Dict[str, Dict[str, str]]
                ) -> Optional[StageSplit]:
    """Per-stage device time of the heartbeats wholly inside the window
    of ``trace`` (a ``trace_reduce.Trace``), or None where it holds no
    such ``jit_beat_*`` execution."""
    if trace.capture_ns is None:
        return None
    window = trace.window_ns
    capture = (trace.capture_ns[0] + EDGE_NS, trace.capture_ns[1] - EDGE_NS)
    execs = []
    for name, s, e in trace.modules:
        module = name.split("(", 1)[0]
        if module.startswith(BEAT_MODULE) and window[0] <= s \
                and e <= window[1] and capture[0] < s and e < capture[1]:
            execs.append((s, e, module))
    if not execs:
        return None
    ops = sorted((ev.start_ns, ev.start_ns + ev.dur_ns, instruction(ev.name))
                 for ev in trace.ops)
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


def idle_by_program_span(trace) -> Dict[str, float]:
    """Seconds of the first device's idle time in the window of ``trace``
    under each innermost ``beat.*`` host span, the rest under ``other``."""
    busy = union_ns([(ev.start_ns, ev.start_ns + ev.dur_ns)
                     for ev in trace.ops])
    spans = sorted((s, e, name) for name, s, e in trace.program_spans)
    out: Dict[str, float] = {}
    for gs, ge in idle_ns(busy, trace.window_ns):
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


def stage_group(run, group: str) -> Optional[float]:
    """One value of ``StageSplit.groups()`` for a run's view: its trace
    split by the engine's op-to-stage map (``run.export``), or None where
    the run was not traced or its trace holds no whole heartbeat."""
    if run.trace is None or not run.export:
        return None
    split = stage_split(run.trace, run.export)
    return None if split is None else split.groups()[group]
