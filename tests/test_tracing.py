"""The heartbeat's own names for a trace: named cycle modules, the
op-to-stage map of ``SharedDBEngine.trace_export``, the per-beat host
spans on one clock, and the admission counters (queue wait, held
writes)."""
import glob
import os
import re

import jax
import numpy as np
import pytest

from repro.core.executor import SharedDBEngine
from repro.core.lowering import STAGES, lower_plan
from repro.workloads import tpcw

SCALE_I, SCALE_C = 128, 256
PHASES = ("beat.admit", "beat.launch", "beat.wait", "beat.route")
# instructions that only name, pack or unpack values: no work of a stage
_PLUMBING = {"parameter", "constant", "tuple", "get-tuple-element",
             "bitcast"}


@pytest.fixture(scope="module")
def plan_data():
    rng = np.random.default_rng(11)
    plan = tpcw.build_tpcw_plan(SCALE_I, SCALE_C, dense_pk_index=False)
    return plan, tpcw.generate_data(rng, SCALE_I, SCALE_C)


@pytest.fixture(scope="module")
def jitted(plan_data):
    """A jitted index-less engine that ran all three cycle flavours:
    a full beat, a delta-join beat (a spine-side write only) and a delta
    beat whose joins probe in full (a write to ``item``, a join's PK)."""
    plan, data = plan_data
    eng = SharedDBEngine(plan, tpcw.DEFAULT_UPDATE_SLOTS, data, jit=True,
                         kernels="jnp")
    paths = []
    for i, upd in enumerate([
            None,
            ("customer", "update", {"key": 7, "col": "c_expiration",
                                    "val": 13001}),
            ("item", "update", {"key": 3, "col": "i_cost", "val": 5})]):
        if upd is not None:
            eng.submit_update(*upd)
        eng.submit("get_book", {0: (1 + i, 1 + i)})
        eng.run_cycle()
        paths.append((eng.last_scan_path, eng.last_join_path))
    assert paths == [("full", "full"), ("delta", "delta"),
                     ("delta", "full")]
    return eng


def _executed_instructions(text):
    """(name, opcode) of every instruction the device runs as an op of
    its own: those of the entry computation and of the bodies and
    conditions of its loops, never those inside a fusion or a
    reducer."""
    comps, cur = {}, None
    for line in text.splitlines():
        head = re.match(r"^(ENTRY )?%([\w.\-]+) \(.*\{$", line)
        if head:
            cur = "ENTRY" if head.group(1) else head.group(2)
            comps[cur] = []
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            m = re.match(r"^\s*(?:ROOT )?%([\w.\-]+) = (.*)$", line)
            if m:
                op = re.search(r"(?<![\w.\-%])([a-z][a-z0-9\-]*)\(",
                               m.group(2))
                comps[cur].append((m.group(1), op.group(1), m.group(2)))
    run, todo = set(), ["ENTRY"]
    while todo:
        c = todo.pop()
        if c in run:
            continue
        run.add(c)
        for _, op, rest in comps[c]:
            if op in ("while", "conditional", "call"):
                todo += re.findall(
                    r"(?:body|condition|to_apply|computations?)=\{?%"
                    r"([\w.\-]+)", rest)
    return [(n, op) for c in run for n, op, _ in comps[c]]


def test_cycle_modules_are_named_per_flavour(jitted):
    hlo = jitted.beat_hlo()
    assert set(hlo) == {"jit_beat_full", "jit_beat_delta",
                        "jit_beat_delta_join"}
    for name, text in hlo.items():
        assert text.startswith(f"HloModule {name},")


def test_trace_export_maps_every_stage_op(jitted, plan_data):
    plan, _ = plan_data
    lowered = lower_plan(plan, key_stats=jitted._key_stats)
    export = jitted.trace_export()
    for module, text in jitted.beat_hlo().items():
        stages = export[module]
        assert {s.split("/", 1)[0] for s in stages.values()} <= set(STAGES)
        work = [(n, op) for n, op in _executed_instructions(text)
                if op not in _PLUMBING]
        mapped = [n for n, _ in work if n in stages]
        assert len(mapped) >= 0.95 * len(work), (module, len(mapped),
                                                 len(work))
        # the shared sorts' argsort maps to its sort stage and the joins
        # each to their own join stage
        for st in lowered.sorts:
            assert any(op == "sort" and stages.get(n) ==
                       f"sort/{st.spine}.{st.col}" for n, op in work)
        for j in lowered.joins:
            assert f"join/{j.label}" in stages.values()
    full = {s.split("/", 1)[0] for s in export["jit_beat_full"].values()}
    assert full == set(STAGES) - {"fused_delta"}
    for module in ("jit_beat_delta", "jit_beat_delta_join"):
        assert "fused_delta" in {s.split("/", 1)[0]
                                 for s in export[module].values()}


def _beats(spans):
    out = {}
    for seq, name, start, end in spans:
        out.setdefault(seq, []).append((name, start, end))
    return out


def test_each_beat_records_its_spans_in_order(jitted):
    eng = jitted
    eng.submit("get_book", {0: (2, 2)})
    eng.submit_update("customer", "update", {"key": 5, "col": "c_since",
                                             "val": 3})
    eng.run_cycle()
    s = eng.last_collect_stats
    (seq, spans), = _beats(s["spans"]).items()
    by = {n: (a, b) for n, a, b in spans}
    assert sorted(by) == sorted(PHASES + ("beat.fill", "beat.h2d"))
    # the four phases follow one another; fill then h2d inside admit
    order = [by[n] for n in PHASES]
    assert all(a <= b for a, b in order)
    assert all(p[1] <= q[0] for p, q in zip(order, order[1:]))
    admit = by["beat.admit"]
    assert admit[0] <= by["beat.fill"][0] <= by["beat.fill"][1] \
        <= by["beat.h2d"][0] <= by["beat.h2d"][1] <= admit[1]
    # the phase times are the spans' lengths, stored once
    assert s["t_stage_s"] == admit[1] - admit[0]
    assert s["t_dispatch_s"] == by["beat.launch"][1] - by["beat.launch"][0]
    assert s["t_kernel_s"] == by["beat.wait"][1] - by["beat.wait"][0]
    assert s["t_collect_s"] == by["beat.route"][1] - by["beat.route"][0]
    assert s["h2d_s"] == by["beat.h2d"][1] - by["beat.h2d"][0]
    # one clock: a ticket is submitted before its beat's admission and
    # done inside its routing
    t = eng.submit("get_book", {0: (3, 3)})
    done = eng.run_until_drained()
    (seq2, spans2), = _beats(eng.last_collect_stats["spans"]).items()
    by2 = {n: (a, b) for n, a, b in spans2}
    assert seq2 == seq + 1
    assert t.submit_time <= by2["beat.admit"][0]
    assert by2["beat.route"][0] <= t.done_time <= by2["beat.route"][1]
    assert done[0].t_stage_s == eng.last_collect_stats["t_stage_s"]


def test_pipelined_beats_keep_their_own_spans(jitted):
    eng = jitted
    for i in range(3):
        eng.submit("get_book", {0: (i, i)})
        eng.dispatch()
    # the third dispatch collected the first beat for backpressure; the
    # next collect surfaces it with the second
    eng.collect()
    beats = _beats(eng.last_collect_stats["spans"])
    first = min(beats)
    assert sorted(beats) == [first, first + 1]
    for spans in beats.values():
        assert len(spans) == 6
    assert eng.last_collect_stats["admitted"] == 2
    eng.collect()
    assert list(_beats(eng.last_collect_stats["spans"])) == [first + 2]


def test_profiler_trace_holds_the_spans(jitted, tmp_path):
    from jax.profiler import ProfileData
    eng = jitted
    eng.submit("get_book", {0: (4, 4)})
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.run_cycle()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    seen = {}
    for p in ProfileData.from_file(path).planes:
        for ln in p.lines:
            for ev in ln.events:
                if ev.name.startswith("beat."):
                    seen[ev.name] = dict(ev.stats).get("beat")
    seq = eng.last_collect_stats["spans"][0][0]
    assert seen == {n: seq for n in PHASES + ("beat.fill", "beat.h2d")}


def test_queue_wait_covers_a_beat_behind_a_full_template(jitted,
                                                         plan_data):
    plan, _ = plan_data
    eng = jitted
    cap = plan.caps["get_book"]
    for i in range(cap + 1):
        eng.submit("get_book", {0: (i, i)})
    eng.run_cycle()
    first = eng.last_collect_stats
    assert first["admitted"] == cap
    assert 0 <= first["queue_wait_s_sum"] <= cap * first["queue_wait_s_max"]
    beat_s = max(sp[3] for sp in first["spans"]) \
        - min(sp[2] for sp in first["spans"])
    eng.run_cycle()
    second = eng.last_collect_stats
    assert second["admitted"] == 1
    assert second["queue_wait_s_max"] >= beat_s
    assert second["queue_wait_s_sum"] == second["queue_wait_s_max"]


def test_held_writes_count_the_overflow_of_a_table(jitted):
    eng = jitted
    slots = eng.update_slots
    for i in range(slots.n_insert + 5):
        eng.submit_update("customer", "insert",
                          {"c_id": SCALE_C + i, "c_uname": i})
    eng.run_cycle()
    assert eng.last_collect_stats["held_writes"] == 5
    assert eng.last_collect_stats["dirty"] == slots.n_insert
    eng.run_cycle()
    assert eng.last_collect_stats["held_writes"] == 0
    assert eng.last_collect_stats["dirty"] == 5
