"""The program-named trace reduction, the stage readers and the
admission readers against hand counts."""
import types

import pytest

import program_trace
import trace_reduce
from run import load_reader

# times in ns.  Window [1000, 20000).  Device modules: jit_beat_full
# [0, 4000) (cut by the window's start), [5000, 11600) and [12000,
# 21000) (cut by the window's end; its op ends the capture).  Their ops:
# fusion.1 [500, 3500); while.3 [5000, 9000) with fusion.4 [6000, 7000)
# nested in it, fusion.7 [9000, 10000), and copy.9 [10000, 11500), which
# the export does not map; fusion.11 [12000, 21000).  Host spans:
# beat.admit [3000, 4500) with beat.h2d [4000, 4500) inside it,
# beat.wait [11000, 11800).  A second device runs one op, [2000, 6000).
TRACE = '''
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 4000000 }
    events { metadata_id: 1 offset_ps: 5000000 duration_ps: 6600000 }
    events { metadata_id: 1 offset_ps: 12000000 duration_ps: 9000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 2 offset_ps: 500000 duration_ps: 3000000 }
    events { metadata_id: 3 offset_ps: 5000000 duration_ps: 4000000 }
    events { metadata_id: 4 offset_ps: 6000000 duration_ps: 1000000 }
    events { metadata_id: 5 offset_ps: 9000000 duration_ps: 1000000 }
    events { metadata_id: 6 offset_ps: 10000000 duration_ps: 1500000 }
    events { metadata_id: 7 offset_ps: 12000000 duration_ps: 9000000 } }
  event_metadata { key: 1 value { id: 1 name: "jit_beat_full(4242)" } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.1 = s32[8]{0} fusion(s32[8]{0} %a), kind=kLoop" } }
  event_metadata { key: 3 value { id: 3 name: "%while.3 = (s32[], s32[8]{0}) while(%t), body=%b" } }
  event_metadata { key: 4 value { id: 4 name: "%fusion.4 = s32[8]{0} fusion(s32[8]{0} %c), kind=kLoop" } }
  event_metadata { key: 5 value { id: 5 name: "%fusion.7 = s32[8]{0} fusion(s32[8]{0} %d), kind=kLoop" } }
  event_metadata { key: 6 value { id: 6 name: "%copy.9 = s32[8]{0} copy(s32[8]{0} %e)" } }
  event_metadata { key: 7 value { id: 7 name: "%fusion.11 = s32[8]{0} fusion(s32[8]{0} %f), kind=kLoop" } } }
planes { id: 3 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 2000000 duration_ps: 4000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 4000000 } }
  event_metadata { key: 1 value { id: 1 name: "jit_beat_full(4242)" } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.1 = s32[8]{0} fusion(s32[8]{0} %a), kind=kLoop" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 19000000 }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 1500000 }
    events { metadata_id: 3 offset_ps: 4000000 duration_ps: 500000 }
    events { metadata_id: 4 offset_ps: 11000000 duration_ps: 800000 } }
  event_metadata { key: 1 value { id: 1 name: "trace_window" } }
  event_metadata { key: 2 value { id: 2 name: "beat.admit" } }
  event_metadata { key: 3 value { id: 3 name: "beat.h2d" } }
  event_metadata { key: 4 value { id: 4 name: "beat.wait" } } }
'''
JOIN = "join/order_line.ol_o_id>orders"
EXPORT = {"jit_beat_full": {"fusion.1": "scan/item", "while.3": JOIN,
                            "fusion.4": JOIN, "fusion.7": "apply/orders",
                            "fusion.11": "route/item"}}


def _trace():
    from jax.profiler import ProfileData
    return trace_reduce.reduce_xspace(ProfileData.from_text_proto(TRACE))


def test_trace_keeps_modules_program_spans_and_busy_by_device():
    tr = _trace()
    # the first device's modules and op-line extent, not clipped
    assert [(n.split("(")[0], s, e) for n, s, e in tr.modules] == [
        ("jit_beat_full", 0, 4000), ("jit_beat_full", 5000, 11600),
        ("jit_beat_full", 12000, 21000)]
    assert tr.capture_ns == (500, 21000)
    assert tr.program_spans == [("beat.admit", 3000, 4500),
                                ("beat.h2d", 4000, 4500),
                                ("beat.wait", 11000, 11800)]
    # busy in the window: [1000, 3500), [5000, 11500), [12000, 20000) on
    # the first device, [2000, 6000) on the second; busy_s their mean
    assert tr.busy_s_by_device == pytest.approx([17e-6, 4e-6])
    assert tr.busy_s == pytest.approx(10.5e-6)


def test_stage_split_hand_counts():
    split = program_trace.stage_split(_trace(), EXPORT)
    # one whole execution; the while's self time is 4000 less its
    # nested fusion's 1000, so the join stage holds 3000 + 1000
    assert split.beats == 1
    assert split.scope_ms == pytest.approx({JOIN: 4e-3,
                                            "apply/orders": 1e-3})
    assert split.stage_ms == pytest.approx({"join": 4e-3, "apply": 1e-3})
    # busy 3000 + 1000 + 1000 + 1500 (the unmapped copy)
    assert split.busy_ms == pytest.approx(6.5e-3)
    assert split.cover == pytest.approx(100 * 5000 / 6500)
    assert split.groups() == pytest.approx(
        {"apply_dev_ms": 1e-3, "scan_dev_ms": 0.0, "join_dev_ms": 4e-3,
         "post_dev_ms": 0.0, "stage_cover": 100 * 5000 / 6500})
    # a module the export does not know maps nothing
    assert program_trace.stage_split(_trace(), {}).cover == 0.0


def test_stage_readers_hand_counts():
    run = types.SimpleNamespace(trace=_trace(), export=EXPORT)
    want = {"apply_dev_ms.sat": 1e-3, "scan_dev_ms.sat": 0.0,
            "join_dev_ms.sat": 4e-3, "post_dev_ms.sat": 0.0,
            "stage_cover.sat": 100 * 5000 / 6500}
    for name, value in want.items():
        assert load_reader(name)(run) == pytest.approx(value), name
    # no trace, no stage map, or no whole beat in the window: nothing
    tr = _trace()
    tr.modules = tr.modules[:1]
    for view in (types.SimpleNamespace(trace=None, export=None),
                 types.SimpleNamespace(trace=_trace(), export={}),
                 types.SimpleNamespace(trace=tr, export=EXPORT)):
        for name in want:
            assert load_reader(name)(view) is None, name


def test_idle_gaps_by_innermost_program_span():
    # busy [1000, 3500) (fusion.1 clipped), [5000, 11500), [12000,
    # 20000); idle [3500, 5000): admit 500, h2d 500 (inside admit),
    # other 500; [11500, 12000): wait 300, other 200
    idle = program_trace.idle_by_program_span(_trace())
    assert idle == pytest.approx({"beat.admit": 0.5e-6, "beat.h2d": 0.5e-6,
                                  "beat.wait": 0.3e-6, "other": 0.7e-6})


def test_self_times_subtract_nested_ops_once():
    ops = [(0, 100, "while"), (10, 30, "a"), (15, 20, "a.inner"),
           (40, 60, "b"), (100, 120, "c")]
    assert dict(program_trace.self_times(ops)) == {
        "while": 60, "a": 15, "a.inner": 5, "b": 20, "c": 20}


def _run(stats):
    beats = [types.SimpleNamespace(stats=s) for s in stats]
    return types.SimpleNamespace(beats=beats)


def test_admission_readers():
    run = _run([{"admitted": 3, "h2d_s": 0.002, "queue_wait_s_sum": 0.3},
                {"admitted": 1, "h2d_s": 0.004, "queue_wait_s_sum": 0.1}])
    assert load_reader("h2d_ms.sat")(run) == pytest.approx(3.0)
    assert load_reader("queue_ms.sat")(run) == pytest.approx(100.0)
    # an engine without these counters reports nothing
    old = _run([{"admitted": 3, "t_stage_s": 0.01}])
    assert load_reader("h2d_ms.sat")(old) is None
    assert load_reader("queue_ms.sat")(old) is None
    assert load_reader("queue_ms.sat")(_run([])) is None
