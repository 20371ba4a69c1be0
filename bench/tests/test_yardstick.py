"""The benchmark's arithmetic against hand counts: trace reduction,
percentiles and rates, roofline bytes, the reference's comparisons, and
that data and traffic follow the seed."""
import types

import numpy as np
import pytest

import trace_reduce
from reference import differs
from run import load_reader
from tpcw_gen import InteractionGenerator, generate_data, think_time

# a recorded-format trace small enough to count by hand (times in ns):
# device ops fusion.1 [1000, 3000), fusion.7 [2000, 4000),
# custom-call.3 [9000, 12000) (clipped to the window's end at 10000);
# host spans dispatch [0, 500), wait [5000, 8000), collect [8500, 9500)
TRACE = '''
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 8000000 duration_ps: 3000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.7" } }
  event_metadata { key: 3 value { id: 3 name: "custom-call.3" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 500000 }
    events { metadata_id: 3 offset_ps: 5000000 duration_ps: 3000000 }
    events { metadata_id: 4 offset_ps: 8500000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "trace_window" } }
  event_metadata { key: 2 value { id: 2 name: "dispatch" } }
  event_metadata { key: 3 value { id: 3 name: "wait" } }
  event_metadata { key: 4 value { id: 4 name: "collect" } } }
'''


def test_trace_reduction_hand_counts():
    from jax.profiler import ProfileData
    tr = trace_reduce.reduce_xspace(ProfileData.from_text_proto(TRACE))
    assert tr.window_s == pytest.approx(10e-6)
    # busy union [1000, 4000) + [9000, 10000)
    assert tr.busy_s == pytest.approx(4e-6)
    assert dict((n, s) for n, s in tr.device_ops) == pytest.approx(
        {"fusion": 4e-6, "custom-call": 1e-6})
    assert trace_reduce.op_name(
        "%while.33 = (s32[]{:T(128)}, s32[1166400]) while(%t)") == \
        "while.33 (s32[]{:T(128)}, s32[1166400]) while(%t)"
    # idle [0, 1000): dispatch 500, other 500;
    # idle [4000, 9000): wait 3000, collect 500, other 1500
    assert dict((n, s) for n, s in tr.idle_gaps) == pytest.approx(
        {"wait": 3e-6, "other": 2e-6, "dispatch": 0.5e-6,
         "collect": 0.5e-6})
    assert [n for n, _ in tr.idle_gaps][:2] == ["wait", "other"]


def test_union_merges_overlaps():
    assert trace_reduce.union_ns([(5, 7), (0, 2), (1, 3), (7, 8)]) == \
        [(0, 3), (5, 8)]


def _run(inters, beats=(), seconds=10.0, trace=None, config=None):
    return types.SimpleNamespace(
        seconds=seconds, setup_s=12.5, t_open=0.0, t_close=seconds,
        inters=list(inters), beats=list(beats), trace=trace,
        config=config or {}, traffic={}, device_kind="TPU v5 lite")


def test_latency_percentiles_and_wips():
    # 100 interactions with latencies 1..100 ms, all done in the window
    # but the last ten, which complete after the close
    inters = [types.SimpleNamespace(due=0.0, done=(i + 1) * 1e-3)
              for i in range(100)]
    for r in inters[90:]:
        r.due, r.done = 9.99, 9.99 + r.done
    run = _run(inters, seconds=10.0)
    assert load_reader("p50_ms")(run) == pytest.approx(50.5)
    assert load_reader("p95_ms")(run) == pytest.approx(95.05)
    assert load_reader("wips")(run) == pytest.approx(90 / 10.0)
    assert load_reader("setup_s")(run) == 12.5


def _beat(admitted, path, t_stage, t_collect, answered):
    return types.SimpleNamespace(
        stats={"admitted": admitted, "scan_path": path,
               "t_stage_s": t_stage, "t_collect_s": t_collect},
        answered=answered)


def test_beat_counters():
    cfg = {"templates": {"a": {"cap": 2}, "b": {"cap": 6}}}
    beats = [_beat(8, "full", 0.002, 0.001, {"a": 2, "b": 6}),
             _beat(2, "delta", 0.004, 0.003, {"a": 1, "b": 1}),
             _beat(2, "delta", 0.006, 0.002, {"b": 2}),
             _beat(4, "full", 0.004, 0.002, {"a": 2, "b": 2})]
    run = _run([], beats, seconds=2.0, config=cfg)
    assert load_reader("slot_fill.sat")(run) == pytest.approx(
        100 * 16 / (8 * 4))
    assert load_reader("binding_beats.sat")(run) == pytest.approx(50.0)
    assert load_reader("admit_ms.sat")(run) == pytest.approx(4.0)
    assert load_reader("route_ms.sat")(run) == pytest.approx(2.0)
    assert load_reader("beat_ms.sat")(run) == pytest.approx(500.0)
    assert load_reader("idle_share.sat")(run) is None
    tr = types.SimpleNamespace(busy_s=0.25, window_s=1.0)
    assert load_reader("idle_share.sat")(_run([], trace=tr)) == \
        pytest.approx(75.0)


def test_group_topk_accepts_any_tie_and_nothing_else():
    sums = np.array([5.0, 9.0, 5.0, 1.0, 0.0])
    counts = np.array([1, 2, 1, 1, 0])
    want = {"sums": sums, "counts": counts, "top": 2}

    def got(groups):
        g = np.array(groups)
        return {"groups": g, "scores": sums[g].astype(np.float32),
                "counts": counts[g].astype(np.float32)}
    assert not differs(got([1, 0]), want)
    assert not differs(got([1, 2]), want)          # the other tied group
    assert differs(got([1, 3]), want)              # not a top-2 sum
    assert differs(got([1, 1]), want)              # a group twice
    bad = got([1, 0])
    bad["counts"][0] = 3.0
    assert differs(bad, want)                      # a wrong count
    assert differs(None, want)


def test_rows_compare_exactly():
    want = {"rows": np.array([3, 1, -1, -1])}
    assert not differs({"rows": np.array([3, 1, -1, -1], np.int32)}, want)
    assert differs({"rows": np.array([1, 3, -1, -1])}, want)
    assert differs({"rows": np.array([3, -1, -1, -1])}, want)


def test_data_and_traffic_follow_the_seed():
    seed = 2 ** 31 + 12345
    a = generate_data(np.random.default_rng(seed), 512, 2880)
    b = generate_data(np.random.default_rng(seed), 512, 2880)
    c = generate_data(np.random.default_rng(seed + 1), 512, 2880)
    for t in a:
        for col in a[t]:
            np.testing.assert_array_equal(a[t][col], b[t][col])
    assert any(not np.array_equal(a[t][col], c[t][col])
               for t in a for col in a[t])
    mix = {"home": 50.0, "shopping_cart": 30.0, "buy_confirm": 20.0}

    def stream(s, n=50):
        g = InteractionGenerator(np.random.default_rng(s), mix, 512, 2880)
        return [g.next() for _ in range(n)]
    assert stream(seed) == stream(seed)
    assert stream(seed) != stream(seed + 1)
    # every seed deals the same kinds, in another order
    kinds = [sorted(i.kind for i in stream(s, 10000)) for s in (1, 2)]
    assert kinds[0] == kinds[1]
    assert kinds[0].count("home") == 5000
    think = {"dist": "neg_exp", "mean_s": 7.0, "max_s": 70.0}
    t1 = [think_time(np.random.default_rng(seed), think) for _ in range(3)]
    t2 = [think_time(np.random.default_rng(seed), think) for _ in range(3)]
    assert t1 == t2
    r = np.random.default_rng(3)
    draws = [think_time(r, think) for _ in range(20000)]
    assert max(draws) <= 70.0
    assert np.mean(draws) == pytest.approx(7.0, rel=0.05)


def test_roofline_bytes_hand_counts():
    import roofline
    # 2 columns x 1024 rows, 64 query slots: cols 8 KiB, valid 4 KiB,
    # bounds 2 x 2 x 64 x 4 = 1 KiB, words 1024 x 2 x 4 = 8 KiB
    assert roofline.clockscan_bytes(2, 1024, 64) == \
        8192 + 4096 + 1024 + 8192
    # 819 MB moved in 2 ms is half the v5e's 819 GB/s
    assert roofline.roofline_share(819e6, 2.0e-3, "TPU v5 lite") == \
        pytest.approx(50.0)
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")


def test_clockscan_roofline_reads_launch_shapes():
    text = ('%custom-call.5 = s32[13,12288]{1,0:T(8,128)} custom-call('
            's32[2,12288]{1,0:T(2,128)} %pad.1, s32[2,416,1]{2,1,0} %a, '
            's32[2,416,1]{2,1,0} %b, s32[1,12288]{1,0} %c), '
            'custom_call_target="tpu_custom_call", backend_config="x"')
    reader = load_reader("clockscan_roofline.sat")
    ev = trace_reduce.OpEvent(text, 0.0, 1e6, True)   # 1 ms
    other = trace_reduce.OpEvent(
        '%fusion.3 = s32[12288]{0} fusion(s32[12288]{0} %x), kind=kLoop',
        0.0, 5e6, True)
    cut = trace_reduce.OpEvent(text, 0.0, 2e5, False)
    tr = types.SimpleNamespace(ops=[ev, other, cut])
    share = reader(_run([], trace=tr))
    n = 4 * (2 * 12288 + 12288 + 2 * 2 * 416 + 12288 * 13)
    assert share == pytest.approx(100 * n / 819e9 / 1e-3)
    assert reader(_run([], trace=types.SimpleNamespace(ops=[other]))) is None


def test_ticket_sample_follows_the_seed_and_caps_each_stratum():
    from run import TicketSample

    def picks(seed):
        s = TicketSample(np.random.default_rng(seed), k=3)
        n = 0
        for b in range(4):
            stats = {"scan_path": "full" if b % 2 else "delta",
                     "join_path": None}
            routed = {}
            for name in ("a", "b"):
                routed[name] = [types.SimpleNamespace(id=n + i)
                                for i in range(5 if name == "a" else 1)]
                n += len(routed[name])
            s(b, stats, routed)
        return {b: [t.id for t in ts] for b, ts in s.by_beat().items()}
    one = picks(2 ** 31 + 7)
    assert one == picks(2 ** 31 + 7)
    assert any(picks(s) != one for s in range(3))
    ids = [i for ts in one.values() for i in ts]
    # per flavour: 10 tickets of "a" sampled down to 3, both of "b" kept
    assert len(ids) == len(set(ids)) == 2 * 3 + 2 * 2
