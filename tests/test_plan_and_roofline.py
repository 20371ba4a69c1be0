"""Plan compiler semantics (operator merging, Fig. 2/3) and roofline
machinery (collective parsing, term derivation)."""
import numpy as np
import pytest

from repro.core.plan import compile_plan
from repro.roofline.analysis import (model_flops, parse_collectives, peaks,
                                     roofline_terms)
from repro.workloads import tpcw


def test_templates_sharing_join_merge_to_one_node():
    plan = tpcw.build_tpcw_plan(400, 1200)
    # get_book and search_author both join item->author: ONE shared node
    ia = [j for j in plan.joins
          if j.spine == "item" and j.pk_table == "author"]
    assert len(ia) == 1
    assert set(ia[0].subscribers) >= {"get_book", "search_author"}
    # search_subject / search_title share the item.i_title sort node
    ts = [s for s in plan.sorts if s.spine == "item" and s.col == "i_title"]
    assert len(ts) == 1
    assert set(ts[0].subscribers) >= {"search_subject", "search_title",
                                      "search_author"}
    # one scan node per base table, regardless of template count
    assert len(plan.scans) <= len(plan.catalog.schemas)


def test_slot_ranges_disjoint_and_within_capacity():
    plan = tpcw.build_tpcw_plan(400, 1200)
    seen = set()
    for name, cap in plan.caps.items():
        o = plan.offsets[name]
        rng = set(range(o, o + cap))
        assert not (rng & seen)
        seen |= rng
    assert max(seen) < plan.qcap
    assert plan.qcap % 32 == 0


def test_sub_mask_and_word_range_consistent():
    plan = tpcw.build_tpcw_plan(400, 1200)
    for node in plan.sorts + plan.groups:
        names = node.subscribers
        sub = plan.sub_mask(names)
        wlo, whi = plan.word_range(names)
        # all set bits fall inside the word window
        assert all(sub[w] == 0 for w in range(len(sub))
                   if not wlo <= w < whi)


# ---------------------------------------------------------------- roofline
HLO_SAMPLE = """
  %all-gather.1 = f32[2048,352]{1,0} all-gather(%x), channel_id=1, replica_groups=[16,16]<=[256], dimensions={0}
  %all-reduce.7 = bf16[128,64]{1,0} all-reduce(%y), channel_id=2, replica_groups=[32,8]<=[256], to_apply=%add
  %reduce-scatter.2 = f32[64,64]{1,0} reduce-scatter(%z), channel_id=3, replica_groups=[16,16]<=[256], dimensions={0}
  %all-to-all.3 = f32[16,16]{1,0} all-to-all(%w), channel_id=4, replica_groups=[1,256]<=[256]
  %collective-permute.9 = u32[8]{0} collective-permute(%v), channel_id=5
  %fusion.1 = f32[10]{0} fusion(%all-gather.1), kind=kLoop
"""


def test_parse_collectives_kinds_and_sizes():
    out = parse_collectives(HLO_SAMPLE, default_group=256)
    assert out["counts"] == {"all-gather": 1, "all-reduce": 1,
                             "reduce-scatter": 1, "all-to-all": 1,
                             "collective-permute": 1}
    ag = 2048 * 352 * 4
    assert out["bytes_by_kind"]["all-gather"] == ag
    # ring traffic: ag output * (gs-1)/gs with gs=16
    np.testing.assert_allclose(out["link_traffic_by_kind"]["all-gather"],
                               ag * 15 / 16)
    ar = 128 * 64 * 2
    np.testing.assert_allclose(out["link_traffic_by_kind"]["all-reduce"],
                               2 * ar * 7 / 8)
    rs = 64 * 64 * 4
    np.testing.assert_allclose(
        out["link_traffic_by_kind"]["reduce-scatter"], rs * 15)
    assert out["link_traffic_by_kind"]["collective-permute"] == 8 * 4


def test_parse_collectives_skips_async_done_and_fusion_refs():
    txt = """
  %all-gather-start.1 = (f32[8]{0}, f32[128]{0}) all-gather-start(%x), replica_groups=[16,16]<=[256]
  %all-gather-done.1 = f32[128]{0} all-gather-done(%all-gather-start.1)
"""
    out = parse_collectives(txt)
    assert out["counts"] == {"all-gather": 1}
    assert out["bytes_by_kind"]["all-gather"] == 128 * 4  # result, not operand


def test_roofline_terms_dominance():
    t = roofline_terms(flops=1e15, bytes_accessed=1e12,
                       collective_bytes=1e10, n_chips=256)
    assert t["dominant"] == "compute"
    assert t["roofline_fraction"] == 1.0
    t2 = roofline_terms(flops=1e12, bytes_accessed=1e15,
                        collective_bytes=0, n_chips=256)
    assert t2["dominant"] == "memory"
    assert 0 < t2["roofline_fraction"] < 0.01


def test_peaks_keyed_by_device_kind_and_unknown_kind_raises():
    from repro.core.sla import HwModel
    v5e = peaks("TPU v5 lite")
    assert (v5e["peak_flops"], v5e["hbm_bw"]) == (197e12, 819e9)
    hw = HwModel.for_device()
    assert (hw.flops_per_s, hw.bytes_per_s) == (197e12, 819e9)
    with pytest.raises(KeyError, match="no published peaks"):
        peaks("cpu")
    with pytest.raises(KeyError):
        roofline_terms(1e12, 1e12, 0, 1, device_kind="TPU v0")
    with pytest.raises(KeyError):
        HwModel.for_device("cpu")


def test_model_flops_moe_counts_active_only():
    from repro.configs import get_config, SHAPES
    mix = get_config("mixtral-8x22b")
    dense = get_config("qwen2-72b")
    f_mix = model_flops(mix, SHAPES["train_4k"])
    # active ~39B of 141B params
    assert f_mix < 6 * mix.param_count() * 4096 * 256 * 0.45
    f_dense = model_flops(dense, SHAPES["train_4k"])
    assert f_dense == pytest.approx(
        6 * (dense.active_param_count()
             - dense.vocab_padded() * dense.d_model) * 4096 * 256)


def test_workload_generator_covers_all_interactions():
    rng = np.random.default_rng(0)
    gen = tpcw.WorkloadGenerator(rng, 400, 1200)
    for kind in tpcw.MIXES["shopping"]:
        it = gen.interaction(kind)
        assert it.kind == kind
        assert it.queries or it.updates
        for name, params in it.queries:
            assert name in {t for t in
                            tpcw.build_tpcw_plan(400, 1200).templates}


def test_mix_probabilities_sum_to_100():
    for mix, probs in tpcw.MIXES.items():
        assert abs(sum(probs.values()) - 100.0) < 0.6, mix
