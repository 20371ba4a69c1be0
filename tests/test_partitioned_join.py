"""Partitioned shared join: property tests vs the dense block-join oracle
(duplicate keys, empty buckets, all-invalid rows, capacity-boundary
padding), jnp/pallas kernel parity, lowering access-path selection, and a
full-engine jnp-vs-pallas parity run over index-less TPC-W."""
import jax.numpy as jnp
import numpy as np
import pytest

try:        # property tests engage when hypothesis is available; the
    # deterministic sweep below always runs
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.core.executor import SharedDBEngine
from repro.core.lowering import (PARTITIONED_MIN_CAPACITY, lower_plan,
                                 partition_layout)
from repro.core.storage import build_key_partitions
from repro.kernels import ref
from repro.kernels.partitioned_join import partitioned_join_pallas
from repro.workloads import tpcw

INT_MAX = 2147483647


def _world(seed, Tr, Tl, W, valid_frac, n_partitions, bucket_cap):
    rng = np.random.default_rng(seed)
    # unique keys, sparse + shuffled (INT_MAX excluded: reserved sentinel)
    keys_r = jnp.asarray(rng.permutation(Tr * 3)[:Tr] - 2, jnp.int32)
    valid_r = jnp.asarray(rng.random(Tr) < valid_frac)
    keys_l = jnp.asarray(rng.integers(-3, Tr * 3, Tl), jnp.int32)
    mask_l = jnp.asarray(rng.integers(0, 2**32, (Tl, W)), jnp.uint32)
    mask_r = jnp.asarray(rng.integers(0, 2**32, (Tr, W)), jnp.uint32)
    parts = build_key_partitions(keys_r, valid_r, n_partitions, bucket_cap)
    return keys_l, mask_l, keys_r, mask_r, valid_r, parts


def _check_against_oracle(seed, Tr, Tl, W, valid_frac, bucket_cap,
                          extra_parts, pallas=False):
    n_partitions = -(-Tr // bucket_cap) + extra_parts
    keys_l, mask_l, keys_r, mask_r, valid_r, parts = _world(
        seed, Tr, Tl, W, valid_frac, n_partitions, bucket_cap)
    want_rid, want_mask = ref.bitmask_join_ref(keys_l, mask_l, keys_r,
                                               mask_r, valid_r)
    got_rid, got_mask = ref.partitioned_join_ref(keys_l, mask_l, *parts,
                                                 mask_r)
    assert (np.asarray(got_rid) == np.asarray(want_rid)).all()
    assert (np.asarray(got_mask) == np.asarray(want_mask)).all()
    if pallas:
        r2, m2 = partitioned_join_pallas(keys_l, mask_l, *parts, mask_r,
                                         interpret=True)
        assert (np.asarray(r2) == np.asarray(want_rid)).all()
        assert (np.asarray(m2) == np.asarray(want_mask)).all()


@pytest.mark.parametrize("seed,Tr,Tl,W,valid_frac,bucket_cap,extra", [
    (0, 160, 120, 2, 0.8, 48, 0),    # plain
    (1, 130, 300, 1, 0.2, 7, 3),     # sparse valid rows -> empty buckets
    (2, 64, 64, 3, 0.0, 16, 1),      # all-invalid table
    (3, 257, 129, 2, 1.0, 32, 0),    # capacity-boundary padding
    (4, 1, 1, 1, 1.0, 1, 2),         # degenerate single row
    (5, 300, 260, 2, 0.9, 256, 0),   # one tile-sized bucket + remainder
])
def test_partitioned_join_matches_block_oracle_sweep(seed, Tr, Tl, W,
                                                     valid_frac,
                                                     bucket_cap, extra):
    """Deterministic edge-case sweep (runs with or without hypothesis):
    empty buckets, all-invalid rows, non-divisible capacities."""
    _check_against_oracle(seed, Tr, Tl, W, valid_frac, bucket_cap, extra,
                          pallas=True)


if HAVE_HYPOTHESIS:
    @settings(max_examples=40, deadline=None)
    @given(seed=st.integers(0, 10_000), Tr=st.integers(1, 160),
           Tl=st.integers(1, 120), W=st.integers(1, 3),
           valid_frac=st.sampled_from([0.0, 0.2, 0.8, 1.0]),
           bucket_cap=st.integers(1, 48), extra_parts=st.integers(0, 3))
    def test_partitioned_join_matches_block_oracle(seed, Tr, Tl, W,
                                                   valid_frac, bucket_cap,
                                                   extra_parts):
        """Any bucket layout whose capacity covers the table is exact."""
        _check_against_oracle(seed, Tr, Tl, W, valid_frac, bucket_cap,
                              extra_parts)

    @settings(max_examples=15, deadline=None)
    @given(seed=st.integers(0, 10_000), Tr=st.integers(1, 140),
           Tl=st.integers(1, 120), bucket_cap=st.integers(1, 48))
    def test_partitioned_join_pallas_parity(seed, Tr, Tl, bucket_cap):
        """The Pallas kernel (interpret mode) == the jnp reference probe
        on awkward non-tile-multiple shapes."""
        _check_against_oracle(seed, Tr, Tl, 2, 0.7, bucket_cap, 0,
                              pallas=True)


def test_duplicate_valid_keys_resolve_to_max_row():
    """Duplicates sort adjacently with row id ascending, so the probed
    (last) bucket holds the highest-row duplicate — the block join's
    resolution rule — even when duplicates straddle a bucket boundary."""
    keys_r = jnp.asarray([5, 7, 7, 7, 7, 9], jnp.int32)
    valid_r = jnp.ones(6, bool)
    mask_r = jnp.asarray(np.arange(1, 7)[:, None], jnp.uint32)
    keys_l = jnp.asarray([5, 7, 9, 8], jnp.int32)
    mask_l = jnp.full((4, 1), 0xFF, jnp.uint32)
    # bucket_cap=2: sorted keys [5,7 | 7,7 | 7,9] — the 7s straddle two
    # boundaries; the probe must land on the bucket holding row 4
    parts = build_key_partitions(keys_r, valid_r, 3, 2)
    rid, mask = ref.partitioned_join_ref(keys_l, mask_l, *parts, mask_r)
    assert np.asarray(rid).tolist() == [0, 4, 5, -1]
    expect = np.where(np.asarray(rid)[:, None] >= 0,
                      0xFF & np.asarray(mask_r)[np.maximum(rid, 0)], 0)
    assert (np.asarray(mask) == expect).all()
    r2, m2 = partitioned_join_pallas(keys_l, mask_l, *parts, mask_r,
                                     interpret=True)
    assert (np.asarray(r2) == np.asarray(rid)).all()
    assert (np.asarray(m2) == np.asarray(mask)).all()


def test_partition_layout_covers_capacity():
    for cap in (1, 7, 255, 256, 257, 512, 4096, 10001):
        n, b = partition_layout(cap)
        assert n * b >= cap
        assert b <= max(cap, 1)


# --------------------------------------------- lowering access-path choice
def test_lowering_selects_partitioned_join_from_capacities():
    """Index-less PK tables pick partitioned vs block by capacity; the
    dense-index configuration keeps the O(1) gather."""
    plan = tpcw.build_tpcw_plan(128, 256, dense_pk_index=False)
    low = lower_plan(plan)
    kinds = {(j.spine, j.pk_table): j.kind for j in low.joins}
    # author/orders/item capacities all exceed the partition threshold
    assert kinds[("item", "author")] == "partitioned"
    assert kinds[("order_line", "orders")] == "partitioned"
    assert kinds[("order_line", "item")] == "partitioned"
    for j in low.joins:
        if j.kind == "partitioned":
            cap = plan.catalog.schemas[j.pk_table].capacity
            assert cap >= PARTITIONED_MIN_CAPACITY
            assert j.n_partitions * j.bucket_cap >= cap
    # with the dense index, every join remains a gather
    low_ix = lower_plan(tpcw.build_tpcw_plan(128, 256))
    assert {j.kind for j in low_ix.joins} == {"gather"}


# ------------------------------------------- full-engine parity over TPC-W
QUERIES = [
    ("get_customer", {0: (7, 7)}),
    ("get_book", {0: (5, 5)}),
    ("search_subject", {0: (3, 3)}),
    ("search_author", {0: (100, 120)}),
    ("new_products", {0: (2, 2)}),
    ("best_sellers", {0: (0, INT_MAX), 1: (2, 2)}),
    ("order_lines", {0: (10, 10)}),
    ("order_display", {0: (17, 17)}),
    ("get_cart", {0: (12, 12)}),
]


@pytest.fixture(scope="module")
def indexless_world():
    rng = np.random.default_rng(5)
    plan = tpcw.build_tpcw_plan(128, 256, dense_pk_index=False)
    data = tpcw.generate_data(rng, 128, 256)
    return plan, data


def test_engine_jnp_vs_pallas_parity_partitioned_tpcw(indexless_world):
    """Acceptance: the full engine produces identical results on both
    backends when every TPC-W join runs the partitioned access path."""
    plan, data = indexless_world
    assert any(j.kind == "partitioned" for j in lower_plan(plan).joins)
    tickets = []
    for kernels in ("jnp", "pallas"):
        eng = SharedDBEngine(plan, tpcw.DEFAULT_UPDATE_SLOTS, data,
                             jit=False, kernels=kernels)
        tickets.append([eng.submit(n, p) for n, p in QUERIES])
        eng.run_cycle()
    for a, b in zip(*tickets):
        assert a.template == b.template
        if "rows" in a.result:
            assert (np.asarray(a.result["rows"])
                    == np.asarray(b.result["rows"])).all(), a.template
        else:
            assert (np.asarray(a.result["groups"])
                    == np.asarray(b.result["groups"])).all()
            np.testing.assert_allclose(np.asarray(a.result["scores"]),
                                       np.asarray(b.result["scores"]),
                                       rtol=1e-5)


def test_partitioned_engine_matches_query_at_a_time(indexless_world):
    """The partitioned path answers exactly like the baseline engine,
    including after updates force a partition rebuild."""
    from repro.core.baseline import QueryAtATimeEngine
    plan, data = indexless_world
    eng = SharedDBEngine(plan, tpcw.DEFAULT_UPDATE_SLOTS, data, jit=False,
                         kernels="jnp")
    base = QueryAtATimeEngine(plan, data, jit=False)
    upd = ("item", "update", {"key": 5, "col": "i_cost", "val": 4242})
    eng.submit_update(*upd)
    base.apply_update(*upd)
    tickets = [eng.submit(n, p) for n, p in QUERIES]
    eng.run_cycle()
    for t in tickets:
        want = base.execute(t.template, t.params).result
        if "rows" in t.result:
            a = set(int(x) for x in np.asarray(t.result["rows"]) if x >= 0)
            b = set(int(x) for x in want["rows"] if x >= 0)
            assert a == b, t.template
        else:
            np.testing.assert_allclose(
                np.sort(np.asarray(t.result["scores"])),
                np.sort(np.asarray(want["scores"])), rtol=1e-6)
