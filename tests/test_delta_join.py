"""Delta-aware shared joins: carried rid arrays across heartbeats.

Covers the PR-4 tentpole end to end — kernel parity of the dirty-row
probe (jnp oracle vs Pallas, padded tails), the conditional partition
refresh in storage, and the engine-level path machinery: steady-state
heartbeats re-probe ONLY dirty spine rows (the full partitioned probe is
never invoked), PK-side writes / dirty overflow / the first heartbeat
fall back to the full probe and reseed the carry, and the carry-layout
assertion refuses a carry from a different admission layout.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import backends
from repro.core.baseline import QueryAtATimeEngine
from repro.core.executor import SharedDBEngine
from repro.core.lowering import lower_plan
from repro.core.storage import (TableSchema, UpdateSlots, apply_updates,
                                build_key_partitions, bulk_load,
                                empty_update_batch,
                                refresh_key_partitions)
from repro.kernels import ref
from repro.kernels.fused_delta import delta_join_pallas
from repro.workloads import tpcw

INT_MAX = tpcw.INT_MAX


# ---------------------------------------------------- kernel-level parity
@pytest.mark.parametrize("seed,Tr,Tl,n_parts,bucket_cap,D", [
    (0, 160, 120, 4, 48, 9),      # plain
    (1, 5, 7, 2, 3, 11),          # D > Tl: duplicate dirty rows
    (2, 257, 300, 9, 32, 33),     # capacity-boundary padding
    (3, 1, 1, 1, 1, 1),           # degenerate single row
    (4, 130, 260, 23, 7, 16),     # sparse valid rows -> empty buckets
])
def test_delta_join_kernel_parity_padded_tails(seed, Tr, Tl, n_parts,
                                               bucket_cap, D):
    rng = np.random.default_rng(seed)
    keys_r = jnp.asarray(rng.permutation(Tr * 3)[:Tr] - 2, jnp.int32)
    valid_r = jnp.asarray(rng.random(Tr) > 0.3)
    keys_l = jnp.asarray(rng.integers(-3, Tr * 3, Tl), jnp.int32)
    parts = build_key_partitions(keys_r, valid_r, n_parts, bucket_cap)
    # pad sentinels both below and above range: callers drop them
    rows = jnp.asarray(rng.choice(
        np.concatenate([np.arange(Tl), [-1, Tl, Tl + 5, Tl]]), D),
        jnp.int32)
    want = ref.delta_join_ref(keys_l, rows, *parts)
    got = delta_join_pallas(keys_l, rows, *parts, interpret=True)
    assert (np.asarray(got) == np.asarray(want)).all()
    # fresh rids agree with the FULL partitioned probe at those rows
    W = 2
    mask_l = jnp.asarray(rng.integers(0, 2**32, (Tl, W)), jnp.uint32)
    mask_r = jnp.asarray(rng.integers(0, 2**32, (Tr, W)), jnp.uint32)
    full_rid, _ = ref.partitioned_join_ref(keys_l, mask_l, *parts, mask_r)
    safe = np.clip(np.asarray(rows), 0, Tl - 1)
    assert (np.asarray(want) == np.asarray(full_rid)[safe]).all()


# --------------------------------------------- conditional partition refresh
def test_refresh_key_partitions_skips_clean_rebuilds_dirty():
    schema = TableSchema("t", ("k", "v"), 32, pk="k", dirty_cap=8)
    t = bulk_load(schema, {"k": np.arange(16) * 3, "v": np.arange(16)})
    parts0 = build_key_partitions(t["k"], t["_valid"], 4, 8)
    # clean batch: carried partitions pass through, no rebuild
    t1 = apply_updates(schema, t, empty_update_batch(schema,
                                                     UpdateSlots(2, 2, 2)))
    parts1, rebuilt1 = refresh_key_partitions(t1, "k", 4, 8, parts0)
    assert not bool(rebuilt1)
    for a, b in zip(parts1, parts0):
        assert (np.asarray(a) == np.asarray(b)).all()
    # dirty batch: rebuild fires and reflects the new snapshot
    b2 = empty_update_batch(schema, UpdateSlots(2, 2, 2))
    b2["del_key"] = b2["del_key"].at[0].set(9)       # delete key 9 (row 3)
    b2["del_mask"] = b2["del_mask"].at[0].set(True)
    t2 = apply_updates(schema, t1, b2)
    parts2, rebuilt2 = refresh_key_partitions(t2, "k", 4, 8, parts1)
    assert bool(rebuilt2)
    want = build_key_partitions(t2["k"], t2["_valid"], 4, 8)
    for a, b in zip(parts2, want):
        assert (np.asarray(a) == np.asarray(b)).all()
    assert 3 not in np.asarray(parts2[1]).ravel().tolist()


# ------------------------------------------------------ engine-level paths
SCALE_I, SCALE_C = 128, 256


@pytest.fixture(scope="module")
def indexless_world():
    rng = np.random.default_rng(5)
    plan = tpcw.build_tpcw_plan(SCALE_I, SCALE_C, dense_pk_index=False)
    data = tpcw.generate_data(rng, SCALE_I, SCALE_C)
    return plan, data


def _probe_recording_backend(full_probes, delta_probes):
    """The jnp backend with every partitioned-probe invocation recorded
    (trace-time: pair with jit=False engines)."""
    base = backends.get_backend("jnp")

    def join_partitioned(*args):
        full_probes.append(args[0].shape[0])
        return base.join_partitioned(*args)

    def join_delta(*args):
        delta_probes.append(args[1].shape[0])
        return base.join_delta(*args)

    backends.register_backend(backends.OperatorBackend(
        name="probe-recording-jnp", scan=base.scan,
        join_block=base.join_block, join_partitioned=join_partitioned,
        groupby=base.groupby, scan_delta=base.scan_delta,
        join_delta=join_delta))
    return "probe-recording-jnp"


def test_steady_state_runs_delta_join_without_full_probe(indexless_world):
    """Acceptance: steady-state heartbeats (spine-side trickle, PK sides
    untouched) merge carried rids — the O(Tl x B) full probe is never
    invoked after the seeding cycle, only O(D x B) dirty probes — and
    stay ticket-for-ticket equal to the query-at-a-time oracle."""
    plan, data = indexless_world
    full_probes, delta_probes = [], []
    name = _probe_recording_backend(full_probes, delta_probes)
    eng = SharedDBEngine(plan, tpcw.DEFAULT_UPDATE_SLOTS, data, jit=False,
                         kernels=name)
    base = QueryAtATimeEngine(plan, data, jit=False)
    eng.submit("get_book", {0: (1, 1)})
    eng.run_cycle()                                   # seeds both carries
    assert eng.last_scan_path == "full"
    assert eng.last_join_path == "full"
    assert full_probes and not delta_probes
    assert all(eng.last_parts_rebuilt.values())
    full_probes.clear()

    for i in range(4):
        # customer is no join's PK table: spine-side only
        upd = ("customer", "update", {"key": 10 + i,
                                      "col": "c_expiration",
                                      "val": 13000 + i})
        eng.submit_update(*upd)
        base.apply_update(*upd)
        t = eng.submit("get_book", {0: (10 + i, 10 + i)})
        eng.run_cycle()
        assert eng.last_scan_path == "delta"
        assert eng.last_join_path == "delta"
        assert eng.last_delta_overflow == 0
        assert not any(eng.last_parts_rebuilt.values())
        want = base.execute(t.template, t.params).result
        assert (np.asarray(t.result["rows"])
                == np.asarray(want["rows"])).all()
    assert eng.delta_join_cycles == 4
    assert not full_probes                            # dirty probes only
    assert delta_probes


def test_pk_side_write_falls_back_to_full_probe_and_reseeds(
        indexless_world):
    """An item write is a PK-side write for the order_line->item and
    cart->item joins: that heartbeat must run full probes (partitions
    rebuild), then the NEXT clean heartbeat is back on the delta path
    with rids reseeded from the full probe."""
    plan, data = indexless_world
    eng = SharedDBEngine(plan, tpcw.DEFAULT_UPDATE_SLOTS, data, jit=False)
    base = QueryAtATimeEngine(plan, data, jit=False)
    eng.submit("order_lines", {0: (10, 10)})
    eng.run_cycle()                                   # seed
    eng.submit("order_lines", {0: (10, 10)})
    eng.run_cycle()                                   # steady: delta joins
    assert eng.last_join_path == "delta"
    # PK-side write: move item 50's cost (item is order_lines' join PK)
    upd = ("item", "update", {"key": 50, "col": "i_cost", "val": 7777})
    eng.submit_update(*upd)
    base.apply_update(*upd)
    t = eng.submit("order_lines", {0: (10, 10)})
    eng.run_cycle()
    assert eng.last_scan_path == "delta"              # scans still delta
    assert eng.last_join_path == "full"               # joins fell back
    assert eng.last_parts_rebuilt["item"]
    assert not eng.last_parts_rebuilt["orders"]
    want = base.execute("order_lines", {0: (10, 10)}).result
    assert set(int(x) for x in np.asarray(t.result["rows"]) if x >= 0) \
        == set(int(x) for x in want["rows"] if x >= 0)
    # clean beat: carried rids were reseeded by the full probe
    t2 = eng.submit("order_lines", {0: (10, 10)})
    eng.run_cycle()
    assert eng.last_join_path == "delta"
    want = base.execute("order_lines", {0: (10, 10)}).result
    assert set(int(x) for x in np.asarray(t2.result["rows"]) if x >= 0) \
        == set(int(x) for x in want["rows"] if x >= 0)


def test_admission_change_rides_carried_rids_exactly(indexless_world):
    """Rids are admission-invariant: a NEW template admitted on a
    delta-join heartbeat (no dirty rows at all) must be answered
    entirely from carried rids — the masks change, the rids don't."""
    plan, data = indexless_world
    eng = SharedDBEngine(plan, tpcw.DEFAULT_UPDATE_SLOTS, data, jit=False)
    base = QueryAtATimeEngine(plan, data, jit=False)
    eng.submit("get_cart", {0: (3, 3)})
    eng.run_cycle()                                   # seed
    t = eng.submit("get_cart", {0: (12, 12)})         # different params
    eng.run_cycle()
    assert eng.last_join_path == "delta"
    want = base.execute("get_cart", {0: (12, 12)}).result
    assert set(int(x) for x in np.asarray(t.result["rows"]) if x >= 0) \
        == set(int(x) for x in want["rows"] if x >= 0)


def _overflow_world():
    from repro.core.plan import Join, Pred, QueryTemplate, compile_plan
    from repro.core.storage import Catalog
    cat = Catalog([
        TableSchema("fact", ("f_id", "f_dim", "f_v"), 640, pk="f_id",
                    dirty_cap=2),
        TableSchema("dim", ("d_id", "d_v"), 640, pk="d_id", dirty_cap=2),
    ])
    tpl = QueryTemplate("by_v", "fact", preds=(Pred("fact", "f_v"),),
                        joins=(Join("f_dim", "dim"),), limit=64)
    plan = compile_plan(cat, [tpl], {"by_v": 32}, max_results=64)
    data = {
        "fact": {"f_id": np.arange(320), "f_dim": np.arange(320) % 64,
                 "f_v": np.arange(320) % 8},
        "dim": {"d_id": np.arange(64), "d_v": np.arange(64)},
    }
    return plan, SharedDBEngine(plan, UpdateSlots(4, 4, 4), data,
                                jit=False, kernels="jnp")


def test_dirty_overflow_forces_full_scan_and_join():
    """A batch overflowing a dirty set cannot trust EITHER carry half:
    the heartbeat runs the full rescan (which reseeds scan words, parts
    and rids) and the next clean beat is delta again."""
    plan, eng = _overflow_world()
    assert any(j.kind == "partitioned"
               for j in lower_plan(plan).joins)
    t0 = eng.submit("by_v", {0: (5, 5)})
    eng.run_cycle()
    assert eng.last_join_path == "full"               # first heartbeat
    eng.submit("by_v", {0: (5, 5)})
    eng.run_cycle()
    assert eng.last_join_path == "delta"
    # 3 updates overflow fact.dirty_cap=2 -> full everything
    for key in (1, 2, 9):
        eng.submit_update("fact", "update", {"key": key, "col": "f_v",
                                             "val": 5})
    t1 = eng.submit("by_v", {0: (5, 5)})
    eng.run_cycle()
    assert eng.last_scan_path == "full"
    assert eng.last_join_path == "full"
    rows1 = set(int(x) for x in np.asarray(t1.result["rows"]) if x >= 0)
    assert {1, 2, 9} <= rows1
    # reseeded: clean beat back to delta, same answer as a fresh engine
    t2 = eng.submit("by_v", {0: (5, 5)})
    eng.run_cycle()
    assert eng.last_join_path == "delta"
    rows2 = set(int(x) for x in np.asarray(t2.result["rows"]) if x >= 0)
    assert rows2 == rows1


def test_delta_joins_flag_forces_full_probes(indexless_world):
    """delta_joins=False keeps delta SCANS but full probes — the
    benchmark baseline — and both engines answer identically."""
    plan, data = indexless_world

    def drive(eng):
        out = []
        eng.submit("get_book", {0: (3, 3)})
        eng.run_cycle()
        for i in range(2):
            t = eng.submit("get_book", {0: (3 + i, 3 + i)})
            eng.run_cycle()
            out.append(np.asarray(t.result["rows"]))
        return out

    a = SharedDBEngine(plan, tpcw.DEFAULT_UPDATE_SLOTS, data, jit=False)
    b = SharedDBEngine(plan, tpcw.DEFAULT_UPDATE_SLOTS, data, jit=False,
                       delta_joins=False)
    ra, rb = drive(a), drive(b)
    assert a.delta_join_cycles == 2 and a.full_join_cycles == 1
    assert b.delta_join_cycles == 0 and b.full_join_cycles == 3
    assert b.last_join_path == "full"
    for x, y in zip(ra, rb):
        assert (x == y).all()


def test_carry_layout_assertion_refuses_foreign_carry(indexless_world):
    """Satellite audit: a delta heartbeat must never consume a carry
    produced under a different admission layout.  The guard is an
    always-on RuntimeError (not a strippable assert) so it survives
    ``python -O`` — plan folding swaps layouts at runtime."""
    plan, data = indexless_world
    eng = SharedDBEngine(plan, tpcw.DEFAULT_UPDATE_SLOTS, data, jit=False)
    eng.submit("get_book", {0: (1, 1)})
    eng.run_cycle()
    eng._carry_token = ("other-layout",)              # simulate re-lower
    eng.submit("get_book", {0: (1, 1)})
    with pytest.raises(RuntimeError, match="admission layout"):
        eng.run_cycle()


def test_cycle_result_reports_join_path(indexless_world):
    """CycleResult attribution: join_path rides along with scan_path."""
    plan, data = indexless_world
    eng = SharedDBEngine(plan, tpcw.DEFAULT_UPDATE_SLOTS, data, jit=False)
    eng.submit("get_book", {0: (1, 1)})
    first = eng.run_until_drained()
    assert [d.join_path for d in first] == ["full"]
    eng.submit("get_book", {0: (2, 2)})
    second = eng.run_until_drained()
    assert [d.join_path for d in second] == ["delta"]
    # dense-index plans have no carried joins: join_path stays empty
    dense_plan = tpcw.build_tpcw_plan(SCALE_I, SCALE_C)
    dense = SharedDBEngine(dense_plan, tpcw.DEFAULT_UPDATE_SLOTS, data,
                           jit=False)
    dense.submit("get_book", {0: (1, 1)})
    assert [d.join_path for d in dense.run_until_drained()] == [""]


def test_jnp_pallas_delta_join_engine_parity(indexless_world):
    """Both backends produce identical tickets across seed, delta-join
    and PK-fallback heartbeats."""
    plan, data = indexless_world
    engines = {k: SharedDBEngine(plan, tpcw.DEFAULT_UPDATE_SLOTS, data,
                                 jit=False, kernels=k)
               for k in ("jnp", "pallas")}
    queries = [("get_book", {0: (5, 5)}), ("order_lines", {0: (10, 10)}),
               ("get_cart", {0: (12, 12)})]
    beats = [
        [],                                           # seed
        [("customer", "update", {"key": 3, "col": "c_expiration",
                                 "val": 13333})],     # delta joins
        [("item", "update", {"key": 50, "col": "i_cost",
                             "val": 4242})],          # PK fallback
        [],                                           # delta again
    ]
    for updates in beats:
        tickets = {}
        for k, eng in engines.items():
            for u in updates:
                eng.submit_update(*u)
            tickets[k] = [eng.submit(n, p) for n, p in queries]
            eng.run_cycle()
        assert (engines["jnp"].last_join_path
                == engines["pallas"].last_join_path)
        for a, b in zip(tickets["jnp"], tickets["pallas"]):
            assert (np.asarray(a.result["rows"])
                    == np.asarray(b.result["rows"])).all(), a.template
    assert engines["pallas"].delta_join_cycles >= 2
