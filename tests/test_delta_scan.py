"""Incremental scan deltas: dirty-row tracking in storage, delete-then-
update batches, dirty-set overflow -> full-rescan fallback, admission
windows overlapping dirty rows, empty batches carrying words unchanged,
jnp-vs-pallas delta-kernel parity on padded tails, and the acceptance
property — a steady-state heartbeat runs the delta path WITHOUT invoking
the full-width compare kernel."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import backends
from repro.core.baseline import QueryAtATimeEngine
from repro.core.executor import SharedDBEngine
from repro.core.lowering import lower_plan
from repro.core.plan import Pred, QueryTemplate, compile_plan
from repro.core.storage import (Catalog, TableSchema, UpdateSlots,
                                apply_updates, bulk_load,
                                empty_update_batch)
from repro.kernels import ref
from repro.kernels.fused_delta import delta_scan_pallas
from repro.workloads import tpcw


# ------------------------------------------------- storage dirty tracking
def _table_world(dirty_cap=8):
    schema = TableSchema("t", ("k", "v"), 32, pk="k", key_space=64,
                         dirty_cap=dirty_cap)
    t = bulk_load(schema, {"k": np.arange(16), "v": np.arange(16) * 10})
    return schema, t


def test_apply_updates_tracks_dirty_rows():
    schema, t = _table_world()
    b = empty_update_batch(schema, UpdateSlots(2, 2, 2))
    b["del_key"] = b["del_key"].at[0].set(3)        # row 3
    b["del_mask"] = b["del_mask"].at[0].set(True)
    b["del_key"] = b["del_key"].at[1].set(55)       # absent: not dirty
    b["del_mask"] = b["del_mask"].at[1].set(True)
    b["upd_key"] = b["upd_key"].at[0].set(7)        # row 7
    b["upd_col"] = b["upd_col"].at[0].set(1)
    b["upd_val"] = b["upd_val"].at[0].set(999)
    b["upd_mask"] = b["upd_mask"].at[0].set(True)
    b["ins_rows"]["k"] = b["ins_rows"]["k"].at[0].set(40)   # row 16
    b["ins_rows"]["v"] = b["ins_rows"]["v"].at[0].set(1)
    b["ins_mask"] = b["ins_mask"].at[0].set(True)
    t2 = apply_updates(schema, t, b)
    rows = np.asarray(t2["_dirty_rows"])
    assert rows[rows < schema.capacity].tolist() == [3, 7, 16]  # sorted
    assert int(t2["_dirty_n"]) == 3
    assert not bool(t2["_dirty_overflow"])
    # a fresh table and an empty batch are fully clean (pad sentinel ==
    # the table capacity, keeping the set sorted for the fast scatter)
    assert (np.asarray(t["_dirty_rows"]) == schema.capacity).all()
    t3 = apply_updates(schema, t2, empty_update_batch(schema,
                                                      UpdateSlots(2, 2, 2)))
    assert (np.asarray(t3["_dirty_rows"]) == schema.capacity).all()
    assert int(t3["_dirty_n"]) == 0


def test_delete_then_update_same_key_one_batch_marks_row_dirty_once():
    """Arrival order: the update finds nothing post-delete, so the row is
    dirtied by the delete alone and stays deleted."""
    schema, t = _table_world()
    b = empty_update_batch(schema, UpdateSlots(1, 1, 1))
    b["del_key"] = b["del_key"].at[0].set(5)
    b["del_mask"] = b["del_mask"].at[0].set(True)
    b["upd_key"] = b["upd_key"].at[0].set(5)
    b["upd_col"] = b["upd_col"].at[0].set(1)
    b["upd_val"] = b["upd_val"].at[0].set(123)
    b["upd_mask"] = b["upd_mask"].at[0].set(True)
    t2 = apply_updates(schema, t, b)
    assert not bool(t2["_valid"][5])
    assert int(t2["v"][5]) == 50                    # update found nothing
    rows = np.asarray(t2["_dirty_rows"])
    assert rows[rows < schema.capacity].tolist() == [5]
    assert int(t2["_dirty_n"]) == 1


def test_dirty_set_overflow_flag():
    schema, t = _table_world(dirty_cap=2)
    b = empty_update_batch(schema, UpdateSlots(1, 4, 1))
    for i, key in enumerate((1, 2, 9)):
        b["upd_key"] = b["upd_key"].at[i].set(key)
        b["upd_col"] = b["upd_col"].at[i].set(1)
        b["upd_val"] = b["upd_val"].at[i].set(7)
        b["upd_mask"] = b["upd_mask"].at[i].set(True)
    t2 = apply_updates(schema, t, b)
    assert bool(t2["_dirty_overflow"])
    assert int(t2["_dirty_n"]) == 2                 # capacity-clamped
    stored = np.asarray(t2["_dirty_rows"])
    assert set(stored[stored < schema.capacity].tolist()) <= {1, 2, 9}


# ---------------------------------------------------- delta kernel parity
@pytest.mark.parametrize("seed,C,T,Q,D", [
    (0, 1, 37, 64, 9),       # odd table size, pad slots in rows
    (1, 3, 200, 96, 16),     # multi-column
    (2, 2, 5, 32, 7),        # D > T: duplicate dirty rows
    (3, 4, 131, 416, 33),    # TPC-W-sized window, non-multiple D
    (4, 1, 1, 32, 1),        # degenerate single row
])
def test_delta_kernel_jnp_pallas_parity_padded_tails(seed, C, T, Q, D):
    rng = np.random.default_rng(seed)
    cols = jnp.asarray(rng.integers(0, 50, (C, T)), jnp.int32)
    lo = jnp.asarray(rng.integers(0, 50, (C, Q)), jnp.int32)
    hi = lo + jnp.asarray(rng.integers(0, 20, (C, Q)), jnp.int32)
    valid = jnp.asarray(rng.random(T) > 0.2)
    # pad sentinels both below and above range: callers drop them
    rows = jnp.asarray(rng.choice(
        np.concatenate([np.arange(T), [-1, T, T + 3, T]]), D), jnp.int32)
    want = ref.delta_scan_ref(cols, lo, hi, valid, rows)
    got = delta_scan_pallas(cols, lo, hi, valid, rows, interpret=True)
    keep = (np.asarray(rows) >= 0) & (np.asarray(rows) < T)
    assert (np.asarray(got)[keep] == np.asarray(want)[keep]).all()
    # the freshly scanned words agree with the full-table oracle rows
    full = ref.clockscan_ref(cols, lo, hi, valid)
    safe = np.clip(np.asarray(rows), 0, T - 1)
    assert (np.asarray(want)[keep] == np.asarray(full)[safe][keep]).all()


# ------------------------------------------------------- engine-level path
SCALE_I, SCALE_C = 128, 256


@pytest.fixture(scope="module")
def tpcw_world():
    rng = np.random.default_rng(5)
    plan = tpcw.build_tpcw_plan(SCALE_I, SCALE_C)
    data = tpcw.generate_data(rng, SCALE_I, SCALE_C)
    return plan, data


def _recording_backend(record):
    """The jnp backend with every compare-kernel invocation's query width
    recorded (trace-time: pair with jit=False engines)."""
    base = backends.get_backend("jnp")

    def scan(cols, lo, hi, valid):
        record.append(int(lo.shape[1]))
        return base.scan(cols, lo, hi, valid)

    backends.register_backend(backends.OperatorBackend(
        name="recording-jnp", scan=scan, join_block=base.join_block,
        join_partitioned=base.join_partitioned, groupby=base.groupby,
        scan_delta=base.scan_delta, join_delta=base.join_delta))
    return "recording-jnp"


def test_steady_state_runs_delta_without_full_width_compare(tpcw_world):
    """Acceptance: a steady-state heartbeat (<=1% dirty rows, trickle
    admission) takes the delta path — the full-table compare at the item
    stage's full window width is never invoked after the seeding cycle,
    only panes of 32 * delta_words slots."""
    plan, data = tpcw_world
    record = []
    name = _recording_backend(record)
    eng = SharedDBEngine(plan, tpcw.DEFAULT_UPDATE_SLOTS, data, jit=False,
                         kernels=name)
    item_stage = next(s for s in lower_plan(plan).scans
                      if s.table == "item")
    full_width = item_stage.q_window
    pane_width = 32 * item_stage.delta_words
    assert pane_width < full_width

    eng.submit("admin_item", {0: (1, 1)})
    eng.run_cycle()                                  # seeds the carry
    assert eng.last_scan_path == "full"
    assert full_width in record
    record.clear()

    base = QueryAtATimeEngine(plan, data, jit=False)
    for i in range(4):                               # steady state
        upd = ("item", "update", {"key": 10 + i, "col": "i_cost",
                                  "val": 1000 + i})
        eng.submit_update(*upd)
        base.apply_update(*upd)
        t = eng.submit("admin_item", {0: (10 + i, 10 + i)})
        eng.run_cycle()
        assert eng.last_scan_path == "delta"
        assert eng.last_delta_overflow == 0
        want = base.execute(t.template, t.params).result
        assert (np.asarray(t.result["rows"])
                == np.asarray(want["rows"])).all()
    assert eng.delta_cycles == 4
    assert full_width not in record                  # panes only
    assert pane_width in record


def test_admission_window_overlap_with_dirty_rows(tpcw_world):
    """A query admitted in the same heartbeat that dirties the row it
    matches: the dirty-row refresh must evaluate the NEW query's
    predicate, not the carried (pre-admission) words."""
    plan, data = tpcw_world
    eng = SharedDBEngine(plan, tpcw.DEFAULT_UPDATE_SLOTS, data, jit=False)
    base = QueryAtATimeEngine(plan, data, jit=False)
    eng.submit("search_subject", {0: (3, 3)})
    eng.run_cycle()                                  # seed carry
    # move item 50 into subject 3 and immediately search subject 3
    upd = ("item", "update", {"key": 50, "col": "i_subject", "val": 3})
    eng.submit_update(*upd)
    base.apply_update(*upd)
    t = eng.submit("search_subject", {0: (3, 3)})
    eng.run_cycle()
    assert eng.last_scan_path == "delta"
    rows = set(int(x) for x in np.asarray(t.result["rows"]) if x >= 0)
    want = base.execute("search_subject", {0: (3, 3)}).result
    assert rows == set(int(x) for x in want["rows"] if x >= 0)
    assert 50 in rows


def test_delete_then_update_same_key_through_delta_engine(tpcw_world):
    """The delta heartbeat honours arrival order inside one batch: a
    delete-then-update of the same key leaves the row deleted, and the
    carried words drop it from every standing result."""
    plan, data = tpcw_world
    eng = SharedDBEngine(plan, tpcw.DEFAULT_UPDATE_SLOTS, data, jit=False)
    t0 = eng.submit("admin_item", {0: (20, 20)})
    eng.run_cycle()
    assert (np.asarray(t0.result["rows"]) >= 0).sum() == 1
    eng.submit_update("item", "delete", {"key": 20})
    eng.submit_update("item", "update",
                      {"key": 20, "col": "i_cost", "val": 1})
    t1 = eng.submit("admin_item", {0: (20, 20)})
    eng.run_cycle()
    assert eng.last_scan_path == "delta"
    assert (np.asarray(t1.result["rows"]) >= 0).sum() == 0


def test_empty_update_batches_carry_words_unchanged(tpcw_world):
    """Heartbeats with no updates (and repeat admission) must carry the
    scan words forward bit-identically to a full rescan."""
    plan, data = tpcw_world

    def drive(eng):
        eng.submit("search_subject", {0: (3, 3)})
        eng.run_cycle()
        for _ in range(2):                           # empty batches
            eng.submit("search_subject", {0: (3, 3)})
            eng.run_cycle()
        return eng

    a = drive(SharedDBEngine(plan, tpcw.DEFAULT_UPDATE_SLOTS, data,
                             jit=False))
    b = drive(SharedDBEngine(plan, tpcw.DEFAULT_UPDATE_SLOTS, data,
                             jit=False, delta_scans=False))
    assert a.delta_cycles == 2 and b.delta_cycles == 0
    assert set(a._carry["scan"]) == set(b._carry["scan"])
    for table in a._carry["scan"]:
        assert (np.asarray(a._carry["scan"][table])
                == np.asarray(b._carry["scan"][table])).all(), table


def _overflow_world():
    cat = Catalog([TableSchema("t", ("k", "v"), 64, pk="k", key_space=64,
                               dirty_cap=2)])
    tpl = QueryTemplate("by_v", "t", preds=(Pred("t", "v"),), limit=64)
    plan = compile_plan(cat, [tpl], {"by_v": 32}, max_results=64)
    data = {"t": {"k": np.arange(32), "v": np.arange(32) % 8}}
    return plan, SharedDBEngine(plan, UpdateSlots(4, 4, 4), data,
                                jit=False, kernels="jnp")


def test_dirty_overflow_falls_back_to_full_rescan():
    """A batch touching more rows than the dirty set holds must run the
    (safe) full rescan — and the results stay exact."""
    plan, eng = _overflow_world()
    t0 = eng.submit("by_v", {0: (5, 5)})
    eng.run_cycle()                                  # seed carry
    # 1 update fits the dirty set: delta
    eng.submit_update("t", "update", {"key": 5, "col": "v", "val": 5})
    eng.run_cycle()
    assert eng.last_scan_path == "delta"
    # 3 updates overflow dirty_cap=2: host falls back before dispatch
    for key in (1, 2, 9):
        eng.submit_update("t", "update", {"key": key, "col": "v",
                                          "val": 5})
    t1 = eng.submit("by_v", {0: (5, 5)})
    eng.run_cycle()
    assert eng.last_scan_path == "full"
    rows = set(int(x) for x in np.asarray(t1.result["rows"]) if x >= 0)
    assert rows == {1, 2, 5, 9, 13, 21, 29}          # v == 5 rows
    # the fallback reseeded the carry: the next light beat is delta again
    eng.submit("by_v", {0: (5, 5)})
    eng.run_cycle()
    assert eng.last_scan_path == "delta"


def test_admission_pane_overflow_falls_back_to_full_rescan(tpcw_world):
    """Admission churn across more words than a stage's pane holds must
    also fall back (many templates flip at once)."""
    plan, data = tpcw_world
    eng = SharedDBEngine(plan, tpcw.DEFAULT_UPDATE_SLOTS, data, jit=False)
    eng.submit("get_book", {0: (1, 1)})
    eng.run_cycle()
    # activate slots across many item-window words in one heartbeat
    for name in ("get_book", "get_related", "search_subject",
                 "search_title", "new_products", "order_lines"):
        eng.submit(name, {0: (2, 2)})
    eng.run_cycle()
    assert eng.last_scan_path == "full"


def test_cycle_result_reports_path_and_counts(tpcw_world):
    """Satellite: run_until_drained attributes each heartbeat — admitted
    queries, dirty touches, and which scan path ran."""
    plan, data = tpcw_world
    eng = SharedDBEngine(plan, tpcw.DEFAULT_UPDATE_SLOTS, data, jit=False)
    eng.submit("get_book", {0: (1, 1)})
    first = eng.run_until_drained()
    assert [d.scan_path for d in first] == ["full"]
    assert first[0].admitted == 1 and first[0].dirty == 0
    eng.submit("get_book", {0: (2, 2)})
    eng.submit_update("item", "update", {"key": 2, "col": "i_cost",
                                         "val": 42})
    second = eng.run_until_drained()
    assert [d.scan_path for d in second] == ["delta"]
    assert second[0].admitted == 1 and second[0].dirty == 1
