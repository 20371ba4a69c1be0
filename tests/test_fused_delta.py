"""Fused delta-heartbeat mega-kernel suite (PR-6 tentpole acceptance).

Three proof obligations for ``backend.fused_delta``:

  * launch count — a steady-state delta-join beat through the engine
    issues exactly ONE fused backend op (counted at trace time by the
    counting backend every engine wraps around its operator backend):
    no chained pane / scan_delta / join_delta / full-probe launches
    hide behind it.  The chained fallback (a backend WITHOUT
    fused_delta) still works and still produces identical tickets.
  * kernel parity — ``fused_delta_pallas`` (interpret mode) is
    bit-identical to the ``fused_delta_ref`` oracle on padded tails
    (table heights straddling the 256-row pane tile), empty dirty
    sets, pane-boundary dirty rows, pseudo-partitioned (block-join)
    probe sides, and — when hypothesis is installed — randomized
    geometries.
  * engine parity — jnp vs pallas full-engine ticket parity through
    the sharded differential harness at shard counts 1 / 2 / 4 (the
    fused op runs INSIDE shard_map, so per-shard slicing must not
    perturb the merged rids or scan words).
"""
import dataclasses

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.backends import (FusedJoinIn, FusedScanIn, get_backend,
                                 register_backend)
from repro.core.executor import SharedDBEngine
from repro.core.storage import INT_SENTINEL, build_key_partitions
from repro.kernels import ref
from repro.kernels.fused_delta import fused_delta_pallas
from repro.workloads import tpcw

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

SCALE_I, SCALE_C = 64, 128

# delta ops the fused launch must fully absorb: a steady-state beat
# issuing ANY of these has fallen off the fused path
CHAINED_DELTA_OPS = ("scan", "scan_delta", "join_delta",
                     "join_partitioned", "join_block")


# ------------------------------------------------------------ builders
def mk_scan(T, C, Q, A, D, dn, span, seed, boundary_rows=()):
    r = np.random.default_rng(seed)
    cols = jnp.asarray(r.integers(0, 50, (C, T)), jnp.int32)
    lo = jnp.asarray(r.integers(0, 30, (C, Q)), jnp.int32)
    hi = lo + jnp.asarray(r.integers(0, 30, (C, Q)), jnp.int32)
    w = Q // 32
    w0 = int(r.integers(0, max(1, w - A + 1)))
    lo_p = jnp.asarray(np.array(lo)[:, w0 * 32:(w0 + A) * 32])
    hi_p = jnp.asarray(np.array(hi)[:, w0 * 32:(w0 + A) * 32])
    valid = jnp.asarray(r.random(T) < 0.9)
    carry = jnp.asarray(
        r.integers(0, 2**32, (T, w), dtype=np.uint64).astype(np.uint32))
    pool = [b for b in boundary_rows if b < T]
    extra = [x for x in r.choice(T, size=D, replace=False)
             if x not in pool][:max(dn - len(pool), 0)]
    rows = np.sort(np.asarray(pool + extra, np.int32)[:dn])
    rows = jnp.asarray(np.concatenate(
        [rows, np.full(D - len(rows), T, np.int32)]))
    return FusedScanIn(cols, lo, hi, lo_p, hi_p, valid, carry,
                       jnp.int32(w0), jnp.int32(span), rows,
                       jnp.int32(min(dn, D)))


def mk_join(Tl, Tr, D, dn, seed, pseudo=False):
    r = np.random.default_rng(seed)
    keys = jnp.asarray(r.integers(0, Tr, Tl), jnp.int32)
    kr = jnp.asarray(r.permutation(Tr), jnp.int32)
    vr = jnp.asarray(r.random(Tr) < 0.9)
    if pseudo:
        # the block-join probe side as lowering builds it: ONE bucket
        # covering the whole pk table (see lowering._pseudo_partitions)
        bkeys = jnp.where(vr, kr, INT_SENTINEL)[None, :]
        brows = jnp.where(vr, jnp.arange(Tr, dtype=jnp.int32), -1)[None, :]
        bounds = jnp.full((1,), np.iinfo(np.int32).min, jnp.int32)
    else:
        bkeys, brows, bounds = build_key_partitions(kr, vr, 2, Tr // 2 + 8)
    rows = np.sort(r.choice(Tl, size=dn, replace=False)).astype(np.int32)
    rows = jnp.asarray(np.concatenate([rows, np.full(D - dn, Tl,
                                                     np.int32)]))
    rid_carry = jnp.asarray(r.integers(-1, Tr, Tl), jnp.int32)
    return FusedJoinIn(keys, rows, jnp.int32(dn), bkeys, brows, bounds,
                       rid_carry)


def _assert_fused_matches_ref(scan_in, join_in, tag=""):
    wr, rr = ref.fused_delta_ref(scan_in, join_in)
    wp, rp = fused_delta_pallas(scan_in, join_in, interpret=True)
    assert len(wr) == len(wp) and len(rr) == len(rp)
    for i, (a, b) in enumerate(zip(wr, wp)):
        np.testing.assert_array_equal(np.array(a), np.array(b),
                                      err_msg=f"{tag}:words[{i}]")
    for i, (a, b) in enumerate(zip(rr, rp)):
        np.testing.assert_array_equal(np.array(a), np.array(b),
                                      err_msg=f"{tag}:rids[{i}]")


# ------------------------------------------------------- kernel parity
def test_fused_kernel_matches_ref_mixed_stages():
    """Three scan stages (padded tail at T=300, exact tile at T=256,
    two-tile tail at T=700) + a partitioned and a pseudo-partitioned
    probe, all in one launch."""
    _assert_fused_matches_ref(
        (mk_scan(300, 2, 64, 1, 8, 5, 1, 1),
         mk_scan(256, 3, 96, 2, 16, 0, 0, 2),
         mk_scan(700, 1, 32, 1, 4, 4, 1, 3)),
        (mk_join(300, 128, 8, 3, 4),
         mk_join(256, 64, 8, 8, 5, pseudo=True)),
        "mixed")


def test_fused_kernel_pane_boundary_dirty_rows():
    """Dirty rows pinned to the pane-tile seams (255 / 256) and the
    last real row — the gathered compare must land in the right grid
    step on both sides of every tile boundary."""
    _assert_fused_matches_ref(
        (mk_scan(300, 2, 64, 1, 8, 5, 1, 11,
                 boundary_rows=(0, 255, 256, 299)),
         mk_scan(512, 1, 64, 2, 8, 4, 1, 12,
                 boundary_rows=(255, 256, 511)),),
        (mk_join(300, 64, 4, 2, 13),), "boundary")


def test_fused_kernel_empty_dirty_and_zero_span():
    """dn == 0 and span == 0 everywhere: the fused op must be an exact
    identity on the carried words and rids (the cond-skip contract the
    lowering relies on for untouched stages)."""
    si = (mk_scan(128, 2, 64, 2, 8, 0, 0, 9),)
    ji = (mk_join(128, 32, 4, 0, 10),)
    _assert_fused_matches_ref(si, ji, "empty_dirty")
    words, rids = fused_delta_pallas(si, ji, interpret=True)
    np.testing.assert_array_equal(np.array(words[0]),
                                  np.array(si[0].carry))
    np.testing.assert_array_equal(np.array(rids[0]),
                                  np.array(ji[0].rid_carry))


def test_fused_kernel_scan_only_join_only_and_empty():
    _assert_fused_matches_ref((mk_scan(64, 1, 32, 1, 4, 2, 1, 7),), (),
                              "scan_only")
    _assert_fused_matches_ref((), (mk_join(100, 50, 4, 4, 8),),
                              "join_only")
    assert fused_delta_pallas((), (), interpret=True) == ((), ())


if HAVE_HYPOTHESIS:
    @settings(max_examples=15, deadline=None)
    @given(t1=st.integers(16, 520), c1=st.integers(1, 3),
           dn1=st.integers(0, 6), span1=st.integers(0, 1),
           tr=st.integers(8, 70), dnj=st.integers(0, 6),
           pseudo=st.booleans(), seed=st.integers(0, 2**16))
    def test_fused_kernel_matches_ref_randomized(t1, c1, dn1, span1, tr,
                                                 dnj, pseudo, seed):
        _assert_fused_matches_ref(
            (mk_scan(t1, c1, 64, 1, 8, min(dn1, t1), span1, seed),),
            (mk_join(t1, tr, 8, min(dnj, t1), seed + 1, pseudo=pseudo),),
            "rand")


# --------------------------------------------------- engine launch count
def _indexless_engine(kernels="auto"):
    # "auto" follows the REPRO_KERNELS override, so each CI leg proves
    # the launch-count contract on ITS backend (jnp and pallas alike)
    rng = np.random.default_rng(0)
    plan = tpcw.build_tpcw_plan(SCALE_I, SCALE_C, dense_pk_index=False)
    data = tpcw.generate_data(rng, SCALE_I, SCALE_C)
    return SharedDBEngine(plan, tpcw.DEFAULT_UPDATE_SLOTS, data,
                          jit=False, kernels=kernels)


def _steady_delta_join_beats(eng, beats=3):
    """Seed, then drive slot-stable trickle beats (customer-only writes,
    fixed join templates) until the engine is on the delta-join path;
    returns the CycleResults of the steady-state beats."""
    eng.submit("order_lines", {0: (10, 10)})
    eng.submit("get_cart", {0: (12, 12)})
    eng.submit("get_book", {0: (5, 5)})
    eng.run_until_drained()                              # seed (full)
    out = []
    for i in range(beats):
        eng.submit_update("customer", "update",
                          {"key": 3 + i, "col": "c_expiration",
                           "val": 13000 + i})
        eng.submit("order_lines", {0: (10, 10)})
        eng.submit("get_cart", {0: (12, 12)})
        eng.submit("get_book", {0: (5, 5)})
        out.extend(eng.run_until_drained())
    return out


def test_steady_state_delta_beat_is_one_fused_launch():
    """The PR-6 contract, proven through the engine's own counting
    backend: every steady-state delta-join beat issues EXACTLY one
    fused_delta op and zero chained delta / full-path operator
    launches (group-by post stages are the only other backend ops a
    beat may carry)."""
    eng = _indexless_engine()
    beats = _steady_delta_join_beats(eng)
    steady = [b for b in beats if b.join_path == "delta"]
    assert len(steady) >= 2, [
        (b.scan_path, b.join_path) for b in beats]
    for b in steady:
        assert b.backend_ops.get("fused_delta") == 1, b.backend_ops
        for op in CHAINED_DELTA_OPS:
            assert b.backend_ops.get(op, 0) == 0, (op, b.backend_ops)
        leftovers = set(b.backend_ops) - {"fused_delta", "groupby"}
        assert all(b.backend_ops[op] == 0 for op in leftovers), \
            b.backend_ops


def test_full_rescan_beat_never_uses_fused_op():
    """The seed / reseed beat runs the full scan + probe chain — the
    fused op is a delta-path-only construct."""
    eng = _indexless_engine()
    eng.submit("get_book", {0: (5, 5)})
    done = eng.run_until_drained()
    assert done and done[-1].scan_path == "full"
    assert done[-1].backend_ops.get("fused_delta", 0) == 0
    assert done[-1].backend_ops.get("scan", 0) >= 1


def test_chained_fallback_backend_matches_fused_tickets():
    """A backend WITHOUT fused_delta falls back to the chained
    pane/scan_delta/join_delta ops, still runs the delta path, and
    produces tickets equal to the fused engine's."""
    chained = dataclasses.replace(get_backend("jnp"),
                                  name="jnp-chained-test",
                                  fused_delta=None)
    register_backend(chained)
    eng_f = _indexless_engine(kernels="jnp")
    eng_c = _indexless_engine(kernels="jnp-chained-test")
    beats_f = _steady_delta_join_beats(eng_f)
    beats_c = _steady_delta_join_beats(eng_c)
    assert [b.scan_path for b in beats_f] == \
        [b.scan_path for b in beats_c]
    assert [b.join_path for b in beats_f] == \
        [b.join_path for b in beats_c]
    assert any(b.join_path == "delta" for b in beats_c)
    for bf, bc in zip(beats_f, beats_c):
        if bf.join_path == "delta":
            assert bc.backend_ops.get("fused_delta", 0) == 0
            assert bc.backend_ops.get("join_delta", 0) >= 1
        for name in bf.tickets:
            for tf, tc in zip(bf.tickets[name], bc.tickets[name]):
                for k in tf.result:
                    np.testing.assert_array_equal(
                        np.asarray(tf.result[k]),
                        np.asarray(tc.result[k]), err_msg=(name, k))


# ------------------------------------------- sharded jnp-vs-pallas parity
@pytest.mark.parametrize("shards", [1, 2, 4])
def test_sharded_fused_parity_jnp_vs_pallas(row_mesh, shards):
    """Full-engine ticket parity, jnp vs pallas, through the sharded
    differential geometry: the fused op runs inside shard_map on
    shard-local slices, so the merged rids / scan words must agree
    across backends at every shard count."""
    mesh = row_mesh(shards)
    rng = np.random.default_rng(0)
    plan = tpcw.build_tpcw_plan(SCALE_I, SCALE_C, dense_pk_index=False)
    data = tpcw.generate_data(rng, SCALE_I, SCALE_C)
    engines = {k: SharedDBEngine(plan, tpcw.DEFAULT_UPDATE_SLOTS, data,
                                 kernels=k, mesh=mesh)
               for k in ("jnp", "pallas")}

    def beat(updates, subs):
        tickets = {}
        for k, eng in engines.items():
            for u in updates:
                eng.submit_update(*u)
            tickets[k] = [eng.submit(n, p) for n, p in subs]
            eng.run_until_drained()
        assert engines["jnp"].last_scan_path == \
            engines["pallas"].last_scan_path
        assert engines["jnp"].last_join_path == \
            engines["pallas"].last_join_path
        for tj, tp in zip(tickets["jnp"], tickets["pallas"]):
            for k in tj.result:
                a, b = np.asarray(tj.result[k]), np.asarray(tp.result[k])
                assert a.shape == b.shape and (a == b).all(), \
                    (tj.template, k)

    subs = [("order_lines", {0: (10, 10)}), ("get_cart", {0: (12, 12)}),
            ("get_book", {0: (5, 5)})]
    beat([], subs)                                       # seed (full)
    for i in range(2):                                   # carried-rid
        beat([("customer", "update",
               {"key": 3 + i, "col": "c_expiration",
                "val": 13000 + i})], subs)
    beat([("item", "update",                             # PK-side write
           {"key": 7, "col": "i_cost", "val": 4242})], subs)
    assert engines["jnp"].delta_join_cycles >= 1
    assert engines["pallas"].delta_join_cycles >= 1
