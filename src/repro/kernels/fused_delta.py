"""Fused delta-heartbeat mega-kernel: the WHOLE incremental beat in one
``pallas_call``.

The chained delta path (PR 3/4) launches one kernel per phase per stage
— an admission-pane compare, a dirty-row rescan and a dirty-spine-row
bucket probe for every predicated scan / carried join — and threads
materialized intermediates (pane words, dirty words, dirty rids) between
them through XLA.  At trickle rates the beat's wall time is dominated by
that dispatch chain, not by compute.  This kernel collapses the chain:

  grid = (N,)   N = Σ_stages (pane tiles + dirty slots) + Σ_joins slots

one flat grid whose every program is ONE unit of delta work, routed by a
scalar-prefetched work descriptor ``sdesc int32[N, 4]`` (flattened to
``int32[4N]`` in SMEM):

  sdesc[i] = (kind, owner, idx, gather)

  kind 0 (PANE)  — one ``R``-row tile of stage ``owner``'s admission-
                   pane compare: the pane-width predicate slices
                   (lo_p/hi_p, pre-sliced at w0 by the caller) against
                   the tile's column values, bit-packed to ``A`` words
                   per row.  ``idx`` picks the tile.
  kind 1 (DIRTY) — one dirty row of stage ``owner``, re-evaluated
                   against the FULL window: ``gather`` holds the row id
                   (pad slots clamp in range); the BlockSpec index_map
                   reads it to DMA the 128-lane block of cols holding
                   that row — the scalar-prefetch gather — and the
                   program selects the row's lane.
  kind 2 (PROBE) — one dirty spine row of carried join ``owner``:
                   ``gather`` holds the row's bucket index (the
                   ``searchsorted`` routing runs in the XLA prologue —
                   it needs the key VALUE, which no index_map can see);
                   the index_map DMAs the 8-bucket block holding it, the
                   program probes that ONE bucket pane against the row's
                   key, read from SMEM.  Block-kind joins arrive as
                   single-bucket pseudo-partitions, so every carried
                   join probes through this same path.

Layout on the chip: table rows sit on the 128 lanes and queries on the
sublanes (predicate bounds arrive as ``[C, Q, 1]`` columns), validity is
int32, every block is full-extent or (8, 128)-aligned, and outputs are
rank-3 with the owned unit on the leading axis.

Non-owning programs park on per-output GARBAGE blocks (one spare tile /
slot appended past the real extent), so each real output block has
exactly one writer and no cross-program masking is needed.  A thin XLA
epilogue inside the op — still one kernel launch on the hot path —
merges the pane into the carried words (in-place dynamic_update_slice,
skipped when ``span == 0``), scatters the dirty words/rids back on the
sorted-unique fast path (pad sentinels drop), and returns the merged
carries directly: the ``[Tl, B]`` candidate panes and full-window
compare matrices of the chained path are never materialized.

The standalone ``delta_scan_pallas`` / ``delta_join_pallas`` kernels
are the DIRTY / PROBE program bodies as free-standing calls, kept as the
chained fallback surface (``OperatorBackend.scan_delta`` /
``join_delta``) for backends or beats the fused path does not cover.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.storage import scatter_dirty_rows
from repro.kernels.bitmask_join import SUBLANES
from repro.kernels.clockscan import (LANES, as_query_column, match_ranges,
                                     pack_words, round_up, words_to_rows)

PANE_TILE = 1024
DIRTY_LANES = LANES        # lane block a dirty row is gathered from

_PANE, _DIRTY, _PROBE = 0, 1, 2
_NCOL = 4                  # sdesc columns: (kind, owner, idx, gather)


class ScanGeom(NamedTuple):
    """Static geometry of one predicated scan stage in the fused grid."""
    C: int        # predicated columns
    Q: int        # full window width (slots)
    A: int        # admission-pane words
    R: int        # pane tile rows (a multiple of 128)
    nt: int       # pane tiles (ceil(T / R)); tile nt is the garbage tile
    D: int        # dirty-row slots; slot D is the garbage slot


class JoinGeom(NamedTuple):
    """Static geometry of one carried join in the fused grid."""
    B: int        # bucket pane width
    D: int        # dirty spine-row slots; slot D is the garbage slot
    P: int        # bucket count (1 for block pseudo-partitions)


def pane_tiling(T: int):
    """(R, nt): pane tile rows and tile count for a table of T rows."""
    R = min(PANE_TILE, round_up(T, LANES))
    return R, -(-T // R)


def probe_rows(P: int) -> int:
    """Buckets per PROBE block: one sublane tile, or all of a small P."""
    return min(SUBLANES, P)


def scan_geometry(e) -> ScanGeom:
    """Geometry from a ``FusedScanIn``'s static shapes."""
    C, T = e.cols.shape
    R, nt = pane_tiling(T)
    return ScanGeom(C=C, Q=e.lo.shape[1], A=e.lo_p.shape[1] // 32,
                    R=R, nt=nt, D=e.rows.shape[0])


def join_geometry(e) -> JoinGeom:
    """Geometry from a ``FusedJoinIn``'s static shapes."""
    P, B = e.bkeys.shape
    return JoinGeom(B=B, D=e.rows.shape[0], P=P)


def build_schedule(sgeom, jgeom) -> np.ndarray:
    """The STATIC third of the work descriptor: int32[N, 3] rows of
    (kind, owner, idx) — one pane tile / dirty slot / probe slot per
    grid program, in stage order.  Pure geometry, no runtime data: this
    is the schedule ``analysis_static.kernel_passes`` validates (every
    extent covered exactly once, grid length == schedule length)."""
    rows = []
    for s, g in enumerate(sgeom):
        rows += [(_PANE, s, t) for t in range(g.nt)]
        rows += [(_DIRTY, s, d) for d in range(g.D)]
    for j, g in enumerate(jgeom):
        rows += [(_PROBE, j, d) for d in range(g.D)]
    return np.asarray(rows, np.int32).reshape(len(rows), 3)


def build_sdesc(schedule, sgeom, jgeom, scan_rows, probe_buckets):
    """Assemble the full scalar-prefetch descriptor int32[N, 4] =
    (kind, owner, idx, gather) by appending the runtime gather column:
    clamped dirty-row ids for DIRTY rows (the BlockSpec index_map DMAs
    the lane block holding that row), routed bucket indices for PROBE
    rows, zeros for PANE rows (unused)."""
    gathers = []
    for g, rows in zip(sgeom, scan_rows):
        gathers.append(jnp.zeros((g.nt,), jnp.int32))
        gathers.append(jnp.clip(rows, 0, g.nt * g.R - 1)
                       .astype(jnp.int32))
    gathers += [b.astype(jnp.int32) for b in probe_buckets]
    gather = jnp.concatenate(gathers) if gathers else \
        jnp.zeros((0,), jnp.int32)
    return jnp.concatenate([jnp.asarray(schedule), gather[:, None]],
                           axis=1)


def _desc(d, i, k):
    """Field ``k`` of descriptor row ``i`` in the flattened descriptor."""
    return d[_NCOL * i + k]


def _own(d, i, k, o):
    """Does grid step ``i``'s descriptor row target (kind k, owner o)?"""
    return (_desc(d, i, 0) == k) & (_desc(d, i, 1) == o)


def make_in_specs(sgeom, jgeom):
    """Input BlockSpecs, in the kernel's ref order: 8 per scan stage
    (cols x2, valid x2, lo/hi, lo_p/hi_p), 3 per join (kd, bkeys,
    brows).  Owners address their real block; non-owners re-read block
    0 (harmless — inputs have no write hazard)."""
    specs = []
    for s, g in enumerate(sgeom):
        C, Q, A, R = g.C, g.Q, g.A, g.R

        def tile(i, d, s=s):
            return jnp.where(_own(d, i, _PANE, s), _desc(d, i, 2), 0)

        def lanes(i, d, s=s):
            return jnp.where(_own(d, i, _DIRTY, s),
                             _desc(d, i, 3) // DIRTY_LANES, 0)

        specs += [
            pl.BlockSpec((C, R), lambda i, d, f=tile: (0, f(i, d))),
            pl.BlockSpec((C, DIRTY_LANES),
                         lambda i, d, f=lanes: (0, f(i, d))),
            pl.BlockSpec((1, R), lambda i, d, f=tile: (0, f(i, d))),
            pl.BlockSpec((1, DIRTY_LANES),
                         lambda i, d, f=lanes: (0, f(i, d))),
            pl.BlockSpec((C, Q, 1), lambda i, d: (0, 0, 0)),
            pl.BlockSpec((C, Q, 1), lambda i, d: (0, 0, 0)),
            pl.BlockSpec((C, 32 * A, 1), lambda i, d: (0, 0, 0)),
            pl.BlockSpec((C, 32 * A, 1), lambda i, d: (0, 0, 0)),
        ]
    for j, g in enumerate(jgeom):
        PB = probe_rows(g.P)

        def bucket(i, d, j=j, PB=PB):
            return jnp.where(_own(d, i, _PROBE, j), _desc(d, i, 3) // PB,
                             0)

        specs += [
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((PB, g.B), lambda i, d, f=bucket: (f(i, d), 0)),
            pl.BlockSpec((PB, g.B), lambda i, d, f=bucket: (f(i, d), 0)),
        ]
    return specs


def make_out_specs(sgeom, jgeom):
    """Output BlockSpecs + shapes: one spare (garbage) tile / slot past
    the real extent parks every non-owning program's write window, so
    each real output block has exactly one writer and no cross-program
    masking is needed.  The owned unit is every output's LEADING block
    index; ``kernel_passes.lint_garbage_park`` re-evaluates these maps
    against a concrete descriptor to prove it."""
    specs, shapes = [], []
    for s, g in enumerate(sgeom):
        specs.append(pl.BlockSpec((1, g.A, g.R), lambda i, d, s=s,
                                  nt=g.nt: (
            jnp.where(_own(d, i, _PANE, s), _desc(d, i, 2), nt), 0, 0)))
        shapes.append(
            jax.ShapeDtypeStruct((g.nt + 1, g.A, g.R), jnp.int32))
        specs.append(pl.BlockSpec((1, g.Q // 32, 1), lambda i, d, s=s,
                                  D=g.D: (
            jnp.where(_own(d, i, _DIRTY, s), _desc(d, i, 2), D), 0, 0)))
        shapes.append(
            jax.ShapeDtypeStruct((g.D + 1, g.Q // 32, 1), jnp.int32))
    for j, g in enumerate(jgeom):
        specs.append(pl.BlockSpec((1, 1, 1), lambda i, d, j=j, D=g.D: (
            jnp.where(_own(d, i, _PROBE, j), _desc(d, i, 2), D), 0, 0)))
        shapes.append(jax.ShapeDtypeStruct((g.D + 1, 1, 1), jnp.int32))
    return specs, shapes


# ---------------------------------------------------------------------------
# Program bodies (shared by the mega-kernel and the standalone kernels)
# ---------------------------------------------------------------------------


def _row_words(cols_ref, valid_ref, lane, lo_ref, hi_ref, n_cols: int,
               qcap: int):
    """One gathered row against the full window -> int32[Q/32, 1].

    ``cols_ref`` [C, L] / ``valid_ref`` [1, L] hold the row at lane
    ``lane``; a masked lane sum extracts it."""
    sel = jax.lax.broadcasted_iota(jnp.int32, valid_ref.shape, 1) == lane
    v = jnp.sum(jnp.where(sel, valid_ref[...], 0), axis=1, keepdims=True)
    xs = [jnp.sum(jnp.where(sel, cols_ref[c:c + 1, :], 0), axis=1,
                  keepdims=True) for c in range(n_cols)]
    ok = jnp.broadcast_to(v != 0, (qcap, 1))
    return pack_words(match_ranges(ok, xs, lo_ref, hi_ref))


def _probe_rid(bkeys_ref, brows_ref, r, key):
    """Max valid row with ``key`` in bucket ``r`` of the [PB, B] block
    (-1 = none) -> int32[1, 1]."""
    rows = brows_ref[...]
    sub = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 0) == r
    hit = sub & (bkeys_ref[...] == key) & (rows >= 0)
    m = jnp.max(jnp.where(hit, rows, -1), axis=1, keepdims=True)
    return jnp.max(m, axis=0, keepdims=True)


def _pad_buckets(bkeys, brows, P: int):
    """Pad the bucket arrays to whole PROBE blocks (pad rows never hit)."""
    pad = round_up(P, probe_rows(P)) - P
    return (jnp.pad(bkeys, ((0, pad), (0, 0))),
            jnp.pad(brows, ((0, pad), (0, 0)), constant_values=-1))


def _route(keys_l, rows, bounds, P: int):
    """XLA prologue of a dirty probe: the dirty rows' keys and the ONE
    bucket each routes to (shared with the reference probe)."""
    kd = keys_l[jnp.clip(rows, 0, keys_l.shape[0] - 1)].astype(jnp.int32)
    b = jnp.searchsorted(bounds, kd, side="right").astype(jnp.int32) - 1
    return kd, jnp.clip(b, 0, P - 1)


# ---------------------------------------------------------------------------
# The mega-kernel
# ---------------------------------------------------------------------------


def _mega_kernel(sdesc_ref, *refs, sgeom, jgeom):
    i = pl.program_id(0)
    kind = _desc(sdesc_ref, i, 0)
    owner = _desc(sdesc_ref, i, 1)
    idx = _desc(sdesc_ref, i, 2)
    gather = _desc(sdesc_ref, i, 3)
    n_in = 8 * len(sgeom) + 3 * len(jgeom)
    for s, g in enumerate(sgeom):
        (cols_t, cols_r, valid_t, valid_r, lo, hi, lo_p,
         hi_p) = refs[8 * s:8 * s + 8]
        pane_out = refs[n_in + 2 * s]
        dwords_out = refs[n_in + 2 * s + 1]

        @pl.when((kind == _PANE) & (owner == s))
        def _():
            ok = jnp.broadcast_to(valid_t[...] != 0, (32 * g.A, g.R))
            xs = [cols_t[c:c + 1, :] for c in range(g.C)]
            pane_out[...] = pack_words(
                match_ranges(ok, xs, lo_p, hi_p))[None]

        @pl.when((kind == _DIRTY) & (owner == s))
        def _():
            dwords_out[...] = _row_words(
                cols_r, valid_r, gather % DIRTY_LANES, lo, hi, g.C,
                g.Q)[None]

    for j, g in enumerate(jgeom):
        kd, bkeys, brows = refs[8 * len(sgeom) + 3 * j:
                                8 * len(sgeom) + 3 * j + 3]
        rid_out = refs[n_in + 2 * len(sgeom) + j]

        @pl.when((kind == _PROBE) & (owner == j))
        def _():
            rid_out[...] = _probe_rid(bkeys, brows,
                                      gather % probe_rows(g.P),
                                      kd[idx])[None]


def fused_delta_pallas(scan_in, join_in, *, interpret: bool):
    """Same contract as kernels/ref.fused_delta_ref: tuples of
    backends.FusedScanIn / FusedJoinIn -> (merged words, merged rids)."""
    scan_in, join_in = tuple(scan_in), tuple(join_in)
    if not scan_in and not join_in:
        return (), ()

    # ---- static geometry + padded inputs -------------------------------
    sgeom = [scan_geometry(e) for e in scan_in]
    jgeom = [join_geometry(e) for e in join_in]
    inputs = []
    for g, e in zip(sgeom, scan_in):
        pad = g.nt * g.R - e.cols.shape[1]
        cols_p = jnp.pad(e.cols, ((0, 0), (0, pad)))
        valid_p = jnp.pad(e.valid.astype(jnp.int32), (0, pad))[None, :]
        inputs += [cols_p, cols_p, valid_p, valid_p,
                   as_query_column(e.lo), as_query_column(e.hi),
                   as_query_column(e.lo_p), as_query_column(e.hi_p)]
    buckets = []
    for g, e in zip(jgeom, join_in):
        kd, b = _route(e.keys, e.rows, e.bounds, g.P)
        buckets.append(b)
        inputs += [kd, *_pad_buckets(e.bkeys, e.brows, g.P)]

    # ---- the flat work descriptor (kind, owner, idx, gather) ----------
    schedule = build_schedule(sgeom, jgeom)
    sdesc = build_sdesc(schedule, sgeom, jgeom,
                        [e.rows for e in scan_in], buckets)
    N = int(schedule.shape[0])

    # ---- block specs: owners address real blocks, others park ---------
    out_specs, out_shapes = make_out_specs(sgeom, jgeom)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(N,),
        in_specs=make_in_specs(sgeom, jgeom), out_specs=out_specs)
    outs = pl.pallas_call(
        functools.partial(_mega_kernel, sgeom=tuple(sgeom),
                          jgeom=tuple(jgeom)),
        grid_spec=grid_spec, out_shape=out_shapes,
        interpret=interpret)(sdesc.reshape(-1), *inputs)

    # ---- XLA epilogue: merge into the carries (no intermediates leave
    # the op; sentinel rows drop in the bounds-checked scatter) ---------
    words = []
    for s, (g, e) in enumerate(zip(sgeom, scan_in)):
        T = e.cols.shape[1]
        pane = jnp.transpose(outs[2 * s], (0, 2, 1))
        pane = jax.lax.bitcast_convert_type(
            pane.reshape((g.nt + 1) * g.R, g.A)[:T], jnp.uint32)
        m = jnp.where(e.span > 0,
                      jax.lax.dynamic_update_slice(e.carry, pane,
                                                   (0, e.w0)),
                      e.carry)
        dwords = jax.lax.bitcast_convert_type(
            outs[2 * s + 1][:g.D, :, 0], jnp.uint32)
        words.append(scatter_dirty_rows(m, e.rows, dwords, T))
    rids = []
    for j, (g, e) in enumerate(zip(jgeom, join_in)):
        rid_d = outs[2 * len(sgeom) + j][:g.D, 0, 0]
        rids.append(scatter_dirty_rows(e.rid_carry, e.rows, rid_d,
                                       e.keys.shape[0]))
    return tuple(words), tuple(rids)


# ---------------------------------------------------------------------------
# Standalone kernels (the chained-fallback surface)
# ---------------------------------------------------------------------------


def _delta_scan_kernel(rows_ref, cols_ref, lo_ref, hi_ref, valid_ref,
                       out_ref, *, n_cols: int, qcap: int):
    lane = rows_ref[pl.program_id(0)] % DIRTY_LANES
    out_ref[...] = _row_words(cols_ref, valid_ref, lane, lo_ref, hi_ref,
                              n_cols, qcap)[None]


def delta_scan_pallas(cols, lo, hi, valid, rows, *, interpret: bool):
    """Dirty-row delta scan (contract: kernels/ref.delta_scan_ref).

    grid = (D,), one program per dirty-row slot; the BlockSpec index_map
    reads the scalar-prefetched row id to DMA the lane block of cols
    holding that row.  Work is O(D * C * Q) — independent of the table
    size.  This is the fused kernel's DIRTY program as a standalone call
    (the chained ``OperatorBackend.scan_delta`` fallback).
    """
    C, T = cols.shape
    Q = lo.shape[1]
    D = rows.shape[0]
    if Q % 32:
        raise ValueError(
            f"delta scan window width {Q} is not a multiple of 32")
    W = Q // 32
    Tp = round_up(T, DIRTY_LANES)
    cols = jnp.pad(cols, ((0, 0), (0, Tp - T)))
    valid = jnp.pad(valid.astype(jnp.int32), (0, Tp - T))[None, :]
    rows = jnp.clip(rows, 0, T - 1).astype(jnp.int32)  # pad slots clamp

    def lanes(i, rows_ref):
        return (0, rows_ref[i] // DIRTY_LANES)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(D,),
        in_specs=[
            # the scalar-prefetch gather: rows[i] picks the cols block
            pl.BlockSpec((C, DIRTY_LANES), lanes),
            pl.BlockSpec((C, Q, 1), lambda i, rows_ref: (0, 0, 0)),
            pl.BlockSpec((C, Q, 1), lambda i, rows_ref: (0, 0, 0)),
            pl.BlockSpec((1, DIRTY_LANES), lanes),
        ],
        out_specs=pl.BlockSpec((1, W, 1), lambda i, rows_ref: (i, 0, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_delta_scan_kernel, n_cols=C, qcap=Q),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((D, W, 1), jnp.int32),
        interpret=interpret,
    )(rows, cols, as_query_column(lo), as_query_column(hi), valid)
    return jax.lax.bitcast_convert_type(out[:, :, 0], jnp.uint32)


def _delta_join_kernel(bidx_ref, kd_ref, bkeys_ref, brows_ref, rid_ref, *,
                       n_rows: int):
    i = pl.program_id(0)
    rid_ref[...] = _probe_rid(bkeys_ref, brows_ref, bidx_ref[i] % n_rows,
                              kd_ref[i])[None]


def delta_join_pallas(keys_l, rows, bucket_keys, bucket_rows, bounds, *,
                      interpret: bool):
    """Dirty-spine-row partitioned probe (contract:
    kernels/ref.delta_join_ref).

    grid = (D,), one program per dirty-row slot; the ``searchsorted``
    bucket routing runs in XLA outside (it needs the key VALUE, which no
    BlockSpec index_map can see), the index_map DMAs the block holding
    the routed bucket and the kernel probes that ONE bucket pane against
    the row's key, read from SMEM.  Work is O(D * B) — independent of
    the spine size.  This is the fused kernel's PROBE program as a
    standalone call (the chained ``OperatorBackend.join_delta``
    fallback).
    """
    P, B = bucket_keys.shape
    D = rows.shape[0]
    PB = probe_rows(P)
    kd, b = _route(keys_l, rows, bounds, P)
    bkeys, brows = _pad_buckets(bucket_keys, bucket_rows, P)

    def bucket(i, bidx_ref):
        return (bidx_ref[i] // PB, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(D,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            # the scalar-prefetch gather: bidx[i] picks the bucket block
            pl.BlockSpec((PB, B), bucket),
            pl.BlockSpec((PB, B), bucket),
        ],
        out_specs=pl.BlockSpec((1, 1, 1), lambda i, bidx_ref: (i, 0, 0)),
    )
    rid = pl.pallas_call(
        functools.partial(_delta_join_kernel, n_rows=PB),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((D, 1, 1), jnp.int32),
        interpret=interpret,
    )(b, kd, bkeys, brows)
    return rid[:, 0, 0]
