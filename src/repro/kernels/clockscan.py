"""ClockScan shared-scan kernel: evaluate ALL queries against a tuple tile.

The paper's storage layer (Crescando [28]) "indexes the queries, not the
data" and joins query predicates against tuples in one clock pass.  On TPU
this becomes a query-data outer comparison per tuple tile, with table rows
on the 128 lanes (the columnar storage layout as it stands) and queries on
the sublanes:

  grid            = (Tp // TILE_T,)
  cols block      = [C, TILE_T]   (VMEM; C = predicated columns, small)
  lo/hi blocks    = [C, Q, 1]     (whole predicate matrix resident in VMEM,
                                   one query per sublane — queries ARE the
                                   indexed side)
  valid block     = [1, TILE_T]   (int32 0/1)
  out block       = [W, TILE_T]   packed words, transposed to [T, W] by
                                   the wrapper

Per tile: broadcast compare (VPU), AND-reduce over columns, then pack 32
query sublanes per word with an int32 weighted sum.  Work per tile is
O(C * TILE_T * Q) independent of selectivity or query count <= Q —
bounded computation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE_T = 1024
LANES = 128


def round_up(x: int, m: int) -> int:
    return -(-max(x, 1) // m) * m


def pack_words(ok):
    """bool[32*w, N] (one query per sublane) -> int32[w, N]: bit b of
    word a is query 32*a + b.  Distinct powers of two never carry, so
    the int32 sum is the bitwise OR (bit 31 lands on the sign bit)."""
    q = jax.lax.broadcasted_iota(jnp.int32, (ok.shape[0], 1), 0)
    v = jnp.where(ok, jnp.left_shift(jnp.int32(1), q % 32), 0)
    return jnp.sum(v.reshape(ok.shape[0] // 32, 32, ok.shape[1]), axis=1)


def match_ranges(ok, xs, lo_ref, hi_ref):
    """AND every predicated column's inclusive range test into ``ok``
    (bool[Q, N]): ``xs[c]`` is int32[1, N] (rows on lanes), ``lo_ref`` /
    ``hi_ref`` are [C, Q, 1] refs (queries on sublanes)."""
    for c, x in enumerate(xs):
        ok = ok & (x >= lo_ref[c]) & (x <= hi_ref[c])
    return ok


def as_query_column(bounds):
    """int32[C, Q] predicate bounds -> [C, Q, 1] (query-per-sublane)."""
    return bounds[:, :, None]


def words_to_rows(words_t):
    """int32[W, T] kernel words -> uint32[T, W] (the ``dq.pack`` layout)."""
    return jax.lax.bitcast_convert_type(words_t.T, jnp.uint32)


def _kernel(cols_ref, lo_ref, hi_ref, valid_ref, out_ref, *, n_cols: int,
            qcap: int):
    ok = jnp.broadcast_to(valid_ref[...] != 0, (qcap, valid_ref.shape[1]))
    xs = [cols_ref[c:c + 1, :] for c in range(n_cols)]
    out_ref[...] = pack_words(match_ranges(ok, xs, lo_ref, hi_ref))


def clockscan_pallas(cols, lo, hi, valid, *, interpret: bool):
    """cols int32[C,T]; lo/hi int32[C,Q]; valid bool[T] -> uint32[T,Q/32]."""
    C, T = cols.shape
    Q = lo.shape[1]
    if Q % 32:
        raise ValueError(f"scan window width {Q} is not a multiple of 32")
    W = Q // 32
    tile = min(TILE_T, round_up(T, LANES))
    Tp = round_up(T, tile)
    # arbitrary table capacities: pad rows (invalid -> all-zero words)
    cols = jnp.pad(cols, ((0, 0), (0, Tp - T)))
    valid = jnp.pad(valid.astype(jnp.int32), (0, Tp - T))[None, :]
    out = pl.pallas_call(
        functools.partial(_kernel, n_cols=C, qcap=Q),
        grid=(Tp // tile,),
        in_specs=[
            pl.BlockSpec((C, tile), lambda i: (0, i)),
            pl.BlockSpec((C, Q, 1), lambda i: (0, 0, 0)),
            pl.BlockSpec((C, Q, 1), lambda i: (0, 0, 0)),
            pl.BlockSpec((1, tile), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((W, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((W, Tp), jnp.int32),
        interpret=interpret,
    )(cols, as_query_column(lo), as_query_column(hi), valid)
    return words_to_rows(out)[:T]
