"""Partitioned shared join kernel: probe fixed-capacity range buckets
instead of the whole right side (the O(Tl*Tr) -> O(Tl*Tr/P) upgrade of
kernels/bitmask_join.py for index-less PK tables).

The right side is pre-partitioned once per heartbeat at update-apply time
(storage.build_key_partitions): valid rows sorted by key, split into P
contiguous buckets of exactly B = bucket_cap entries — a range radix on
the sorted key order, so no bucket can overflow and the join stays exact
for any key distribution.  The probe has three parts:

  1. bucket routing (``route_buckets``): each left key's ONE candidate
     bucket is b = clip(#{p : bounds[p] <= k} - 1, 0, P-1), the last
     bucket whose bound <= k.  The shape rule picks how, by the static P:
     up to ``COUNT_ROUTE_MAX_P`` buckets a compare-count kernel
     (``_route_kernel``: left keys on the lanes, the bounds on the
     sublanes, an int32 count summed over bound chunks — Tl*P compares,
     no gather); above it ``jnp.searchsorted``, whose log2(P) rounds each
     gather Tl bounds.  Both give the same b for every int32 key.
  2. candidate gather (XLA): that bucket's keys/rows are gathered to
     [B, Tl] candidate panes (left rows on the lanes) — TPU-native
     dynamic slicing.
  3. the match reduction (``_kernel``): grid over (left-tile, bucket
     chunk); each program compares a left tile against one chunk of its
     rows' candidate panes and accumulates the matched right row id by a
     sublane max — identical accumulation to bitmask_join's right-tile
     loop, but over B candidates per row instead of Tr.

The bitmask intersection (mask_l & mask_r[rid] — the paper's amended
``R.query_id = S.query_id`` join predicate) is a single O(Tl) gather once
rid is known, shared by both backends.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.bitmask_join import SUBLANES, intersect_matched
from repro.kernels.clockscan import LANES, round_up

TILE_L = 512
TILE_B = 512
#: routing tiles: left keys per program (lanes), bounds per grid chunk
ROUTE_TILE_L = 1024
ROUTE_CHUNK = 2048
ROUTE_UNROLL = 8          # sublane groups per loop step
#: the shape rule of ``route_buckets``: the compare-count up to this many
#: buckets, the search above.  Both grow linearly in the key count; the
#: count grows as P, the search as log2(P) gather rounds.  At 1,166,400
#: keys on a TPU v5e (PERF.md) the count took 1.22 ms at P = 1,519
#: against the search's 85.7 ms, 86.2 against 140.1 ms at 131,072, 129.1
#: against 140.0 at 196,608 and 221.3 against 147.8 at 262,144.
COUNT_ROUTE_MAX_P = 131_072


def join_route(n_buckets: int) -> str:
    """The routing ``route_buckets`` takes over ``n_buckets`` bounds."""
    return "count" if n_buckets <= COUNT_ROUTE_MAX_P else "search"


def _route_kernel(bounds_ref, keys_ref, cnt_ref, *, n_bounds: int,
                  chunk: int):
    """cnt[k] += #{p in this chunk, p < n_bounds : bounds[p] <= k}.

    The bounds arrive lane-replicated ([chunk, 128]), so one load gives
    eight bounds, one per sublane, against every lane; each 128-lane slab
    of the key tile keeps its own [8, 128] int32 count, summed by vector
    adds and reduced over the sublanes once per chunk.  Rows past
    ``n_bounds`` (the chunk padding) are excluded by their row index,
    never by their value, so every int32 key counts exactly the real
    bounds at or below it."""
    j = pl.program_id(1)
    last = (n_bounds - 1) // chunk
    slabs = [jnp.broadcast_to(keys_ref[:, s:s + LANES], (SUBLANES, LANES))
             for s in range(0, keys_ref.shape[1], LANES)]

    @pl.when(j == 0)
    def _init():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    def add(acc, r, real=None):         # the sublane group at row r
        le = [bounds_ref[pl.ds(r, SUBLANES), :] <= k for k in slabs]
        if real is not None:
            le = [x & real for x in le]
        return tuple(a + x.astype(jnp.int32) for a, x in zip(acc, le))

    def count(n_rows: int):
        full, tail = divmod(n_rows, SUBLANES)
        steps, rest = divmod(full, ROUTE_UNROLL)

        def step(i, acc):
            r = pl.multiple_of(i * ROUTE_UNROLL * SUBLANES, SUBLANES)
            for u in range(ROUTE_UNROLL):
                acc = add(acc, r + u * SUBLANES)
            return acc

        acc = jax.lax.fori_loop(
            0, steps, step,
            tuple(jnp.zeros((SUBLANES, LANES), jnp.int32) for _ in slabs))
        for g in range(steps * ROUTE_UNROLL, full):
            acc = add(acc, g * SUBLANES)
        if tail:
            acc = add(acc, full * SUBLANES, jax.lax.broadcasted_iota(
                jnp.int32, (SUBLANES, LANES), 0) < tail)
        cnt_ref[...] += jnp.concatenate(
            [jnp.sum(a, axis=0, keepdims=True) for a in acc], axis=1)

    if last:
        pl.when(j < last)(lambda: count(chunk))
    pl.when(j == last)(lambda: count(n_bounds - last * chunk))


def route_count_pallas(keys, bounds, *, interpret: bool):
    """#{p : bounds[p] <= k} for each key k, as int32[Tl]."""
    P, Tl = bounds.shape[0], keys.shape[0]
    tl = min(ROUTE_TILE_L, round_up(Tl, LANES))
    pc = min(ROUTE_CHUNK, round_up(P, SUBLANES))
    Tlp, Pp = round_up(Tl, tl), round_up(P, pc)
    kp = jnp.pad(keys.astype(jnp.int32), (0, Tlp - Tl))
    bp = jnp.pad(bounds.astype(jnp.int32), (0, Pp - P))
    cnt = pl.pallas_call(
        functools.partial(_route_kernel, n_bounds=P, chunk=pc),
        grid=(Tlp // tl, Pp // pc),
        in_specs=[pl.BlockSpec((pc, LANES), lambda i, j: (j, 0)),
                  pl.BlockSpec((1, tl), lambda i, j: (0, i))],
        out_specs=pl.BlockSpec((1, tl), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, Tlp), jnp.int32),
        interpret=interpret,
    )(jnp.broadcast_to(bp[:, None], (Pp, LANES)), kp[None, :])
    return cnt[0, :Tl]


def route_buckets(keys, bounds, *, interpret: bool):
    """Each key's candidate bucket, clip(#{p : bounds[p] <= k} - 1, 0,
    P-1) — by the compare-count kernel or the search, per ``join_route``
    of the static P.  ``bounds`` must be ascending."""
    P = bounds.shape[0]
    if join_route(P) == "count":
        n = route_count_pallas(keys, bounds, interpret=interpret)
    else:
        n = jnp.searchsorted(bounds, keys, side="right").astype(jnp.int32)
    return jnp.clip(n - 1, 0, P - 1)


def _kernel(keys_l_ref, cand_keys_ref, cand_rows_ref, rid_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        rid_ref[...] = jnp.full_like(rid_ref, -1)

    rows = cand_rows_ref[...]                                # [Bt, Tl]
    hit = (cand_keys_ref[...] == keys_l_ref[...]) & (rows >= 0)
    cand = jnp.max(jnp.where(hit, rows, -1), axis=0, keepdims=True)
    rid_ref[...] = jnp.maximum(rid_ref[...], cand)


def partitioned_join_pallas(keys_l, mask_l, bucket_keys, bucket_rows,
                            bounds, mask_r, *, interpret: bool):
    """Same contract as kernels/ref.partitioned_join_ref."""
    P, B = bucket_keys.shape
    Tl = keys_l.shape[0]
    tl = min(TILE_L, round_up(Tl, LANES))
    tb = min(TILE_B, round_up(B, SUBLANES))
    Tlp, Bp = round_up(Tl, tl), round_up(B, tb)
    b = route_buckets(keys_l, bounds, interpret=interpret)
    # pad to tile multiples BEFORE the gather, so the big candidate panes
    # are materialized once: padded candidates carry row -1 (never a
    # hit), padded left rows are sliced off
    kl = jnp.pad(keys_l.astype(jnp.int32), (0, Tlp - Tl))
    b = jnp.pad(b, (0, Tlp - Tl))
    bk = jnp.pad(bucket_keys, ((0, 0), (0, Bp - B))).T       # [Bp, P]
    br = jnp.pad(bucket_rows, ((0, 0), (0, Bp - B)),
                 constant_values=-1).T
    cand_keys = jnp.take(bk, b, axis=1)                      # [Bp, Tlp]
    cand_rows = jnp.take(br, b, axis=1)
    rid = pl.pallas_call(
        _kernel,
        grid=(Tlp // tl, Bp // tb),
        in_specs=[
            pl.BlockSpec((1, tl), lambda i, j: (0, i)),
            pl.BlockSpec((tb, tl), lambda i, j: (j, i)),
            pl.BlockSpec((tb, tl), lambda i, j: (j, i)),
        ],
        out_specs=pl.BlockSpec((1, tl), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, Tlp), jnp.int32),
        interpret=interpret,
    )(kl[None, :], cand_keys, cand_rows)
    rid = rid[0, :Tl]
    return rid, intersect_matched(rid, mask_l, mask_r)
