"""Partitioned shared join kernel: probe fixed-capacity range buckets
instead of the whole right side (the O(Tl*Tr) -> O(Tl*Tr/P) upgrade of
kernels/bitmask_join.py for index-less PK tables).

The right side is pre-partitioned once per heartbeat at update-apply time
(storage.build_key_partitions): valid rows sorted by key, split into P
contiguous buckets of exactly B = bucket_cap entries — a range radix on
the sorted key order, so no bucket can overflow and the join stays exact
for any key distribution.  The probe has two parts:

  1. bucket routing + gather (XLA): each left key finds its ONE candidate
     bucket via searchsorted over the P bucket bounds, and that bucket's
     keys/rows are gathered to [B, Tl] candidate panes (left rows on the
     lanes) — TPU-native dynamic slicing.
  2. the match reduction (THIS kernel): grid over (left-tile, bucket
     chunk); each program compares a left tile against one chunk of its
     rows' candidate panes and accumulates the matched right row id by a
     sublane max — identical accumulation to bitmask_join's right-tile
     loop, but over B candidates per row instead of Tr.

The bitmask intersection (mask_l & mask_r[rid] — the paper's amended
``R.query_id = S.query_id`` join predicate) is a single O(Tl) gather once
rid is known, shared by both backends.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.bitmask_join import SUBLANES, intersect_matched
from repro.kernels.clockscan import LANES, round_up

TILE_L = 512
TILE_B = 512


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
    b = jnp.searchsorted(bounds, keys_l, side="right").astype(jnp.int32) - 1
    b = jnp.clip(b, 0, P - 1)
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
