"""Shared block join kernel: key-equality outer compare (the paper's
shared join, §3.3) for small index-less PK tables.

  grid = (Tl // TILE_L, Tr // TILE_R)   (right tiles innermost —
                                         sequential reduction)
  blocks: keys_l [1, TILE_L]            left keys on the lanes
          keys_r [TILE_R, 1]            right keys on the sublanes
          valid_r [TILE_R, 1]           int32 0/1
  out:    rid    [1, TILE_L]            matched right row + 1 (0 = none)

Each program compares one right tile against one left tile and keeps the
largest matching right row by a sublane max, so duplicates resolve to the
max row id exactly as the oracle does.  With unique right keys the
matched row alone determines the join, so the query-set intersection
``mask_l & mask_r[rid]`` — the paper's amended ``R.query_id =
S.query_id`` join predicate — is one O(Tl) gather after the kernel,
shared with the partitioned join.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.clockscan import LANES, round_up

TILE_L = 512
TILE_R = 512
SUBLANES = 8


def _kernel(keys_l_ref, keys_r_ref, valid_r_ref, rid_ref, *, tile_r: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        rid_ref[...] = jnp.zeros_like(rid_ref)

    eq = (keys_r_ref[...] == keys_l_ref[...]) & (valid_r_ref[...] != 0)
    rows = (jax.lax.broadcasted_iota(jnp.int32, (eq.shape[0], 1), 0)
            + j * tile_r + 1)                                # [Tr, 1]
    cand = jnp.max(jnp.where(eq, rows, 0), axis=0, keepdims=True)
    rid_ref[...] = jnp.maximum(rid_ref[...], cand)


def intersect_matched(rid, mask_l, mask_r):
    """mask_l & mask_r[rid] where rid matched (-1 = no match -> empty)."""
    safe = jnp.clip(rid, 0, mask_r.shape[0] - 1)
    return jnp.where((rid >= 0)[:, None], mask_l & mask_r[safe],
                     jnp.uint32(0))


def bitmask_join_pallas(keys_l, mask_l, keys_r, mask_r, valid_r, *,
                        interpret: bool):
    """Same contract as kernels/ref.bitmask_join_ref."""
    Tl = keys_l.shape[0]
    Tr = keys_r.shape[0]
    tl = min(TILE_L, round_up(Tl, LANES))
    tr = min(TILE_R, round_up(Tr, SUBLANES))
    Tlp, Trp = round_up(Tl, tl), round_up(Tr, tr)
    # arbitrary table capacities: padded right rows are invalid so they
    # never match; padded left rows are sliced off
    kl = jnp.pad(keys_l.astype(jnp.int32), (0, Tlp - Tl))[None, :]
    kr = jnp.pad(keys_r.astype(jnp.int32), (0, Trp - Tr))[:, None]
    vr = jnp.pad(valid_r.astype(jnp.int32), (0, Trp - Tr))[:, None]
    rid = pl.pallas_call(
        functools.partial(_kernel, tile_r=tr),
        grid=(Tlp // tl, Trp // tr),
        in_specs=[
            pl.BlockSpec((1, tl), lambda i, j: (0, i)),
            pl.BlockSpec((tr, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((tr, 1), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((1, tl), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, Tlp), jnp.int32),
        interpret=interpret,
    )(kl, kr, vr)
    rid = rid[0, :Tl] - 1
    return rid, intersect_matched(rid, mask_l, mask_r)
