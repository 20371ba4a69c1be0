"""Shared group-by kernel: aggregation as an MXU contraction.

Phase 1 of the paper's shared group-by (§3.4) — grouping the union of all
queries' tuples — becomes, per (group-tile, row-tile) and per bitmask
word ``a``:

  count[32a:32a+32, G_t] += unpack(mask_a)            @ onehot(group)
  sum  [32a:32a+32, G_t] += (unpack(mask_a) * value) @ onehot(group)

i.e. "all queries x all groups" aggregation is two dense f32 matmuls per
word and tile — exactly what the MXU is built for.  Rows sit on the
lanes of every operand (mask words arrive transposed, [W, T]), so each
product contracts the lane axis of both sides; the accumulators are
query-major [Q, G] and the wrapper transposes them back.  Row tiles are
the inner (sequential) grid dim so accumulation stays in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.clockscan import LANES, round_up

TILE_T = 512
TILE_G = 256

_CONTRACT_ROWS = (((1,), (1,)), ((), ()))


def _kernel(group_ref, value_ref, mask_ref, count_ref, sum_ref, *,
            n_words: int, tile_g: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        count_ref[...] = jnp.zeros_like(count_ref)
        sum_ref[...] = jnp.zeros_like(sum_ref)

    codes = group_ref[...]                                   # [1, Tt]
    g = (jax.lax.broadcasted_iota(jnp.int32, (tile_g, codes.shape[1]), 0)
         + pl.program_id(0) * tile_g)
    onehot = jnp.where(g == codes, 1.0, 0.0).astype(jnp.float32)  # [Gt, Tt]
    vals = value_ref[...].astype(jnp.float32)                # [1, Tt]
    shifts = jax.lax.broadcasted_iota(jnp.int32, (32, 1), 0)
    for a in range(n_words):
        bits = jnp.bitwise_and(
            jnp.right_shift(mask_ref[a:a + 1, :], shifts), 1)
        bits = bits.astype(jnp.float32)                      # [32, Tt]
        rows = pl.ds(32 * a, 32)
        count_ref[rows, :] += jax.lax.dot_general(
            bits, onehot, _CONTRACT_ROWS,
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        sum_ref[rows, :] += jax.lax.dot_general(
            bits * vals, onehot, _CONTRACT_ROWS,
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)


def shared_groupby_pallas(group_code, values, mask, n_groups: int, *,
                          interpret: bool):
    """codes int32[T]; values int32[T]; mask uint32[T, W] ->
    (count f32[G, Q], sum f32[G, Q]) — contract: ref.shared_groupby_ref."""
    T, W = mask.shape
    Q = W * 32
    tt = min(TILE_T, round_up(T, LANES))
    Tp = round_up(T, tt)
    # arbitrary row counts: padded rows carry empty masks
    codes = jnp.pad(group_code.astype(jnp.int32), (0, Tp - T))[None, :]
    vals = jnp.pad(values.astype(jnp.int32), (0, Tp - T))[None, :]
    mask_t = jnp.pad(jax.lax.bitcast_convert_type(mask, jnp.int32).T,
                     ((0, 0), (0, Tp - T)))                  # [W, Tp]
    tg = min(TILE_G, round_up(n_groups, LANES))
    Gp = round_up(n_groups, tg)                              # pad groups
    count, ssum = pl.pallas_call(
        functools.partial(_kernel, n_words=W, tile_g=tg),
        grid=(Gp // tg, Tp // tt),
        in_specs=[
            pl.BlockSpec((1, tt), lambda i, j: (0, j)),
            pl.BlockSpec((1, tt), lambda i, j: (0, j)),
            pl.BlockSpec((W, tt), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((Q, tg), lambda i, j: (0, i)),
            pl.BlockSpec((Q, tg), lambda i, j: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Q, Gp), jnp.float32),
            jax.ShapeDtypeStruct((Q, Gp), jnp.float32),
        ],
        interpret=interpret,
    )(codes, vals, mask_t)
    return count.T[:n_groups], ssum.T[:n_groups]
