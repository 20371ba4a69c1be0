"""Shared relational operators over the data-query model (paper §3.3-3.4).

Every operator processes the UNION of tuples needed by all concurrent
queries exactly once, carrying the packed query bitmask.  Worst-case work is
a function of table capacity only — never of the number of queries — which
is the bounded-computation property behind the paper's SLA guarantees.

The hot loops (shared scan, block/partitioned join, group-by) resolve
through core/backends.py (Pallas TPU kernels or their kernels/ref.py
oracles); the operators here lower straight to XLA and are shared by
both backends.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core import dataquery as dq

INT_MIN = -2147483647
INT_MAX = 2147483647


# ---------------------------------------------------------------------------
# Shared join — one big join; query-set intersection == query_id predicate
# ---------------------------------------------------------------------------


def shared_join_fk(fk, left_mask, pk_index, right_mask):
    """PK-FK shared join (the paper's >< with query_id in the predicate).

    fk:         int32[T_l] foreign key of the left (spine) relation
    left_mask:  uint32[T_l, W]
    pk_index:   int32[K]  dense key -> right row (-1 absent)
    right_mask: uint32[T_r, W]
    Returns (right_row int32[T_l]  (-1 = no match),
             combined mask uint32[T_l, W] = left & right[match]).
    """
    K = pk_index.shape[0]
    safe_fk = jnp.clip(fk, 0, K - 1)
    r = jnp.where((fk >= 0) & (fk < K), pk_index[safe_fk], -1)
    gathered = right_mask[jnp.clip(r, 0, right_mask.shape[0] - 1)]
    combined = jnp.where((r >= 0)[:, None], left_mask & gathered,
                         jnp.uint32(0))
    return r, combined


# ---------------------------------------------------------------------------
# Union compression: extract the tuples at least one query wants.
#
# The paper's shared operators process "the union of all R and S tuples that
# the queries are interested in" (Fig. 3/4) — NOT the whole table.  The
# union is extracted with a BOUNDED capacity (bounded computation, §3.5):
# per-cycle work stays a static function of the cap; overflow beyond the
# cap is reported, never silently mis-answered (rows past the cap are
# dropped deterministically from the tail).
# ---------------------------------------------------------------------------


def compress_union(mask, cap: int):
    """Returns (row_idx int32[cap] (-1 pad), cmask uint32[cap, W],
    n_wanted int32 — observability: n_wanted > cap means overflow)."""
    T = mask.shape[0]
    wanted = dq.any_query(mask)
    n_wanted = jnp.sum(wanted.astype(jnp.int32))
    idx = jnp.nonzero(wanted, size=cap, fill_value=T)[0]
    safe = jnp.minimum(idx, T - 1).astype(jnp.int32)
    live = idx < T
    cmask = jnp.where(live[:, None], mask[safe], jnp.uint32(0))
    rows = jnp.where(live, safe, -1).astype(jnp.int32)
    return rows, cmask, n_wanted


# ---------------------------------------------------------------------------
# Result routing (the paper's Gamma operator): top-R row ids per query
# ---------------------------------------------------------------------------


def route_topn(mask_in_order, n_per_query, max_results: int, rows=None):
    """Fused shared Top-N + result routing: ONE unpack + cumsum pass.

    mask_in_order: uint32[K, W] in output order (typically the compressed
    union, post-sort); rows: int32[K] storage row ids (-1 invalid; default
    the positional index); n_per_query: int32[W*32].
    Returns int32[Q, max_results] row ids (-1 padded).
    """
    K, W = mask_in_order.shape
    Q = W * dq.WORD
    bits = dq.unpack(mask_in_order)                  # [K, Q]
    if rows is None:
        rows = jnp.arange(K, dtype=jnp.int32)
    bits &= (rows >= 0)[:, None]
    rank = jnp.cumsum(bits.astype(jnp.int32), axis=0) - 1
    keep = bits & (rank < jnp.minimum(n_per_query, max_results)[None, :])
    # at most Q*max_results entries survive: compress before scattering
    # (scatters are serial-ish on CPU; keep them tiny)
    flat = jnp.nonzero(keep.reshape(-1), size=Q * max_results,
                       fill_value=K * Q)[0]
    safe = jnp.minimum(flat, K * Q - 1)
    live = flat < K * Q
    k_idx = safe // Q
    q_idx = jnp.where(live, safe % Q, Q)
    slot = jnp.where(live, rank.reshape(-1)[safe], max_results)
    out = jnp.full((Q, max_results), -1, jnp.int32)
    out = out.at[q_idx, slot].set(rows[k_idx], mode="drop")
    return out
