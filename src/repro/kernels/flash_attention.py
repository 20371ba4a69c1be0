"""Flash attention (causal / sliding-window, GQA) — the LM serving hot-spot.

  grid = (B * H, Sq // BLOCK_Q, Sk // BLOCK_K)   (k blocks innermost)
  q block  [BLOCK_Q, D] VMEM; k/v blocks [BLOCK_K, D] VMEM
  online-softmax running (m, l, acc) kept in VMEM scratch across k blocks;
  finalized on the last k block.

GQA is handled by mapping head h to kv head h // (H // KV) in the k/v
index_map, so the repeated KV never materializes in HBM.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_Q = 128
BLOCK_K = 128
NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, window: int, block_q: int,
            block_k: int, n_k: int, sq: int, sk: int):
    iq = pl.program_id(1)
    jk = pl.program_id(2)

    @pl.when(jk == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                 # [bq, D]
    k = k_ref[0].astype(jnp.float32)                 # [bk, D]
    s = jnp.einsum("qd,kd->qk", q, k) * scale

    qpos = iq * block_q + jnp.arange(block_q) + (sk - sq)
    kpos = jk * block_k + jnp.arange(block_k)
    ok = jnp.ones((block_q, block_k), bool)
    if causal:
        ok &= qpos[:, None] >= kpos[None, :]
    if window > 0:
        ok &= qpos[:, None] - kpos[None, :] < window
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1)
    acc_scr[...] = acc_scr[...] * corr[:, None] + jnp.einsum(
        "qk,kd->qd", p, v_ref[0].astype(jnp.float32))
    m_scr[...] = m_new

    @pl.when(jk == n_k - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, interpret: bool, causal: bool = True,
                           window: int = 0,
                           block_q: int = BLOCK_Q, block_k: int = BLOCK_K):
    """q: [B, Sq, H, D]; k, v: [B, Sk, KV, D] -> [B, Sq, H, D]."""
    B, Sq, H, D = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    g = H // KV
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    assert Sq % bq == 0 and Sk % bk == 0
    scale = 1.0 / math.sqrt(D)

    # flatten (B, H) into the leading grid dim
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * KV, Sk, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * KV, Sk, D)

    def kv_map(bh, i, j):
        # bh = b * H + h  ->  b * KV + h // g
        return (bh // H) * KV + (bh % H) // g, j, 0

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window, block_q=bq,
        block_k=bk, n_k=Sk // bk, sq=Sq, sk=Sk)
    of = pl.pallas_call(
        kernel,
        grid=(B * H, Sq // bq, Sk // bk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, bk, D), kv_map),
            pl.BlockSpec((1, bk, D), kv_map),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return of.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)
