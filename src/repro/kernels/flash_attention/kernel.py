"""Flash attention Pallas TPU kernel (prefill / training forward).

Grid (B, H, nq, nk) with the kv-block dimension innermost/sequential;
running max / denominator / accumulator live in VMEM scratch and persist
across kv blocks (the standard TPU flash pattern).  Supports causal
masking, sliding windows, logit soft-capping and GQA (kv head = h // g).

The kernel takes q as ``[B, Sp, H*hd]`` and k/v as ``[B, Sp, KV*hd]``
(ops.py merges the head axes): a block is then one head's ``(bq, hd)``
column tile, whose last two dims satisfy the TPU's (8, 128) tiling rule.
On TPU the merge is a relayout copy, because the tiled layout of
``[.., H, hd]`` tiles the head axis.  hd is padded to a multiple of 128 by ops.py so the MXU
matmul dims stay hardware-aligned, and bq/bk default to 128/512 to keep
the working set (bq*hd + 2*bk*hd + bq*bk floats) inside VMEM (~16 MB/core
on v5e).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                 scale: float, causal: bool, window: Optional[int],
                 cap: Optional[float], bq: int, bk: int, nk: int,
                 seq_len: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[...].astype(jnp.float32)                 # [bq, hd]
    k = k_ref[...].astype(jnp.float32)                 # [bk, hd]
    v = v_ref[...].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
    if cap is not None:
        s = cap * jnp.tanh(s / cap)

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    ok = k_pos < seq_len                                # padding mask
    if causal:
        ok &= q_pos >= k_pos
    if window is not None:
        ok &= (q_pos - k_pos) < window
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot(p, v)
    m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[...] = (
            acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        ).astype(o_ref.dtype)


def flash_attention_kernel(q, k, v, *, n_heads: int, causal: bool = True,
                           window: Optional[int] = None,
                           cap: Optional[float] = None,
                           bq: int = 128, bk: int = 512,
                           seq_len: Optional[int] = None,
                           interpret: bool = True):
    """q: [B, Sp, H*hd], k/v: [B, Sp, KV*hd] — Sp pre-padded to block
    multiples, hd padded to 128, by ops.py.  seq_len = true length."""
    B, Sq, Dq = q.shape
    Sk = k.shape[1]
    hd = Dq // n_heads
    g = n_heads // (k.shape[2] // hd)
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    assert Sq % bq == 0 and Sk % bk == 0
    nq, nk = Sq // bq, Sk // bk
    seq_len = seq_len or Sk

    kernel = functools.partial(
        _attn_kernel, scale=1.0 / float(hd) ** 0.5, causal=causal,
        window=window, cap=cap, bq=bq, bk=bk, nk=nk, seq_len=seq_len)

    return pl.pallas_call(
        kernel,
        grid=(B, n_heads, nq, nk),
        in_specs=[
            pl.BlockSpec((None, bq, hd), lambda b, h, i, j: (b, i, h)),
            pl.BlockSpec((None, bk, hd), lambda b, h, i, j: (b, j, h // g)),
            pl.BlockSpec((None, bk, hd), lambda b, h, i, j: (b, j, h // g)),
        ],
        out_specs=pl.BlockSpec((None, bq, hd),
                               lambda b, h, i, j: (b, i, h)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),    # running max
            pltpu.VMEM((bq, 1), jnp.float32),    # running denominator
            pltpu.VMEM((bq, hd), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
    )(q, k, v)
