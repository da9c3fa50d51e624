"""Flash-decode Pallas kernel: one query token vs a (possibly ring-buffer)
KV cache, K-blocked online softmax with an explicit slot-validity mask
(the caller derives it from ring positions / causal window).

Grid (B, KV, nk); kv blocks iterate innermost with running stats in VMEM
scratch.  Each step serves one GQA group: the g query heads that share
kv head ``kv`` are scored together against a ``(bk, hd)`` key tile.

Layouts keep every block's last two dims on the TPU's (8, 128) tiling
rule (or whole): q is ``[B, KV, g, hd]`` (block ``(g, hd)``, the whole
trailing dims), k/v are ``[B, L, KV*hd]`` (block ``(bk, hd)``, one kv
head's column tile) and the bias is ``[B, 1, L]`` (block ``(1, bk)``).
ops.py produces them by reshaping; on TPU the k/v merge is a relayout
copy of the cache, because the tiled layout of ``[.., KV, hd]`` tiles the
head axis.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, scale: float,
                   cap: Optional[float], nk: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[...].astype(jnp.float32)                 # [g, hd]
    k = k_ref[...].astype(jnp.float32)                 # [bk, hd]
    v = v_ref[...].astype(jnp.float32)
    bias = bias_ref[...].astype(jnp.float32)           # [1, bk] additive

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
    if cap is not None:
        s = cap * jnp.tanh(s / cap)
    s = s + bias                                       # [g, bk]

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


def decode_attention_kernel(q, k, v, bias, *, cap: Optional[float] = None,
                            bk: int = 512, interpret: bool = True):
    """q: [B, KV, g, hd]; k/v: [B, L, KV*hd]; bias: [B, 1, L] additive
    (0 = attend, NEG_INF = masked).  Returns [B, KV, g, hd]."""
    B, KV, g, hd = q.shape
    L = k.shape[1]
    bk = min(bk, L)
    assert L % bk == 0
    nk = L // bk
    kernel = functools.partial(_decode_kernel,
                               scale=1.0 / float(hd) ** 0.5,
                               cap=cap, nk=nk)
    return pl.pallas_call(
        kernel,
        grid=(B, KV, nk),
        in_specs=[
            pl.BlockSpec((None, None, g, hd), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((None, bk, hd), lambda b, h, j: (b, j, h)),
            pl.BlockSpec((None, bk, hd), lambda b, h, j: (b, j, h)),
            pl.BlockSpec((None, 1, bk), lambda b, h, j: (b, 0, j)),
        ],
        out_specs=pl.BlockSpec((None, None, g, hd),
                               lambda b, h, j: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, bias)
