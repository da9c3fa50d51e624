"""Jitted wrapper for flash-decode (pads hd -> 128, L -> block multiple)."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.backend import resolve_interpret
from repro.kernels.decode_attention.kernel import decode_attention_kernel

NEG_INF = -1e30


def _pad_axis(x, axis, mult, value=0.0):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def decode_attention(q, k, v, bias, *, cap: Optional[float] = None,
                     bk: int = 512, interpret: Optional[bool] = None):
    """q: [B,H,hd]; k/v: [B,L,KV,hd]; bias: [B,L] additive mask.

    ``interpret=None`` resolves backend-aware outside the jit boundary
    (compiled on TPU, interpreter elsewhere)."""
    return _decode_attention(q, k, v, bias, cap=cap, bk=bk,
                             interpret=resolve_interpret(interpret))


@functools.partial(jax.jit,
                   static_argnames=("cap", "bk", "interpret"))
def _decode_attention(q, k, v, bias, *, cap: Optional[float],
                      bk: int, interpret: bool):
    B, H, hd = q.shape
    L = k.shape[1]
    hd_pad = max(hd + (-hd % 128), 128)
    if hd_pad != hd:
        q = _pad_axis(q, 2, 128) * jnp.asarray((hd_pad / hd) ** 0.5, q.dtype)
        k = _pad_axis(k, 3, 128)
        v = _pad_axis(v, 3, 128)
    bk = min(bk, L)
    kp = _pad_axis(k, 1, bk)
    vp = _pad_axis(v, 1, bk)
    biasp = _pad_axis(bias, 1, bk, value=NEG_INF)
    # kernel layouts: q grouped by kv head, kv heads merged into the
    # lane axis, bias with a unit row axis
    KV, hdp, Lp = k.shape[2], q.shape[2], kp.shape[1]
    out = decode_attention_kernel(
        q.reshape(B, KV, H // KV, hdp), kp.reshape(B, Lp, KV * hdp),
        vp.reshape(B, Lp, KV * hdp), biasp.reshape(B, 1, Lp), cap=cap,
        bk=bk, interpret=interpret)
    return out.reshape(B, H, hdp)[:, :, :hd]
