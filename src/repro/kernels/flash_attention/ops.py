"""Jitted wrapper: pads (seq -> block multiple, hd -> 128 for MXU
alignment), dispatches to the Pallas kernel, unpads."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.backend import resolve_interpret
from repro.kernels.flash_attention.kernel import flash_attention_kernel


def _pad_axis(x, axis, mult):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    cap: Optional[float] = None,
                    bq: int = 128, bk: int = 512,
                    interpret: Optional[bool] = None):
    """``interpret=None`` resolves backend-aware outside the jit
    boundary (repro.kernels.backend)."""
    return _flash_attention(q, k, v, causal=causal, window=window,
                            cap=cap, bq=bq, bk=bk,
                            interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "cap", "bq", "bk", "interpret"))
def _flash_attention(q, k, v, *, causal: bool, window: Optional[int],
                     cap: Optional[float], bq: int, bk: int,
                     interpret: bool):
    B, S, H, hd = q.shape
    Sk = k.shape[1]
    scale_fix = 1.0
    hd_pad = max(-hd % 128 + hd, 128)
    if hd_pad != hd:
        # zero-pad head dim: dot products are unchanged, but the kernel's
        # 1/sqrt(hd_pad) scale must be corrected back to 1/sqrt(hd)
        q = _pad_axis(q, 3, 128) * jnp.asarray(
            (hd_pad / hd) ** 0.5, q.dtype)
        k = _pad_axis(k, 3, 128)
        v = _pad_axis(v, 3, 128)
    qp = _pad_axis(q, 1, bq if S > bq else S)
    kp = _pad_axis(k, 1, bk if Sk > bk else Sk)
    vp = _pad_axis(v, 1, bk if Sk > bk else Sk)
    # merge the head axes: each kernel block is one head's (rows, hd)
    # column tile
    Sp, Skp, hdp = qp.shape[1], kp.shape[1], qp.shape[3]
    out = flash_attention_kernel(
        qp.reshape(B, Sp, H * hdp), kp.reshape(B, Skp, -1),
        vp.reshape(B, Skp, -1), n_heads=H, causal=causal, window=window,
        cap=cap, bq=bq, bk=bk, seq_len=Sk, interpret=interpret)
    return out.reshape(B, Sp, H, hdp)[:, :S, :, :hd]
