"""Top-level kernel dispatch used by the model layer when
`set_attention_impl("pallas")` is active.  Execution mode is
backend-aware (``repro.kernels.backend``): compiled Pallas on TPU,
interpret mode elsewhere."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.backend import default_interpret, resolve_interpret
from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.flash_attention.ops import flash_attention as _flash
from repro.kernels.mamba_scan.ops import mamba_scan
from repro.kernels.rwkv6_scan.ops import rwkv6_scan


def flash_attention(q, k, v, q_pos, k_pos, *, causal=True, window=None,
                    cap=None, k_valid=None):
    """Adapter matching models.attention.multihead_attention's contract.

    The Pallas kernel assumes contiguous arange positions (training /
    prefill self-attention); anything else falls back to the jnp path."""
    Sq, Sk = q.shape[1], k.shape[1]
    contiguous = (Sq == Sk and q_pos.ndim == 1 and k_valid is None)
    if not contiguous:
        from repro.models import attention as attn
        per_row = (q_pos.ndim > 1 or k_pos.ndim > 1
                   or (k_valid is not None and k_valid.ndim > 1))
        if per_row:
            # ragged per-row positions (chunked prefill): the blockwise
            # jnp path shares bias across rows — use the plain oracle
            return attn.plain_attention(q, k, v, q_pos, k_pos,
                                        causal=causal, window=window,
                                        cap=cap, k_valid=k_valid)
        return attn.chunked_attention(q, k, v, q_pos, k_pos, causal=causal,
                                      window=window, cap=cap,
                                      k_valid=k_valid)
    return _flash(q, k, v, causal=causal, window=window, cap=cap)


def flash_decode_attention(q, k, v, bias, *, cap=None):
    """Single-token (Sq == 1) decode attention over a slot-validity bias.

    q: [B, H, hd]; k/v: [B, L, KV, hd] (GQA via H // KV); bias: [B, L]
    additive (0 = attend, -inf = masked).  Returns [B, H, hd]."""
    return decode_attention(q, k, v, bias, cap=cap)
