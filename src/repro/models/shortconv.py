"""Gated short convolution mixer (LFM2).

    B, C, x = split3(in_proj(h))
    y = out_proj(C * causal_depthwise_conv(B * x))

The convolution is depthwise over ``conv_kernel`` positions with no bias
(``mamba._causal_conv``), summed in float32.  Its decode state is the
last ``conv_kernel - 1`` gated inputs ``B * x`` of each row, in float32:
``{"conv": [B, conv_kernel - 1, d]}``.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers import dense_init
from repro.models.mamba import _causal_conv


def init_shortconv(key, cfg: ModelConfig, dtype):
    d, L = cfg.d_model, cfg.conv_kernel
    ks = jax.random.split(key, 3)
    return {"in_proj": dense_init(ks[0], (d, 3 * d), dtype=dtype),
            "conv_w": dense_init(ks[1], (L, d), dtype=dtype),
            "out_proj": dense_init(ks[2], (d, d), dtype=dtype)}


def apply_shortconv(params, cfg: ModelConfig, h,
                    state: Optional[dict] = None,
                    return_state: bool = False):
    """h: [B, S, d] (normed).  ``state``: {"conv": [B, L-1, d]} from the
    tokens before this chunk (None: a sequence start)."""
    d = cfg.d_model
    with jax.named_scope("shortconv"):
        bcx = h @ params["in_proj"]
        b, c, x = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
        conv, new_state = _causal_conv(
            (b * x).astype(jnp.float32),
            params["conv_w"].astype(jnp.float32), 0.0,
            state["conv"] if state is not None else None)
        y = (c * conv).astype(h.dtype) @ params["out_proj"]
    if return_state:
        return y, {"conv": new_state}
    return y

