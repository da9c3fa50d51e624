"""Shared neural-net building blocks (pure functional JAX, no flax).

Parameters are nested dicts of jnp arrays. Every block has an
``init_*(key, cfg, ...) -> params`` and an ``apply`` function.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.config import FFN, ModelConfig


def dense_init(key, shape, scale: Optional[float] = None, dtype=jnp.float32):
    """Truncated-normal fan-in init."""
    if scale is None:
        scale = 1.0 / math.sqrt(shape[0])
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * scale).astype(dtype)


# ---------------------------------------------------------------------------
# Normalization
# ---------------------------------------------------------------------------

def init_rmsnorm(d: int, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(params, x, eps: float = 1e-5):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * params["scale"].astype(jnp.float32)).astype(dtype)


def softcap(x, cap: Optional[float]):
    """Gemma2-style logit soft capping."""
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float):
    half = head_dim // 2
    return theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)


def apply_rope(x, positions, theta: float):
    """x: [..., S, H, hd]; positions: [..., S] int32."""
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta)                      # [hd/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., S, hd/2]
    cos = jnp.cos(angles)[..., None, :]                      # [..., S, 1, hd/2]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(seq_len: int, d_model: int, dtype=jnp.float32):
    pos = jnp.arange(seq_len, dtype=jnp.float32)[:, None]
    half = d_model // 2
    freq = jnp.exp(-math.log(10_000.0) * jnp.arange(half) / half)[None, :]
    ang = pos * freq
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1).astype(dtype)


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def init_embeddings(key, cfg: ModelConfig, dtype):
    keys = jax.random.split(key, 3)
    p = {}
    p["tokens"] = dense_init(keys[0], (cfg.vocab_size, cfg.d_model),
                             scale=0.02, dtype=dtype)
    if cfg.frontend == "features":
        p["feature_proj"] = dense_init(
            keys[1], (cfg.feature_dim, cfg.d_model), dtype=dtype)
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(keys[2], (cfg.d_model, cfg.vocab_size),
                                  dtype=dtype)
    return p


def embed_tokens(params, cfg: ModelConfig, tokens):
    emb = jnp.take(params["tokens"], tokens, axis=0)
    if cfg.frontend == "tokens" and cfg.token_embed_scale != 1.0:
        emb = emb * jnp.asarray(cfg.token_embed_scale, emb.dtype)
    return emb


def embed_features(params, cfg: ModelConfig, features):
    return features.astype(params["feature_proj"].dtype) @ params["feature_proj"]


def unembed(params, cfg: ModelConfig, x):
    if cfg.tie_embeddings:
        logits = x @ params["tokens"].T
    else:
        logits = x @ params["lm_head"]
    return softcap(logits, cfg.final_softcap)


# ---------------------------------------------------------------------------
# Feed-forward variants
# ---------------------------------------------------------------------------

def init_ffn(key, cfg: ModelConfig, kind: FFN, dtype):
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    if kind in (FFN.SWIGLU, FFN.GEGLU):
        return {"w_gate": dense_init(ks[0], (d, f), dtype=dtype),
                "w_up": dense_init(ks[1], (d, f), dtype=dtype),
                "w_down": dense_init(ks[2], (f, d), dtype=dtype)}
    if kind == FFN.RWKV_CHANNEL:
        return {"w_key": dense_init(ks[0], (d, f), dtype=dtype),
                "w_value": dense_init(ks[1], (f, d), dtype=dtype),
                "w_recept": dense_init(ks[2], (d, d), dtype=dtype),
                "mu_k": jnp.full((d,), 0.5, dtype),
                "mu_r": jnp.full((d,), 0.5, dtype)}
    # GELU / SQUARED_RELU two-matrix MLP
    return {"w_up": dense_init(ks[0], (d, f), dtype=dtype),
            "w_down": dense_init(ks[1], (f, d), dtype=dtype)}


def apply_ffn(params, cfg: ModelConfig, kind: FFN, x, *, shifted=None):
    """``shifted``: previous-token tensor for RWKV channel mix."""
    if kind == FFN.SWIGLU:
        return (jax.nn.silu(x @ params["w_gate"]) * (x @ params["w_up"])) \
            @ params["w_down"]
    if kind == FFN.GEGLU:
        return (jax.nn.gelu(x @ params["w_gate"], approximate=True)
                * (x @ params["w_up"])) @ params["w_down"]
    if kind == FFN.SQUARED_RELU:
        h = jax.nn.relu(x @ params["w_up"])
        return jnp.square(h) @ params["w_down"]
    if kind == FFN.GELU:
        return jax.nn.gelu(x @ params["w_up"], approximate=True) \
            @ params["w_down"]
    if kind == FFN.RWKV_CHANNEL:
        assert shifted is not None
        xk = x + (shifted - x) * params["mu_k"]
        xr = x + (shifted - x) * params["mu_r"]
        k = jnp.square(jax.nn.relu(xk @ params["w_key"]))
        return jax.nn.sigmoid(xr @ params["w_recept"]) * (k @ params["w_value"])
    raise ValueError(kind)


def token_shift(x):
    """RWKV-style shift: x[t] -> x[t-1] (zeros at t=0). x: [B, S, d]."""
    return jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1, :]
