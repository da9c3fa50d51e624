"""Mixture-of-experts FFN: dropless routing over the router's whole width,
computing the part of the output that the experts held here give.

Routing (``route``) scores every token against all ``n_experts`` with a
softmax or a sigmoid.  With ``expert_bias`` a fixed per-expert bias (a
buffer: no gradient, no optimizer state) is added to the scores for the
selection only.  The weights are the selected scores, renormalised
(``norm_topk``) and multiplied by ``routed_scale``.

A layer holds ``experts_held`` experts from ``expert_offset`` on: one
chip's share under expert parallelism.  An assignment to an expert held
elsewhere adds nothing here, so the outputs of all the shares sum to the
whole layer's.  No token is ever dropped:

- training and prefill (``S > 1``) sort the token-to-held-expert
  assignments by expert and run the held experts as grouped matrix
  products (``jax.lax.ragged_dot``);
- decode (``S == 1``) evaluates every held expert on the batch and masks
  by the gates: the step is bound by reading the held experts' weights,
  which it reads once either way.

Both paths route with ``route`` and give the same weights.  Each returns
``load``, the token-to-held-expert assignments per held expert.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.config import FFN, ModelConfig
from repro.models.layers import dense_init


def init_moe(key, cfg: ModelConfig, kind: FFN, dtype):
    d, f = cfg.d_model, cfg.resolved_expert_d_ff
    E, H = cfg.n_experts, cfg.n_held
    ks = jax.random.split(key, 4)
    if kind in (FFN.SWIGLU, FFN.GEGLU):
        experts = {
            "w_gate": dense_init(ks[0], (H, d, f), dtype=dtype),
            "w_up": dense_init(ks[1], (H, d, f), dtype=dtype),
            "w_down": dense_init(ks[2], (H, f, d), dtype=dtype),
        }
    else:
        experts = {
            "w_up": dense_init(ks[0], (H, d, f), dtype=dtype),
            "w_down": dense_init(ks[1], (H, f, d), dtype=dtype),
        }
    p = {"router": dense_init(ks[3], (d, E), scale=0.02, dtype=dtype),
         "experts": experts}
    if cfg.expert_bias:
        p["expert_bias"] = jnp.zeros((E,), jnp.float32)
    return p


def route(params, cfg: ModelConfig, x2):
    """x2: [T, d] -> (weights [T, K] float32, experts [T, K] over the
    router's width, aux loss)."""
    E, K = cfg.n_experts, cfg.top_k
    with jax.named_scope("moe.route"):
        logits = (x2 @ params["router"]).astype(jnp.float32)
        scores = (jax.nn.sigmoid(logits) if cfg.router_score == "sigmoid"
                  else jax.nn.softmax(logits, axis=-1))
        select = scores
        if cfg.expert_bias:
            select = scores + jax.lax.stop_gradient(
                params["expert_bias"].astype(jnp.float32))
        _, top_e = jax.lax.top_k(select, K)
        top_w = jnp.take_along_axis(scores, top_e, axis=-1)
        if cfg.norm_topk:
            top_w = top_w / (top_w.sum(-1, keepdims=True) + 1e-6)
        top_w = top_w * cfg.routed_scale
        aux = jnp.zeros((), jnp.float32)
        if cfg.router_aux_coef:
            # Switch-style load balancing over the router's width
            me = scores.mean(axis=0)
            ce = jax.nn.one_hot(top_e[:, 0], E, dtype=jnp.float32).mean(0)
            aux = cfg.router_aux_coef * E * jnp.sum(me * ce)
    return top_w, top_e, aux


def _held(cfg: ModelConfig, top_e):
    """(local expert ids [T, K], held mask [T, K])."""
    local = top_e - cfg.expert_offset
    return local, (local >= 0) & (local < cfg.n_held)


def _activate(kind: FFN, gate, up):
    if kind == FFN.SWIGLU:
        return jax.nn.silu(gate) * up
    if kind == FFN.GEGLU:
        return jax.nn.gelu(gate, approximate=True) * up
    if kind == FFN.SQUARED_RELU:
        return jnp.square(jax.nn.relu(up))
    return jax.nn.gelu(up, approximate=True)


def _grouped_experts(experts, cfg: ModelConfig, kind: FFN, x2, top_w,
                     top_e):
    """Dropless grouped evaluation: x2 [T, d] -> ([T, d], load [H])."""
    T, d = x2.shape
    K, H = cfg.top_k, cfg.n_held
    local, held = _held(cfg, top_e)
    flat = jnp.where(held, local, H).reshape(-1)          # [T*K]
    # held assignments first, by expert; at most min(K, H) per token
    order = jnp.argsort(flat, stable=True)[:T * min(K, H)]
    load = jnp.bincount(flat, length=H + 1)[:H].astype(jnp.int32)
    tok = order // K
    # rows past the held assignments belong to no group, and ragged_dot
    # leaves its output there undefined (uninitialised on the TPU): each
    # product's input and output are zeroed there, which also zeroes
    # whatever the backward pass computes for those rows
    live = (jnp.arange(order.shape[0]) < load.sum())[:, None]

    def rdot(lhs, w):
        return jnp.where(live, jax.lax.ragged_dot(lhs, w, load), 0)

    xs = jnp.where(live, jnp.take(x2, tok, axis=0), 0)
    gate = rdot(xs, experts["w_gate"]) if "w_gate" in experts else None
    up = rdot(xs, experts["w_up"])
    out = rdot(_activate(kind, gate, up), experts["w_down"])
    w = jnp.where(held, top_w, 0.0).reshape(-1)[order]
    y = jnp.zeros((T, d), jnp.float32).at[tok].add(
        out.astype(jnp.float32) * w[:, None])
    return y.astype(x2.dtype), load


def _dense_experts(experts, cfg: ModelConfig, kind: FFN, x2, top_w, top_e):
    """Every held expert on every row, masked by the gates: x2 [T, d] ->
    ([T, d], load [H])."""
    H = cfg.n_held
    local, held = _held(cfg, top_e)
    hot = jax.nn.one_hot(jnp.where(held, local, H), H,
                         dtype=jnp.float32)                # [T, K, H]
    w_mask = jnp.einsum("tk,tkh->th", top_w, hot)
    load = hot.sum(axis=(0, 1)).astype(jnp.int32)
    up = jnp.einsum("td,hdf->thf", x2, experts["w_up"])
    gate = (jnp.einsum("td,hdf->thf", x2, experts["w_gate"])
            if "w_gate" in experts else None)
    out = jnp.einsum("thf,hfd->thd", _activate(kind, gate, up),
                     experts["w_down"])
    y = jnp.einsum("thd,th->td", out, w_mask.astype(out.dtype))
    return y.astype(x2.dtype), load


def apply_moe(params, cfg: ModelConfig, kind: FFN, x):
    """x: [B, S, d] -> (y, aux_loss, load [n_held])."""
    B, S, d = x.shape
    x2 = x.reshape(B * S, d)
    top_w, top_e, aux = route(params, cfg, x2)
    experts_fn = _dense_experts if S == 1 else _grouped_experts
    with jax.named_scope("moe.experts"):
        y, load = experts_fn(params["experts"], cfg, kind, x2, top_w, top_e)
    return y.reshape(B, S, d), aux, load
