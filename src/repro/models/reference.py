"""Plain float32 reference of the token models' forward pass and of the
GRPO actor loss and its gradients.

Straightforward ``jax.numpy`` over one sequence at a time: no cache, no
kernels, no grouped or sorted dispatch, every matrix product at
``jax.default_matmul_precision("highest")``.  It reads the program's
parameter tree (``transformer.init_params`` layout) and follows the
published layer equations:

- attention: GQA with per-head RMSNorm on q and k before RoPE (split
  halves), causal softmax;
- gated short convolution (LFM2): ``B, C, x = split3(in_proj(h))``,
  ``out_proj(C * causal_depthwise_conv(B * x))``, no bias;
- dense SwiGLU;
- expert layer: softmax or sigmoid scores over the router's whole
  width, a selection bias added for the top-k choice only, the selected
  scores renormalised (plus 1e-6) and scaled; every held expert runs on
  every token and is masked by its gate, so nothing can be dropped;
- pre-norm blocks ``h = x + mixer(norm(x))``, ``out = h + ffn(norm(h))``,
  a final RMSNorm and the (tied or untied) head; token embeddings times
  the configuration's scale.

Departures, none of which a covered configuration uses: no sliding
windows, logit soft-capping, Mamba or RWKV mixers, feature frontends or
router auxiliary loss.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.models.config import Mixer, ModelConfig
from repro.optim import adam


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32),
                                  tree)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def _rope(x, theta):
    """x: [S, H, hd] at positions 0..S-1."""
    S, _, hd = x.shape
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(p, cfg: ModelConfig, h):
    S = h.shape[0]
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q = (h @ p["wq"]).reshape(S, H, hd)
    kv = (h @ p["wkv"]).reshape(S, 2 * KV, hd)
    k, v = kv[:, :KV], kv[:, KV:]
    if cfg.qk_norm:
        q = _rms(q, p["q_norm"]["scale"], cfg.norm_eps)
        k = _rms(k, p["k_norm"]["scale"], cfg.norm_eps)
    if cfg.rope:
        q, k = _rope(q, cfg.rope_theta), _rope(k, cfg.rope_theta)
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(float(hd))
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)
    return o.reshape(S, H * hd) @ p["wo"]


def shortconv(p, cfg: ModelConfig, h):
    d, L = cfg.d_model, cfg.conv_kernel
    bcx = h @ p["in_proj"]
    b, c, x = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
    bx = jnp.pad(b * x, ((L - 1, 0), (0, 0)))
    S = h.shape[0]
    conv = sum(p["conv_w"][i] * bx[i:i + S] for i in range(L))
    return (c * conv) @ p["out_proj"]


def swiglu(p, h):
    return (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_down"]


def gates(p, cfg: ModelConfig, h):
    """[S, n_experts] routing weights: zero off each token's top-k."""
    logits = h @ p["router"]
    scores = (jax.nn.sigmoid(logits) if cfg.router_score == "sigmoid"
              else jax.nn.softmax(logits, -1))
    select = scores + p["expert_bias"] if cfg.expert_bias else scores
    kth = jnp.sort(select, -1)[:, -cfg.top_k][:, None]
    w = jnp.where(select >= kth, scores, 0.0)
    if cfg.norm_topk:
        w = w / (w.sum(-1, keepdims=True) + 1e-6)
    return w * cfg.routed_scale


def experts(p, cfg: ModelConfig, h):
    """The held experts' part of the layer's output."""
    g = gates(p, cfg, h)
    y = jnp.zeros_like(h)
    for e in range(cfg.n_held):
        w = jax.tree_util.tree_map(lambda a: a[e], p["experts"])
        y = y + g[:, cfg.expert_offset + e, None] * swiglu(w, h)
    return y


_MIXERS = {Mixer.ATTENTION: ("attn", attention),
           Mixer.SHORTCONV: ("shortconv", shortconv)}


def block(p, cfg: ModelConfig, spec, x):
    key, mixer = _MIXERS[spec.mixer]
    x = x + mixer(p[key], cfg, _rms(x, p["norm1"]["scale"], cfg.norm_eps))
    h = _rms(x, p["norm2"]["scale"], cfg.norm_eps)
    return x + (experts(p["moe"], cfg, h) if spec.moe
                else swiglu(p["ffn"], h))


def forward(params, cfg: ModelConfig, tokens):
    """Logits [S, V] of one sequence ``tokens`` [S]."""
    assert cfg.router_aux_coef == 0 or not cfg.has_moe, \
        "the reference has no router auxiliary loss"
    p = _f32(params)
    x = p["embed"]["tokens"][tokens] * cfg.token_embed_scale
    for r in range(cfg.n_pattern_repeats):
        for j, spec in enumerate(cfg.pattern):
            layer = jax.tree_util.tree_map(lambda a: a[r],
                                           p["blocks"][f"layer{j}"])
            x = block(layer, cfg, spec, x)
    x = _rms(x, p["final_norm"]["scale"], cfg.norm_eps)
    head = (p["embed"]["tokens"].T if cfg.tie_embeddings
            else p["embed"]["lm_head"])
    return x @ head


def logits(params, cfg: ModelConfig, tokens):
    """[B, S, V] logits, one sequence at a time."""
    with jax.default_matmul_precision("highest"):
        return jnp.stack([forward(params, cfg, t) for t in tokens])


def grpo_loss(params, cfg: ModelConfig, batch, gen_start: int,
              clip_eps: float):
    """The actor's clipped-surrogate loss over the generated tokens:
    ``-min(r A, clip(r) A)`` with ``r = exp(logp - logp_old)``, summed
    over ``mask`` and divided by its count."""
    with jax.default_matmul_precision("highest"):
        per = []
        for i, seq in enumerate(batch["sequences"]):
            lp = jax.nn.log_softmax(forward(params, cfg, seq), -1)
            lp = jnp.take_along_axis(lp[gen_start - 1:-1],
                                     seq[gen_start:, None], -1)[:, 0]
            r = jnp.exp(lp - batch["logp_old"][i])
            a = batch["advantages"][i]
            per.append(-jnp.minimum(
                r * a, jnp.clip(r, 1 - clip_eps, 1 + clip_eps) * a))
        mask = batch["mask"]
        return (jnp.stack(per) * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def grpo_grad(params, cfg: ModelConfig, batch, gen_start: int,
              clip_eps: float) -> Dict:
    """(loss, gradient) over the trainable leaves (``adam.partition``: a
    fixed buffer such as the expert bias has none)."""
    train, frozen = adam.partition(params)
    return jax.value_and_grad(
        lambda t: grpo_loss(adam.join(t, frozen), cfg, batch, gen_start,
                            clip_eps))(train)
