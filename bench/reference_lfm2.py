"""LFM2-8B-A1B for the benchmark: seeded weights in a layout of this
module's own and a plain float32 forward pass, independent of the
program.

The configuration file (``bench/configs/lfm2-8b-a1b.json``) gives the
published sizes, the layers kept (``layer_types``), the router's width
(``router_experts``) and the experts held here (``num_experts`` from
``expert_offset``).  Layer equations, as in the ``lfm2``/``lfm2_moe``
modelling code:

- short conv: ``B, C, x = split3(in_proj(h))``,
  ``y = out_proj(C * causal_depthwise_conv(B * x))``, no bias;
- attention: GQA with per-head RMSNorm on q and k, then RoPE (split
  halves), causal softmax;
- dense SwiGLU (``intermediate_size``) in the first ``num_dense_layers``,
  then the expert layer: sigmoid scores over all ``router_experts``, the
  fixed bias added for the top-k selection only, the selected scores over
  their sum plus 1e-6, times ``routed_scaling_factor``; each held expert
  (SwiGLU of ``moe_intermediate_size``) on every token, masked by its
  gate, so nothing is dropped; what experts held elsewhere add is left
  out, as on the chip;
- ``h = x + mixer(norm(x))``, ``out = h + ffn(norm(h))``; final RMSNorm;
  the tied head; no embedding scale.

Every matrix product goes through ``bench.reference._mm`` (float32 at the
highest precision, or the float8 control).
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from bench.reference import _mm, _q8, _rms, _rope

BIAS_STD = 0.1


def sizes(spec: Dict) -> Dict[str, int]:
    d = spec["hidden_size"]
    H, KV = spec["num_attention_heads"], spec["num_key_value_heads"]
    return {"d": d, "H": H, "KV": KV, "hd": spec.get("head_dim", d // H),
            "f": spec["intermediate_size"],
            "fe": spec["moe_intermediate_size"],
            "E": spec["router_experts"], "held": spec["num_experts"],
            "off": spec["expert_offset"], "K": spec["num_experts_per_tok"],
            "L": spec["conv_L_cache"], "V": spec["vocab_size"],
            "eps": spec["norm_eps"], "theta": float(spec["rope_theta"]),
            "dense": spec["num_dense_layers"],
            "scale": float(spec["routed_scaling_factor"])}


def layer_shapes(spec: Dict, i: int) -> Dict[str, tuple]:
    s = sizes(spec)
    d = s["d"]
    out = {"norm1": (d,), "norm2": (d,)}
    if spec["layer_types"][i] == "full_attention":
        out.update(wq=(d, s["H"] * s["hd"]), wk=(d, s["KV"] * s["hd"]),
                   wv=(d, s["KV"] * s["hd"]), q_norm=(s["hd"],),
                   k_norm=(s["hd"],), wo=(s["H"] * s["hd"], d))
    else:
        out.update(in_proj=(d, 3 * d), conv_w=(s["L"], d),
                   out_proj=(d, d))
    if i < s["dense"]:
        out.update(w_gate=(d, s["f"]), w_up=(d, s["f"]), w_down=(s["f"], d))
    else:
        out["router"] = (d, s["E"])
    return out


TENSORS = ("norm1", "wq", "wk", "wv", "q_norm", "k_norm", "wo", "in_proj",
           "conv_w", "out_proj", "norm2", "w_gate", "w_up", "w_down",
           "router")
EXPERT_TENSORS = ("w_gate", "w_up", "w_down")


def _draw(key, shape, dtype):
    if len(shape) == 1:            # a norm's scale: near 1
        x = 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    else:                          # fan-in truncated normal
        x = jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) \
            / math.sqrt(shape[0])
    return x.astype(dtype)


def layer_weights(key, spec: Dict, i: int, dtype) -> Dict:
    """Layer ``i``'s tensors in ``dtype``; an expert layer's held experts
    are drawn by their ids over the router's width (so every chip's share
    is a slice of the same whole) and its bias is float32."""
    s = sizes(spec)
    k = jax.random.fold_in(jax.random.fold_in(key, 1), i)
    w = {n: _draw(jax.random.fold_in(k, TENSORS.index(n)), shape, dtype)
         for n, shape in layer_shapes(spec, i).items()}
    if i >= s["dense"]:
        d, fe = s["d"], s["fe"]
        ids = range(s["off"], s["off"] + s["held"])
        ek = jax.random.fold_in(k, 100)
        for j, n in enumerate(EXPERT_TENSORS):
            shape = (fe, d) if n == "w_down" else (d, fe)
            w["experts." + n] = jnp.stack([_draw(
                jax.random.fold_in(jax.random.fold_in(ek, e), j), shape,
                dtype) for e in ids])
        w["expert_bias"] = BIAS_STD * jax.random.normal(
            jax.random.fold_in(k, 200), (s["E"],), jnp.float32)
    return w


def weights(spec: Dict, key, dtype=jnp.bfloat16) -> Dict:
    s = sizes(spec)
    k = jax.random.fold_in(key, 2)
    return {"outer": {
        "tokens": (0.02 * jax.random.normal(jax.random.fold_in(k, 0),
                                            (s["V"], s["d"]), jnp.float32)
                   ).astype(dtype),
        "final_norm": _draw(jax.random.fold_in(k, 2), (s["d"],), dtype)},
        "layers": [layer_weights(key, spec, i, dtype)
                   for i in range(spec["num_hidden_layers"])]}


def split_bias(theta) -> tuple:
    """(trained tensors, {layer: expert_bias}): the bias is a fixed
    buffer, neither trained nor given Adam state."""
    layers, bias = [], {}
    for i, w in enumerate(theta["layers"]):
        w = dict(w)
        if "expert_bias" in w:
            bias[i] = w.pop("expert_bias")
        layers.append(w)
    return {"outer": theta["outer"], "layers": layers}, bias


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _swiglu(w, h, prec):
    return _mm(jax.nn.silu(_mm(h, w["w_gate"], prec))
               * _mm(h, w["w_up"], prec), w["w_down"], prec)


def _attention(s, w, h, prec):
    B, S, _ = h.shape
    H, KV, hd = s["H"], s["KV"], s["hd"]
    q = _rms(_mm(h, w["wq"], prec).reshape(B, S, H, hd), w["q_norm"],
             s["eps"])
    k = _rms(_mm(h, w["wk"], prec).reshape(B, S, KV, hd), w["k_norm"],
             s["eps"])
    v = _mm(h, w["wv"], prec).reshape(B, S, KV, hd)
    q, k = _rope(q, s["theta"]), _rope(k, s["theta"])
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                    precision=jax.lax.Precision.HIGHEST) / math.sqrt(hd)
    sc = jnp.where(jnp.tril(jnp.ones((S, S), bool)), sc, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v,
                   precision=jax.lax.Precision.HIGHEST)
    return _mm(o.reshape(B, S, H * hd), w["wo"], prec)


def _shortconv(s, w, h, prec):
    d, L, S = s["d"], s["L"], h.shape[1]
    bcx = _mm(h, w["in_proj"], prec)
    b, c, x = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
    bx = jnp.pad(b * x, ((0, 0), (L - 1, 0), (0, 0)))
    conv = sum(w["conv_w"][i] * bx[:, i:i + S] for i in range(L))
    return _mm(c * conv, w["out_proj"], prec)


def gates(s, w, bias, h, prec):
    """[..., E] routing weights over the router's width: the selected
    sigmoid scores, zero elsewhere."""
    scores = jax.nn.sigmoid(_mm(h, w["router"], prec))
    select = scores + bias
    kth = jnp.sort(select, -1)[..., -s["K"]][..., None]
    g = jnp.where(select >= kth, scores, 0.0)
    return g / (g.sum(-1, keepdims=True) + 1e-6) * s["scale"]


def _emm(spec, x, w, prec):
    """Every held expert's product: x [B, S, in] (or [B, S, held, in])
    by w [held, in, out] -> [B, S, held, out]; the float8 control takes
    one scale per expert."""
    if prec == "fp8":
        x, w = _q8(x, -1), _q8(w, (1, 2))
    return jnp.einsum(spec, x, w, precision=jax.lax.Precision.HIGHEST)


def _experts(s, w, bias, h, prec):
    """Each held expert's SwiGLU on every token, weighted by its gate:
    zero where the token did not select it."""
    g = gates(s, w, bias, h, prec)[..., s["off"]:s["off"] + s["held"]]
    act = (jax.nn.silu(_emm("bsd,edf->bsef", h, w["experts.w_gate"], prec))
           * _emm("bsd,edf->bsef", h, w["experts.w_up"], prec))
    y = _emm("bsef,efd->bsed", act, w["experts.w_down"], prec)
    return jnp.einsum("bsed,bse->bsd", y, g,
                      precision=jax.lax.Precision.HIGHEST)


def layer(spec: Dict, i: int, w, bias, x, prec="f32"):
    s = sizes(spec)
    mixer = (_attention if spec["layer_types"][i] == "full_attention"
             else _shortconv)
    x = x + mixer(s, w, _rms(x, w["norm1"], s["eps"]), prec)
    h = _rms(x, w["norm2"], s["eps"])
    ffn = (_swiglu(w, h, prec) if i < s["dense"]
           else _experts(s, w, bias, h, prec))
    return x + ffn


def make_forward(spec: Dict):
    """forward(theta32, bias, tokens, prec) -> logits [B, S, V], with
    each layer recomputed in the backward pass; ``bias`` from
    ``split_bias``."""
    s = sizes(spec)

    def forward(theta32, bias, tokens, prec="f32"):
        x = theta32["outer"]["tokens"][tokens]
        for i, w in enumerate(theta32["layers"]):
            x = jax.checkpoint(
                lambda x, w, b, i=i: layer(spec, i, w, b, x, prec))(
                x, w, bias.get(i))
        x = _rms(x, theta32["outer"]["final_norm"], s["eps"])
        return _mm(x, theta32["outer"]["tokens"].T, prec)
    return forward


def leaf_norms(tree) -> Dict[str, float]:
    """Norms of a reference-layout tree (trained tensors, their gradients
    or changes): ``L03.experts.w_up`` etc. (wk and wv joined as wkv),
    plus ``embed`` and ``final_norm``."""
    out = {}
    for i, w in enumerate(tree["layers"]):
        sq = {n: float(jnp.sum(jnp.square(x.astype(jnp.float32))))
              for n, x in w.items()}
        if "wk" in sq:
            sq["wkv"] = sq.pop("wk") + sq.pop("wv")
        for n, v in sq.items():
            out[f"L{i:02d}.{n}"] = math.sqrt(v)
    o = tree["outer"]
    out["embed"] = float(jnp.linalg.norm(o["tokens"].astype(jnp.float32)))
    out["final_norm"] = float(jnp.linalg.norm(
        o["final_norm"].astype(jnp.float32)))
    return out
