"""LFM2-8B-A1B for ``bench/drivers/rl_plan.py`` (the configuration
file's ``bench_model``): the program's ``ModelConfig`` from the file, the
seed's weights (``bench.reference_lfm2``) put into the program's
parameter tree, norms of that tree keyed as the reference keys its own,
and the reference's GRPO steps.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from bench import reference_lfm2 as R
from bench.reference_grpo import GRPOReference

reference_norms = R.leaf_norms


def program_config(spec: Dict):
    """The program's ModelConfig for the configuration file's dict."""
    from repro.configs.archs import lfm2_pattern
    from repro.models.config import ModelConfig
    s = R.sizes(spec)
    cfg = ModelConfig(
        name=spec["name"], source=spec["source"],
        n_layers=spec["num_hidden_layers"], d_model=s["d"],
        n_heads=s["H"], n_kv_heads=s["KV"], head_dim=s["hd"], d_ff=s["f"],
        vocab_size=s["V"],
        pattern=lfm2_pattern(spec["layer_types"], s["dense"]),
        n_experts=s["E"], top_k=s["K"], experts_held=s["held"],
        expert_offset=s["off"], expert_d_ff=s["fe"], router_aux_coef=0.0,
        router_score="sigmoid", expert_bias=spec["use_expert_bias"],
        norm_topk=spec["norm_topk_prob"], routed_scale=s["scale"],
        conv_kernel=s["L"], qk_norm=True, rope_theta=s["theta"],
        tie_embeddings=spec["tie_word_embeddings"], embed_scale=1.0,
        norm_eps=s["eps"], dtype=spec["torch_dtype"])
    cfg.validate()
    return cfg


def configs(spec: Dict):
    """(the program's ModelConfig, the file's dict the reference draws
    from): the file as it is (the CPU tests replace this function to run
    a small model)."""
    return program_config(spec), spec


def spec_at(cfg, spec: Dict) -> Dict:
    """The configuration file's dict at the sizes of ``cfg`` (the CPU
    tests' small LFM2 config)."""
    from repro.models.config import Mixer
    types = ["full_attention" if s.mixer == Mixer.ATTENTION else "conv"
             for s in cfg.pattern] * cfg.n_pattern_repeats
    return dict(
        spec, hidden_size=cfg.d_model, num_attention_heads=cfg.n_heads,
        num_key_value_heads=cfg.n_kv_heads, head_dim=cfg.resolved_head_dim,
        intermediate_size=cfg.d_ff,
        moe_intermediate_size=cfg.resolved_expert_d_ff,
        vocab_size=cfg.vocab_size, router_experts=cfg.n_experts,
        num_experts=cfg.n_held, expert_offset=cfg.expert_offset,
        num_experts_per_tok=cfg.top_k, num_hidden_layers=cfg.n_layers,
        layer_types=types,
        num_dense_layers=sum(not s.moe for s in cfg.pattern),
        torch_dtype=cfg.dtype)


def program_tree(spec: Dict, theta) -> Dict:
    """The program's parameter tree (one pattern of all the layers, one
    repeat) from the reference-layout weights."""
    one = lambda x: x[None]          # noqa: E731  the repeat axis
    blocks = {}
    for i, w in enumerate(theta["layers"]):
        b = {"norm1": {"scale": one(w["norm1"])},
             "norm2": {"scale": one(w["norm2"])}}
        if "wq" in w:
            b["attn"] = {
                "wq": one(w["wq"]),
                "wkv": one(jnp.concatenate([w["wk"], w["wv"]], axis=-1)),
                "wo": one(w["wo"]),
                "q_norm": {"scale": one(w["q_norm"])},
                "k_norm": {"scale": one(w["k_norm"])}}
        else:
            b["shortconv"] = {n: one(w[n])
                              for n in ("in_proj", "conv_w", "out_proj")}
        if "router" in w:
            b["moe"] = {"router": one(w["router"]),
                        "experts": {n: one(w["experts." + n])
                                    for n in R.EXPERT_TENSORS}}
            if "expert_bias" in w:
                b["moe"]["expert_bias"] = one(w["expert_bias"])
        else:
            b["ffn"] = {n: one(w[n]) for n in ("w_gate", "w_up", "w_down")}
        blocks[f"layer{i}"] = b
    return {"embed": {"tokens": theta["outer"]["tokens"]},
            "blocks": blocks,
            "final_norm": {"scale": theta["outer"]["final_norm"]}}


def program_params(key, spec: Dict, cfg):
    """All weights, on the device, in one jitted call."""
    dt = jnp.dtype(cfg.dtype)
    return jax.jit(lambda k: program_tree(spec, R.weights(spec, k, dt)))(
        key)


def leaf_norms(tree) -> Dict[str, jnp.ndarray]:
    """Squared norms of a program-layout tree's trained leaves, keyed as
    ``reference_lfm2.leaf_norms`` keys its own (jit-able; see
    ``flatten``)."""
    out = {}
    for name, b in tree["blocks"].items():
        i = int(name[len("layer"):])
        flat = {"norm1": b["norm1"]["scale"], "norm2": b["norm2"]["scale"]}
        if "attn" in b:
            a = b["attn"]
            flat.update(wq=a["wq"], wkv=a["wkv"], wo=a["wo"],
                        q_norm=a["q_norm"]["scale"],
                        k_norm=a["k_norm"]["scale"])
        else:
            flat.update(b["shortconv"])
        if "moe" in b:
            flat["router"] = b["moe"]["router"]
            flat.update({"experts." + n: x
                         for n, x in b["moe"]["experts"].items()})
        else:
            flat.update(b["ffn"])
        for n, x in flat.items():
            out[f"L{i:02d}.{n}"] = jnp.sum(jnp.square(x.astype(jnp.float32)))
    out["embed"] = jnp.sum(jnp.square(
        tree["embed"]["tokens"].astype(jnp.float32)))
    out["final_norm"] = jnp.sum(jnp.square(
        tree["final_norm"]["scale"].astype(jnp.float32)))
    return out


def flatten(sq: Dict) -> Dict[str, float]:
    """``leaf_norms`` read to the host: {name: norm}."""
    return {k: math.sqrt(float(v)) for k, v in sq.items()}


def norms(tree) -> Dict[str, float]:
    """``leaf_norms`` of a program tree, read to the host."""
    return flatten(jax.device_get(jax.jit(leaf_norms)(tree)))


def reference(spec: Dict, cfg, key, prec, **grpo) -> GRPOReference:
    """The reference's GRPO steps from the seed's weights (the expert
    bias apart, as a constant of the forward pass)."""
    theta, bias = R.split_bias(jax.jit(
        lambda k: R.weights(spec, k, jnp.dtype(cfg.dtype)))(key))
    return GRPOReference(R.make_forward(spec), theta, consts=bias,
                         prec=prec, **grpo)
