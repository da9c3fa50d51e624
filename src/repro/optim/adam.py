"""Adam/AdamW + gradient clipping + LR schedules, from scratch (no optax
in this environment).  Mixed-precision aware: moments can be stored in a
reduced dtype for memory-constrained configs (see DESIGN.md jamba notes).

Leaves under a ``FROZEN`` key are fixed buffers: ``partition`` splits
them off, they get no gradient and no optimizer state, and
``adam_update`` returns them as it got them.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class AdamConfig:
    lr: float = 3e-4
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.0
    grad_clip_norm: Optional[float] = 1.0
    moment_dtype: str = "float32"   # "bfloat16" to halve optimizer memory
    warmup_steps: int = 0
    total_steps: Optional[int] = None  # enables cosine decay
    min_lr_frac: float = 0.1


# an expert layer's selection bias (models/moe.py) is a buffer
FROZEN = ("expert_bias",)


def partition(tree):
    """(trainable, frozen): ``tree`` split at ``FROZEN`` dict keys, both
    in its nesting (empty where there is nothing frozen)."""
    if isinstance(tree, dict):
        train, frozen = {}, {}
        for k, v in tree.items():
            if k in FROZEN:
                frozen[k] = v
            else:
                train[k], frozen[k] = partition(v)
        return train, frozen
    if isinstance(tree, (tuple, list)):
        parts = [partition(v) for v in tree]
        return (type(tree)(p[0] for p in parts),
                type(tree)(p[1] for p in parts))
    return tree, {}


def join(train, frozen):
    """The inverse of ``partition``."""
    if isinstance(train, dict):
        out = dict(train)
        for k, v in frozen.items():
            out[k] = join(train[k], v) if k in train else v
        return out
    if isinstance(train, (tuple, list)):
        return type(train)(join(t, f) for t, f in zip(train, frozen))
    return train


def init_adam_state(params, cfg: AdamConfig):
    params = partition(params)[0]
    mdt = jnp.dtype(cfg.moment_dtype)
    zeros = lambda p: jnp.zeros_like(p, dtype=mdt)
    return {
        "step": jnp.zeros((), jnp.int32),
        "m": jax.tree_util.tree_map(zeros, params),
        "v": jax.tree_util.tree_map(zeros, params),
    }


def schedule_lr(cfg: AdamConfig, step):
    lr = jnp.asarray(cfg.lr, jnp.float32)
    if cfg.warmup_steps > 0:
        lr = lr * jnp.minimum((step + 1) / cfg.warmup_steps, 1.0)
    if cfg.total_steps:
        t = jnp.clip((step - cfg.warmup_steps)
                     / max(cfg.total_steps - cfg.warmup_steps, 1), 0.0, 1.0)
        cos = 0.5 * (1 + jnp.cos(jnp.pi * t))
        lr = lr * (cfg.min_lr_frac + (1 - cfg.min_lr_frac) * cos)
    return lr


def global_norm(tree) -> jnp.ndarray:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in leaves))


def clip_by_global_norm(grads, max_norm: float):
    norm = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return jax.tree_util.tree_map(
        lambda g: (g.astype(jnp.float32) * scale).astype(g.dtype), grads), norm


def adam_update(params, grads, state, cfg: AdamConfig):
    """Returns (new_params, new_state, metrics).  ``grads`` covers the
    trainable leaves (or all, frozen ones then ignored)."""
    params, frozen = partition(params)
    grads = partition(grads)[0]
    step = state["step"] + 1
    if cfg.grad_clip_norm is not None:
        grads, gnorm = clip_by_global_norm(grads, cfg.grad_clip_norm)
    else:
        gnorm = global_norm(grads)
    lr = schedule_lr(cfg, step)
    b1, b2 = cfg.beta1, cfg.beta2
    bc1 = 1 - b1 ** step.astype(jnp.float32)
    bc2 = 1 - b2 ** step.astype(jnp.float32)
    mdt = jnp.dtype(cfg.moment_dtype)

    def upd(p, g, m, v):
        g32 = g.astype(jnp.float32)
        m32 = m.astype(jnp.float32) * b1 + (1 - b1) * g32
        v32 = v.astype(jnp.float32) * b2 + (1 - b2) * jnp.square(g32)
        mhat = m32 / bc1
        vhat = v32 / bc2
        delta = mhat / (jnp.sqrt(vhat) + cfg.eps)
        if cfg.weight_decay:
            delta = delta + cfg.weight_decay * p.astype(jnp.float32)
        new_p = p.astype(jnp.float32) - lr * delta
        return new_p.astype(p.dtype), m32.astype(mdt), v32.astype(mdt)

    flat_p, treedef = jax.tree_util.tree_flatten(params)
    flat_g = jax.tree_util.tree_leaves(grads)
    flat_m = jax.tree_util.tree_leaves(state["m"])
    flat_v = jax.tree_util.tree_leaves(state["v"])
    outs = [upd(p, g, m, v)
            for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
    unf = lambda i: jax.tree_util.tree_unflatten(
        treedef, [o[i] for o in outs])
    new_state = {"step": step, "m": unf(1), "v": unf(2)}
    return join(unf(0), frozen), new_state, {"lr": lr, "grad_norm": gnorm}
