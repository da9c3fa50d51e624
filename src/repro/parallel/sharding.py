"""Sharding rules + activation sharding hints.

Two pieces:

1. ``hint(x, name)`` — models call this on named intermediate activations
   (residual stream, mamba inner, moe buffer, logits...).  Outside
   ``use_hints`` it is the identity, so all models run unchanged on a
   single CPU device.  Inside ``use_hints(rules)`` each named activation
   gets a ``with_sharding_constraint`` — this is where the distribution
   schedule (and the §Perf iterations) plug in without touching model
   code.

2. ``param_tree_specs(params)`` — maps a parameter pytree to
   PartitionSpecs by parameter-name pattern (TP over ``model``, FSDP over
   ``data``); ``named_shardings`` turns them into per-mesh
   ``NamedSharding``s, sanitized so non-dividing axes replicate.

Plan → mesh → sharding flow (how the engine uses this module):

- the scheduler's ``Plan`` assigns each task (dp, pp, tp) and plan
  device ids; ``engine.placement.build_placements`` folds those onto the
  host's real devices (group-aware: disjoint plan groups get disjoint
  real device sets when the host is large enough) and builds one
  ``Mesh(("data", "model"))`` per task with ``model = gcd(tp, n)``.
- ``TaskPlacement.param_shardings`` runs ``param_tree_specs`` →
  ``named_shardings`` on that mesh; ``rl.trainer`` commits each task's
  state (params + optimizer moments) onto its owner's shardings with
  ``device_put`` and jits the step functions with explicit
  in/out_shardings (jit refuses mismatched committed arrays, so the
  device_put is load-bearing, not an optimization).
- batches are committed with ``TaskPlacement.shard_batch`` (leading dim
  over ``data``); step bodies trace inside ``use_hints(rules)`` under
  ``with mesh:`` so ``hint`` constraints resolve (a bare
  ``PartitionSpec`` constraint needs an ambient mesh at trace time).
- train → gen weight publication is an explicit ``device_put`` onto the
  gen placement's shardings (``rl.pipeline.sync_actor_weights``), priced
  by the cost model's sync term.
"""
from __future__ import annotations

import contextlib
import re
import threading
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

_local = threading.local()


def _rules() -> Optional[Dict[str, P]]:
    return getattr(_local, "rules", None)


@contextlib.contextmanager
def use_hints(rules: Dict[str, P]):
    prev = _rules()
    _local.rules = rules
    try:
        yield
    finally:
        _local.rules = prev


def hint(x, name: str):
    rules = _rules()
    if rules is None or name not in rules:
        return x
    spec = rules[name]
    # trim spec to rank
    spec = P(*(list(spec) + [None] * x.ndim)[: x.ndim])
    return jax.lax.with_sharding_constraint(x, spec)


# ---------------------------------------------------------------------------
# Activation-sharding rule sets
# ---------------------------------------------------------------------------

def default_activation_rules(*, data_axes=("data",), model_axis="model",
                             seq_shard: bool = True) -> Dict[str, P]:
    """Baseline schedule: batch over data axes, TP over model axis,
    sequence-parallel residual stream (S over model) when seq_shard."""
    da = data_axes if len(data_axes) > 1 else data_axes[0]
    m = model_axis
    rules = {
        # [B, S, d]
        "residual": P(da, m, None) if seq_shard else P(da, None, None),
        # [B, S, H, hd] after qkv projection — heads over model
        "attn_q": P(da, None, m, None),
        "attn_kv": P(da, None, m, None),
        # [B, S, f]
        "ffn_hidden": P(da, None, m),
        # [B, S, di] mamba inner — channels over model (recurrence is
        # elementwise in di, so this shards the scan with zero collectives)
        "mamba_inner": P(da, None, m),
        # [B, S, H, hd, hd']-ish rwkv head state dims: heads over model
        "rwkv_heads": P(da, None, m, None),
        # [Gn, E, C, d] moe dispatch buffer — groups over data; experts
        # over model in EP mode
        "moe_buffer": P(da, None, None, None),
        "moe_buffer_ep": P(da, m, None, None),
        # expert weights at USE time (storage stays FSDP-sharded):
        #  - EP mode (E >= model axis): experts over model, ffn unsharded —
        #    the combine einsum contracts sharded-E => the TP all-reduce
        #    carries token-sized tensors, not the capacity buffer
        #  - TP mode (small E): ffn dim over model
        "moe_w_in_ep": P(m, None, None),   # [E, d, f]
        "moe_w_out_ep": P(m, None, None),  # [E, f, d]
        "moe_w_in": P(None, None, m),
        "moe_w_out": P(None, m, None),
        # [Gn, g, E, C] dispatch/combine one-hots in EP mode
        "moe_onehot_ep": P(da, None, m, None),
        # [Gn, E, C, f] expert hidden activations: 2D-sharded (GSPMD left
        # to itself gathers the group dim to apply the ffn sharding)
        "moe_hidden": P(da, None, None, m),
        "moe_hidden_ep": P(da, m, None, None),
        # [B, S, V]
        "logits": P(da, None, m),
        # decode: [B, 1, d]
        "decode_residual": P(da, None, None),
        # [B, S_cache, KV, hd]
        "kv_cache": P(da, None, m, None),
        # long-context decode: cache sequence-sharded over data (batch=1)
        "kv_cache_seqshard": P(None, da, m, None),
    }
    return rules


# ---------------------------------------------------------------------------
# Parameter partition specs
# ---------------------------------------------------------------------------

# pattern -> spec builder keyed by array rank; leading stacked "repeats"
# dimension (from scan-over-blocks) is added automatically.
_PARAM_RULES = [
    # embeddings / unembedding
    (r"embed/tokens$", lambda r: P("model", "data")),
    (r"embed/lm_head$", lambda r: P("data", "model")),
    (r"embed/feature_proj$", lambda r: P(None, "model")),
    # attention
    (r"wq$|wkv$", lambda r: P("data", "model")),
    (r"wo$", lambda r: P("model", "data")),
    # dense ffn
    (r"w_gate$|w_up$|w_key$", lambda r: P("data", "model")),
    (r"w_down$|w_value$", lambda r: P("model", "data")),
    (r"w_recept$", lambda r: P("data", "model")),
    # moe experts: expert dim over data (expert parallelism), TP inside
    (r"experts/w_gate$|experts/w_up$|experts/w_key$",
     lambda r: P("data", None, "model")),
    (r"experts/w_down$|experts/w_value$", lambda r: P("data", "model", None)),
    (r"router$", lambda r: P(None, None)),
    # mamba
    (r"in_proj$", lambda r: P("data", "model")),
    (r"out_proj$", lambda r: P("model", "data")),
    (r"x_proj$", lambda r: P("model", None)),
    (r"dt_proj$", lambda r: P(None, "model")),
    (r"conv_w$", lambda r: P(None, "model")),
    (r"conv_b$|dt_bias$|D$", lambda r: P("model")),
    (r"A_log$", lambda r: P("model", None)),
    # rwkv time mix
    (r"w_r$|w_k$|w_v$|w_g$", lambda r: P("data", "model")),
    (r"w_o$", lambda r: P("model", "data")),
    (r"decay_w1$", lambda r: P("model", None)),
    (r"decay_w2$", lambda r: P(None, "model")),
]


def path_str(path) -> str:
    parts = []
    for p in path:
        if isinstance(p, jax.tree_util.DictKey):
            parts.append(str(p.key))
        else:
            parts.append(str(p))
    return "/".join(parts)


def spec_for_param(path: str, arr, *, stacked: bool) -> P:
    """PartitionSpec for one parameter; `stacked` = leading scan-repeat dim."""
    rank = arr.ndim - (1 if stacked else 0)
    for pat, builder in _PARAM_RULES:
        if re.search(pat, path):
            spec = builder(rank)
            spec = P(*(list(spec) + [None] * rank)[:rank])
            break
    else:
        # default: replicate small params (norm scales, biases, mixes)
        spec = P(*([None] * rank))
    if stacked:
        spec = P(None, *spec)
    return spec


def param_tree_specs(params) -> "jax.tree_util.PyTreeDef":
    """PartitionSpec pytree mirroring the param tree. Leaves under
    'blocks/' carry a leading stacked repeat dim."""
    def fn(path, leaf):
        p = path_str(path)
        return spec_for_param(p, leaf, stacked=p.startswith("blocks/"))
    return jax.tree_util.tree_map_with_path(fn, params)


def sanitize_spec(spec: P, shape, mesh) -> P:
    """jit in_/out_shardings demand exact divisibility (GSPMD pads only
    internal constraints).  Drop axis assignments whose product does not
    divide the dimension; try axis subsets first for tuple entries."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    out = []
    for d, entry in enumerate(list(spec)[:len(shape)]):
        if entry is None:
            out.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        # greedily keep a prefix of axes that divides the dim
        kept = []
        prod = 1
        for a in axes:
            if shape[d] % (prod * sizes[a]) == 0:
                kept.append(a)
                prod *= sizes[a]
        if not kept:
            out.append(None)
        elif len(kept) == 1:
            out.append(kept[0])
        else:
            out.append(tuple(kept))
    return P(*out)


def named_shardings(mesh, spec_tree, shape_tree=None):
    from jax.sharding import NamedSharding
    if shape_tree is None:
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), spec_tree,
            is_leaf=lambda s: isinstance(s, P))
    return jax.tree_util.tree_map(
        lambda s, x: NamedSharding(mesh, sanitize_spec(s, x.shape, mesh)),
        spec_tree, shape_tree,
        is_leaf=lambda s: isinstance(s, P))
