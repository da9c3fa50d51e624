"""Model assembly: init, forward (train/prefill), single-token decode.

The layer stack is a lax.scan over `R = n_layers / len(pattern)` repeats of
the (possibly heterogeneous) pattern super-block, with jax.checkpoint at
super-block granularity.  Block params are stacked over the leading [R] dim.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro.models import attention as attn_mod
from repro.models import cache as cache_mod
from repro.models import mamba as mamba_mod
from repro.models import moe as moe_mod
from repro.models import rwkv as rwkv_mod
from repro.models import shortconv as shortconv_mod
from repro.models.config import FFN, Mixer, ModelConfig
from repro.models.layers import (apply_ffn, embed_features, embed_tokens,
                                 init_embeddings, init_ffn, init_rmsnorm,
                                 rmsnorm, sinusoidal_positions, token_shift,
                                 unembed)
from repro.parallel.sharding import hint


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_layer(key, cfg: ModelConfig, spec) -> Dict[str, Any]:
    ks = jax.random.split(key, 4)
    dt = _dtype(cfg)
    p = {"norm1": init_rmsnorm(cfg.d_model, dt),
         "norm2": init_rmsnorm(cfg.d_model, dt)}
    if spec.mixer == Mixer.ATTENTION:
        p["attn"] = attn_mod.init_attention(ks[0], cfg, dt)
    elif spec.mixer == Mixer.MAMBA:
        p["mamba"] = mamba_mod.init_mamba(ks[0], cfg, dt)
    elif spec.mixer == Mixer.RWKV6:
        p["rwkv"] = rwkv_mod.init_rwkv(ks[0], cfg, dt)
    elif spec.mixer == Mixer.SHORTCONV:
        p["shortconv"] = shortconv_mod.init_shortconv(ks[0], cfg, dt)
    if spec.moe:
        p["moe"] = moe_mod.init_moe(ks[1], cfg, spec.ffn, dt)
    else:
        p["ffn"] = init_ffn(ks[1], cfg, spec.ffn, dt)
    return p


def _init_superblock(key, cfg: ModelConfig) -> Dict[str, Any]:
    ks = jax.random.split(key, len(cfg.pattern))
    return {f"layer{j}": _init_layer(ks[j], cfg, spec)
            for j, spec in enumerate(cfg.pattern)}


def init_params(key, cfg: ModelConfig) -> Dict[str, Any]:
    cfg.validate()
    k_emb, k_blocks = jax.random.split(key)
    R = cfg.n_pattern_repeats
    block_keys = jax.random.split(k_blocks, R)
    blocks = jax.vmap(lambda k: _init_superblock(k, cfg))(block_keys)
    return {
        "embed": init_embeddings(k_emb, cfg, _dtype(cfg)),
        "blocks": blocks,
        "final_norm": init_rmsnorm(cfg.d_model, _dtype(cfg)),
    }


def count_params(params) -> int:
    return sum(x.size for x in jax.tree_util.tree_leaves(params))


# ---------------------------------------------------------------------------
# Embedding of mixed inputs
# ---------------------------------------------------------------------------

def embed_inputs(params, cfg: ModelConfig, inputs: Dict[str, Any]):
    """inputs: {"tokens": [B,S_t]} and/or {"features": [B,S_f,feat]}.

    Features (audio frames / image patches) are prepended to the token
    embeddings — the modality-frontend carve-out per spec."""
    parts = []
    if "features" in inputs and inputs["features"] is not None:
        parts.append(embed_features(params["embed"], cfg, inputs["features"]))
    if "tokens" in inputs and inputs["tokens"] is not None:
        parts.append(embed_tokens(params["embed"], cfg, inputs["tokens"]))
    x = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
    if cfg.is_encoder_only:
        x = x + sinusoidal_positions(x.shape[1], cfg.d_model, x.dtype)[None]
    return x


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------

def _stack_loads(loads):
    """Per-layer loads scanned over the repeats ([R, n_held] each) ->
    [n_moe_layers, n_held] in layer order."""
    return jnp.stack(loads, axis=1).reshape(-1, loads[0].shape[-1])


def _layer_forward(p, cfg: ModelConfig, spec, x, positions, *, long_mode,
                   want_cache, max_cache_len):
    """Returns (x, aux, load, cache_entry_or_None); ``load`` is the
    expert layer's assignments per held expert (None for a dense FFN)."""
    aux = jnp.zeros((), jnp.float32)
    load = entry = None
    h = rmsnorm(p["norm1"], x, cfg.norm_eps)
    if spec.mixer == Mixer.ATTENTION:
        window = cache_mod.effective_window(cfg, spec, long_mode)
        q, k, v = attn_mod.qkv_project(p["attn"], cfg, h, positions)
        q = hint(q, "attn_q")
        k = hint(k, "attn_kv")
        y = attn_mod.multihead_attention(
            q, k, v, positions, positions, causal=cfg.causal, window=window,
            cap=cfg.attn_softcap)
        B, S = h.shape[:2]
        y = y.reshape(B, S, -1) @ p["attn"]["wo"]
        if want_cache:
            L = cache_mod.kv_cache_len(cfg, spec, max_cache_len, long_mode)
            hd = cfg.resolved_head_dim
            ck = jnp.zeros((B, L, cfg.n_kv_heads, hd), _dtype(cfg))
            cv = jnp.zeros_like(ck)
            ck, cv = cache_mod.prefill_kv(ck, cv, k, v, window)
            entry = {"k": ck, "v": cv}
    elif spec.mixer == Mixer.MAMBA:
        if want_cache:
            y, st = mamba_mod.apply_mamba(p["mamba"], cfg, h,
                                          return_state=True)
            entry = st
        else:
            y = mamba_mod.apply_mamba(p["mamba"], cfg, h)
    elif spec.mixer == Mixer.RWKV6:
        if want_cache:
            y, st = rwkv_mod.apply_rwkv(p["rwkv"], cfg, h, return_state=True)
            entry = st
        else:
            y = rwkv_mod.apply_rwkv(p["rwkv"], cfg, h)
    elif spec.mixer == Mixer.SHORTCONV:
        if want_cache:
            y, entry = shortconv_mod.apply_shortconv(
                p["shortconv"], cfg, h, return_state=True)
        else:
            y = shortconv_mod.apply_shortconv(p["shortconv"], cfg, h)
    else:
        raise ValueError(spec.mixer)
    # name the mixer output so the remat policy can save it: recomputing
    # attention/SSM scans in the backward pass doubles their HBM traffic
    # (§Perf jamba iteration)
    y = checkpoint_name(y, "mixer_out")
    x = hint(x + y, "residual")

    h = rmsnorm(p["norm2"], x, cfg.norm_eps)
    if spec.moe:
        y, a, load = moe_mod.apply_moe(p["moe"], cfg, spec.ffn, h)
        aux = aux + a
    else:
        shifted = token_shift(h) if spec.ffn == FFN.RWKV_CHANNEL else None
        y = apply_ffn(p["ffn"], cfg, spec.ffn, h, shifted=shifted)
        if want_cache and spec.ffn == FFN.RWKV_CHANNEL:
            entry = dict(entry or {})
            entry["cm_shift"] = h[:, -1, :]
    x = hint(x + y, "residual")
    return x, aux, load, entry


def forward(params, cfg: ModelConfig, inputs: Dict[str, Any], *,
            long_mode: bool = False, return_cache: bool = False,
            max_cache_len: Optional[int] = None, remat: bool = True,
            remat_policy: str = "save_mixer"):
    """Full-sequence forward. Returns dict with logits [B,S,V], aux_loss,
    ``moe_load`` (MoE models: [n_moe_layers, n_held] token-to-held-expert
    assignments) and (optionally) a decode cache primed with the
    sequence."""
    x = embed_inputs(params, cfg, inputs)
    B, S, _ = x.shape
    positions = jnp.arange(S)
    x = hint(x, "residual")
    max_cache_len = max_cache_len or S

    def superblock(x, block_params):
        aux = jnp.zeros((), jnp.float32)
        entries, loads = {}, []
        for j, spec in enumerate(cfg.pattern):
            x, a, load, entry = _layer_forward(
                block_params[f"layer{j}"], cfg, spec, x, positions,
                long_mode=long_mode, want_cache=return_cache,
                max_cache_len=max_cache_len)
            aux = aux + a
            if load is not None:
                loads.append(load)
            if entry is not None:
                entries[f"layer{j}"] = entry
        return x, (aux, loads, entries)

    if remat and remat_policy == "save_mixer":
        policy = jax.checkpoint_policies.save_only_these_names("mixer_out")
        block_fn = jax.checkpoint(superblock, policy=policy)
    elif remat:
        block_fn = jax.checkpoint(superblock)
    else:
        block_fn = superblock

    def scan_body(x, bp):
        x, out = block_fn(x, bp)
        return x, out

    x, (auxes, loads, entries) = jax.lax.scan(scan_body, x,
                                              params["blocks"])
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = hint(unembed(params["embed"], cfg, x), "logits")
    out = {"logits": logits, "aux_loss": jnp.sum(auxes), "hidden": x}
    if loads:
        out["moe_load"] = _stack_loads(loads)
    if return_cache:
        cache = {"blocks": entries, "pos": jnp.asarray(S, jnp.int32)}
        out["cache"] = cache
    return out


# ---------------------------------------------------------------------------
# Chunked prefill (bounded prompt-ingestion steps against a live cache)
# ---------------------------------------------------------------------------

def _layer_prefill_chunk(p, cfg: ModelConfig, spec, x, cache_entry, pos,
                         valid, n_valid, *, long_mode):
    """x: [B,C,d] — one bounded prompt chunk per slot.  Returns
    (x, new_cache_entry).

    `pos` [B] is each slot's chunk-start cursor; `valid` [B,C] marks the
    real tokens (a slot's final chunk may be partial; a slot with
    n_valid == 0 passes through with its state untouched).  Attention
    runs against the *pre-write* cache concatenated with the fresh chunk
    k/v — causal-within-chunk plus the ragged-cache bias over per-row
    absolute positions — then writes the chunk at per-row cursors, so a
    sequence of chunk steps reproduces the one-shot prefill exactly
    (ring windows included: every in-window position is still resident
    when the chunk that needs it arrives)."""
    new_entry = {}
    B, C = x.shape[:2]
    q_pos = jnp.asarray(pos, jnp.int32)[:, None] + jnp.arange(C)     # [B,C]
    h = rmsnorm(p["norm1"], x, cfg.norm_eps)
    if spec.mixer == Mixer.ATTENTION:
        window = cache_mod.effective_window(cfg, spec, long_mode)
        q, k, v = attn_mod.qkv_project(p["attn"], cfg, h, q_pos)
        ck0, cv0 = cache_entry["k"], cache_entry["v"]
        L = ck0.shape[1]
        # cache contents *before* this chunk (cursor pos-1); pos == 0
        # rows see an all-invalid cache
        k_pos_c, valid_c = cache_mod.ring_slot_positions(L, window, pos - 1)
        y = attn_mod.multihead_attention(
            q, jnp.concatenate([ck0.astype(k.dtype), k], axis=1),
            jnp.concatenate([cv0.astype(v.dtype), v], axis=1),
            q_pos, jnp.concatenate([k_pos_c, q_pos], axis=1),
            causal=True, window=window, cap=cfg.attn_softcap,
            k_valid=jnp.concatenate([valid_c, valid], axis=1))
        ck, cv = cache_mod.write_kv(ck0, cv0, k, v, pos, window, valid=valid)
        new_entry.update(k=ck, v=cv)
        y = y.reshape(B, C, -1) @ p["attn"]["wo"]
    elif spec.mixer == Mixer.MAMBA:
        st = {kk: cache_entry[kk] for kk in ("conv", "ssm")}
        y, st_new = mamba_mod.apply_mamba(p["mamba"], cfg, h, state=st,
                                          return_state=True, valid=valid)
        new_entry.update(st_new)
    elif spec.mixer == Mixer.RWKV6:
        st = {"tm_shift": cache_entry["tm_shift"], "wkv": cache_entry["wkv"]}
        y, st_new = rwkv_mod.apply_rwkv(p["rwkv"], cfg, h, state=st,
                                        return_state=True, valid=valid)
        new_entry.update(st_new)
    else:
        raise ValueError(spec.mixer)
    x = x + y

    h = rmsnorm(p["norm2"], x, cfg.norm_eps)
    if spec.moe:
        y, _, _ = moe_mod.apply_moe(p["moe"], cfg, spec.ffn, h)
    else:
        if spec.ffn == FFN.RWKV_CHANNEL:
            shifted = jnp.concatenate(
                [cache_entry["cm_shift"][:, None, :].astype(h.dtype),
                 h[:, :-1]], axis=1)
            y = apply_ffn(p["ffn"], cfg, spec.ffn, h, shifted=shifted)
            idx = jnp.clip(n_valid - 1, 0, C - 1)
            new_entry["cm_shift"] = jnp.take_along_axis(
                h, idx[:, None, None], axis=1)[:, 0]
        else:
            y = apply_ffn(p["ffn"], cfg, spec.ffn, h)
    return x + y, new_entry


def prefill_chunk_step(params, cfg: ModelConfig, tokens, cache, *,
                       n_valid, long_mode: bool = False):
    """One bounded prompt-ingestion step: tokens [B, C] int32 chunks.

    ``cache["pos"]`` is a [B] vector of per-slot prefill cursors (tokens
    already ingested); ``n_valid`` [B] counts the real tokens in each
    row's chunk (0 = slot idle this round: its cache entry passes
    through untouched — the caller merges rows, see genserve).  Returns
    (last_logits [B, V], new_cache): `last_logits` is each row's logit
    at its last valid chunk token — the first-token sampling point when
    a slot's final chunk lands — and `new_cache["pos"]` advances by
    ``n_valid``.  A sequence of chunk steps over a prompt is
    numerically equivalent to the one-shot ``forward(return_cache)``
    prefill (the genserve parity tests pin this)."""
    assert not cfg.is_encoder_only, "encoder-only models have no decode path"
    pos = jnp.asarray(cache["pos"], jnp.int32)
    B, C = tokens.shape
    n_valid = jnp.asarray(n_valid, jnp.int32)
    valid = jnp.arange(C)[None, :] < n_valid[:, None]
    x = embed_tokens(params["embed"], cfg, tokens)
    x = hint(x, "decode_residual")

    def scan_body(x, inp):
        bp, centry = inp
        new_entries = {}
        for j, spec in enumerate(cfg.pattern):
            x, ne = _layer_prefill_chunk(bp[f"layer{j}"], cfg, spec, x,
                                         centry[f"layer{j}"], pos, valid,
                                         n_valid, long_mode=long_mode)
            new_entries[f"layer{j}"] = ne
        return x, new_entries

    x, new_blocks = jax.lax.scan(scan_body, x,
                                 (params["blocks"], cache["blocks"]))
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    idx = jnp.clip(n_valid - 1, 0, C - 1)
    h_last = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
    logits = unembed(params["embed"], cfg, h_last)
    new_cache = {"blocks": new_blocks, "pos": pos + n_valid}
    return logits, new_cache


# ---------------------------------------------------------------------------
# Speculative verify (one batched step over a candidate chunk, no commit)
# ---------------------------------------------------------------------------

def _layer_verify_chunk(p, cfg: ModelConfig, spec, x, cache_entry, pos, *,
                        long_mode):
    """x: [B,C,d] — the candidate chunk [t0, d_1..d_{C-1}] per slot.
    Returns (x, {"k","v"} fresh chunk projections, *uncommitted*).

    Identical attention pattern to `_layer_prefill_chunk` (pre-write
    cache concat fresh chunk, causal-within-chunk, ragged-cache bias at
    per-row cursors) but the cache is left untouched: the caller learns
    the accept length from the returned logits and commits only the
    accepted prefix via `cache.write_kv` with a short validity mask —
    rejected speculative k/v never lands, so ring windows stay exact."""
    if spec.mixer != Mixer.ATTENTION:
        raise ValueError(
            "speculative verify requires attention-only targets "
            f"(got {spec.mixer}: recurrent state cannot roll back "
            "rejected tokens)")
    if spec.ffn == FFN.RWKV_CHANNEL:
        raise ValueError("speculative verify cannot roll back the "
                         "rwkv_channel cm_shift state")
    B, C = x.shape[:2]
    q_pos = jnp.asarray(pos, jnp.int32)[:, None] + jnp.arange(C)     # [B,C]
    h = rmsnorm(p["norm1"], x, cfg.norm_eps)
    window = cache_mod.effective_window(cfg, spec, long_mode)
    q, k, v = attn_mod.qkv_project(p["attn"], cfg, h, q_pos)
    ck0, cv0 = cache_entry["k"], cache_entry["v"]
    L = ck0.shape[1]
    k_pos_c, valid_c = cache_mod.ring_slot_positions(L, window, pos - 1)
    chunk_valid = jnp.ones((B, C), bool)
    y = attn_mod.multihead_attention(
        q, jnp.concatenate([ck0.astype(k.dtype), k], axis=1),
        jnp.concatenate([cv0.astype(v.dtype), v], axis=1),
        q_pos, jnp.concatenate([k_pos_c, q_pos], axis=1),
        causal=True, window=window, cap=cfg.attn_softcap,
        k_valid=jnp.concatenate([valid_c, chunk_valid], axis=1))
    x = x + y.reshape(B, C, -1) @ p["attn"]["wo"]

    h = rmsnorm(p["norm2"], x, cfg.norm_eps)
    if spec.moe:
        y, _, _ = moe_mod.apply_moe(p["moe"], cfg, spec.ffn, h)
    else:
        y = apply_ffn(p["ffn"], cfg, spec.ffn, h)
    return x + y, {"k": k, "v": v}


def verify_chunk_step(params, cfg: ModelConfig, tokens, cache, *,
                      long_mode: bool = False):
    """One batched target step over a [B, C] candidate chunk
    ([t0, d_1..d_{C-1}] at each row's ``cache["pos"]`` cursor).

    Returns (logits [B, C, V], deltas): logits at *every* chunk
    position (position j scores the token following the candidate
    prefix up to j — the accept test and the bonus/corrected draw both
    read from here), and ``deltas`` — the per-layer fresh-chunk
    ``{"k","v"}`` projections, NOT written to the cache.  After the
    caller computes the accept length it commits the accepted prefix
    with ``cache.write_kv(ck, cv, deltas.k, deltas.v, pos, window,
    valid=<short mask>)`` — the same variable-length chunk-write
    machinery chunked prefill uses.  Requires an attention-only config
    (see `cache.supports_speculative_target`)."""
    assert not cfg.is_encoder_only, "encoder-only models have no decode path"
    pos = jnp.asarray(cache["pos"], jnp.int32)
    x = embed_tokens(params["embed"], cfg, tokens)
    x = hint(x, "decode_residual")

    def scan_body(x, inp):
        bp, centry = inp
        deltas = {}
        for j, spec in enumerate(cfg.pattern):
            x, d = _layer_verify_chunk(bp[f"layer{j}"], cfg, spec, x,
                                       centry[f"layer{j}"], pos,
                                       long_mode=long_mode)
            deltas[f"layer{j}"] = d
        return x, deltas

    x, deltas = jax.lax.scan(scan_body, x,
                             (params["blocks"], cache["blocks"]))
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = unembed(params["embed"], cfg, x)
    return logits, deltas


# ---------------------------------------------------------------------------
# Decode (single token, serve_step)
# ---------------------------------------------------------------------------

def _split_entry(spec, entry):
    """(carried, scanned) leaves of one layer's decode cache entry: the
    layer scan carries attention k/v whole ([R, B, L, KV, hd], written
    one token in place); every other leaf is whole-state recurrence and
    goes through the scan per repeat."""
    carried, scanned = {}, {}
    for key, leaf in entry.items():
        kv = spec.mixer == Mixer.ATTENTION and key in ("k", "v")
        (carried if kv else scanned)[key] = leaf
    return carried, scanned


def _layer_decode(p, cfg: ModelConfig, spec, x, cache_entry, pos, layer, *,
                  long_mode):
    """x: [B,1,d]. Returns (x, new_cache_entry, load): ``load`` as in
    ``_layer_forward``.

    `cache_entry` holds this layer's recurrent leaves as per-repeat
    slices and, for attention, the k/v stacks of every repeat
    ([R, B, L, KV, hd]); `layer` is the repeat index into them.  The
    token's k/v are written into the stacks in place and attention reads
    repeat `layer` of the written stacks.

    `pos` is a scalar (all rows decode the same position — the
    single-sequence / full-batch rollout path) or a [B] vector of
    per-row cache positions (batched wave decode: every slot keeps its
    own RoPE phase, ring offset and validity mask)."""
    new_entry = {}
    per_slot = jnp.ndim(pos) > 0
    q_pos = (jnp.asarray(pos, jnp.int32)[:, None] if per_slot
             else jnp.full((1,), pos, jnp.int32))      # [B,1] or [1]
    h = rmsnorm(p["norm1"], x, cfg.norm_eps)
    if spec.mixer == Mixer.ATTENTION:
        window = cache_mod.effective_window(cfg, spec, long_mode)
        q, k, v = attn_mod.qkv_project(p["attn"], cfg, h, q_pos)
        ck, cv = cache_mod.write_token_kv(cache_entry["k"], cache_entry["v"],
                                          layer, k, v, pos, window)
        new_entry.update(k=ck, v=cv)
        L = ck.shape[2]
        k_pos, valid = cache_mod.ring_slot_positions(L, window, pos)
        y = attn_mod.multihead_attention(
            q, jax.lax.dynamic_index_in_dim(ck, layer, keepdims=False),
            jax.lax.dynamic_index_in_dim(cv, layer, keepdims=False),
            q_pos, k_pos,
            causal=True, window=window, cap=cfg.attn_softcap, k_valid=valid)
        y = y.reshape(x.shape[0], 1, -1) @ p["attn"]["wo"]
    elif spec.mixer == Mixer.MAMBA:
        st = {k: cache_entry[k] for k in ("conv", "ssm")}
        y, st_new = mamba_mod.apply_mamba(p["mamba"], cfg, h, state=st,
                                          return_state=True)
        new_entry.update(st_new)
    elif spec.mixer == Mixer.RWKV6:
        st = {"tm_shift": cache_entry["tm_shift"], "wkv": cache_entry["wkv"]}
        y, st_new = rwkv_mod.apply_rwkv(p["rwkv"], cfg, h, state=st,
                                        return_state=True)
        new_entry.update(st_new)
    elif spec.mixer == Mixer.SHORTCONV:
        y, st_new = shortconv_mod.apply_shortconv(
            p["shortconv"], cfg, h, state={"conv": cache_entry["conv"]},
            return_state=True)
        new_entry.update(st_new)
    x = x + y

    load = None
    h = rmsnorm(p["norm2"], x, cfg.norm_eps)
    if spec.moe:
        y, _, load = moe_mod.apply_moe(p["moe"], cfg, spec.ffn, h)
    else:
        if spec.ffn == FFN.RWKV_CHANNEL:
            shifted = cache_entry["cm_shift"][:, None, :].astype(h.dtype)
            y = apply_ffn(p["ffn"], cfg, spec.ffn, h, shifted=shifted)
            new_entry["cm_shift"] = h[:, -1, :]
        else:
            y = apply_ffn(p["ffn"], cfg, spec.ffn, h)
    return x + y, new_entry, load


def decode_step(params, cfg: ModelConfig, tokens, cache, *,
                long_mode: bool = False, with_load: bool = False):
    """tokens: [B, 1] int32. Returns (logits [B, V], new_cache), and with
    ``with_load`` the step's ``moe_load`` as ``forward`` gives it.

    ``cache["pos"]`` is a scalar (every row at the same position) or a
    [B] vector of per-row positions — the natively batched fast path the
    genserve wave decode uses: ragged KV lengths, RoPE phases and
    ring-window validity are expressed per-row, so one fused attention
    call covers a whole wave of recycled slots.

    The layer scan carries the attention k/v stacks ([R, B, L, KV, hd])
    and writes each layer's token into them in place, so no layer's
    cache is sliced out and stacked back each step; recurrent state is
    scanned per repeat."""
    assert not cfg.is_encoder_only, "encoder-only models have no decode step"
    pos = cache["pos"]
    x = embed_tokens(params["embed"], cfg, tokens)
    x = hint(x, "decode_residual")

    names = [f"layer{j}" for j in range(len(cfg.pattern))]

    def scan_body(carry, inp):
        x, kv = carry
        bp, state, r = inp
        kv, new_state, loads = dict(kv), {}, []
        for name, spec in zip(names, cfg.pattern):
            x, ne, load = _layer_decode(bp[name], cfg, spec, x,
                                        {**kv[name], **state[name]}, pos, r,
                                        long_mode=long_mode)
            kv[name], new_state[name] = _split_entry(spec, ne)
            if load is not None:
                loads.append(load)
        return (x, kv), (new_state, loads)

    kv, state = {}, {}
    for name, spec in zip(names, cfg.pattern):
        kv[name], state[name] = _split_entry(spec, cache["blocks"][name])
    (x, kv), (state, loads) = jax.lax.scan(
        scan_body, (x, kv),
        (params["blocks"], state, jnp.arange(cfg.n_pattern_repeats)))
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = unembed(params["embed"], cfg, x)[:, 0]
    new_blocks = {name: {**kv[name], **state[name]} for name in kv}
    new_cache = {"blocks": new_blocks, "pos": pos + 1}
    if with_load:
        return logits, new_cache, _stack_loads(loads)
    return logits, new_cache
