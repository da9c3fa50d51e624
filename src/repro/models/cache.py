"""Decode-time state: KV caches (full + ring-buffer windowed), SSM states,
RWKV states.  Cache leaves for the scanned layer stack carry a leading
[R] repeats dim; decode carries the attention k/v stacks through its
layer scan whole and writes each token in place (``write_token_kv``),
while recurrent state goes through the scan per repeat.

Every block-cache leaf is laid out ``[R, B, ...]`` with the batch (decode
*slot*) dim at axis 1; ``gather_slots`` / ``scatter_slots`` exploit this
to move whole per-slot cache rows in and out, which is what the
continuous-batching engine (``repro.genserve``) uses to recycle decode
slots: a retired slot's rows are simply overwritten by the freshly
prefilled rows of the next request.

Paged layout (``init_paged_cache`` + the ``paged_*`` helpers): attention
k/v leaves become a shared *page pool* ``[R, n_pages, page_size, KV, hd]``
addressed through a per-slot block table ``[B, max_pages]`` (entry j of a
row maps cache positions ``[j*page_size, (j+1)*page_size)``; the sentinel
value ``n_pages`` marks an unmapped entry).  ``paged_view`` gathers each
slot's pages into exactly the contiguous ``[R, B, L, KV, hd]`` view the
existing decode / prefill-chunk programs consume — everything below the
gather (batched-jnp attention, the Pallas flash-decode kernel, ring
windows, GQA) is untouched — and ``paged_update_decode`` /
``paged_update_chunk`` scatter just the freshly written token k/v back
into the pool (an exact delta: values are extracted from the contiguous
view *after* the decode or chunk write applied its ring/clamp semantics,
so a paged run is token-for-token the contiguous run).  Recurrent (Mamba/RWKV)
state and ``cm_shift`` stay per-slot ``[R, B, ...]``: O(1) state has no
pages to share.  An identity block table (``identity_block_table``) makes
the pool a mere reshape of today's contiguous layout — the exact-parity
fallback.  Page refcounts, copy-on-write and prefix-cache admission are
host-side concerns (``repro.genserve.pagepool``); this module only
provides the device-side indirection (including ``copy_pages`` for the
COW copies the host schedules).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import FFN, Mixer, ModelConfig
from repro.models import mamba as mamba_mod
from repro.models import rwkv as rwkv_mod


def effective_window(cfg: ModelConfig, spec, long_mode: bool) -> Optional[int]:
    """Window for an attention layer; long mode caps global layers."""
    if spec.window is not None:
        return spec.window
    if long_mode and cfg.long_mode_window is not None:
        return cfg.long_mode_window
    return None


def kv_cache_len(cfg: ModelConfig, spec, max_seq: int,
                 long_mode: bool) -> int:
    w = effective_window(cfg, spec, long_mode)
    return min(w, max_seq) if w is not None else max_seq


def init_cache(cfg: ModelConfig, batch: int, max_seq: int, *,
               long_mode: bool = False, dtype=jnp.bfloat16):
    """Cache pytree: {"blocks": {"layer{j}": leaves [R, B, ...]}, "pos": i32}."""
    R = cfg.n_pattern_repeats
    hd = cfg.resolved_head_dim
    blocks = {}
    for j, spec in enumerate(cfg.pattern):
        if spec.mixer == Mixer.ATTENTION:
            L = kv_cache_len(cfg, spec, max_seq, long_mode)
            layer = {
                "k": jnp.zeros((R, batch, L, cfg.n_kv_heads, hd), dtype),
                "v": jnp.zeros((R, batch, L, cfg.n_kv_heads, hd), dtype),
            }
        elif spec.mixer == Mixer.MAMBA:
            st = mamba_mod.init_mamba_state(cfg, batch)
            layer = {k: jnp.broadcast_to(v, (R,) + v.shape)
                     for k, v in st.items()}
        elif spec.mixer == Mixer.RWKV6:
            st = rwkv_mod.init_rwkv_state(cfg, batch)
            layer = {k: jnp.broadcast_to(v, (R,) + v.shape)
                     for k, v in st.items()}
        else:
            raise ValueError(spec.mixer)
        if spec.ffn.value == "rwkv_channel":
            layer["cm_shift"] = jnp.zeros((R, batch, cfg.d_model), dtype)
        blocks[f"layer{j}"] = layer
    return {"blocks": blocks, "pos": jnp.zeros((), jnp.int32)}


def _slot_axes_mask(mask, leaf):
    """Broadcast a [B]-shaped slot mask over a [R, B, ...] cache leaf."""
    return mask.reshape((1, mask.shape[0]) + (1,) * (leaf.ndim - 2))


def gather_slots(blocks, idx):
    """Extract per-slot cache rows: leaves [R, B, ...] -> [R, len(idx), ...].

    `idx` is an int array of slot indices; works under jit (idx traced)."""
    return jax.tree_util.tree_map(lambda l: jnp.take(l, idx, axis=1), blocks)


def scatter_slots(dst_blocks, src_blocks, slot_mask):
    """Overwrite slot rows of `dst_blocks` with `src_blocks` where
    `slot_mask` [B] is True.  Both pytrees have leaves [R, B, ...]; this
    is the whole-row replacement the genserve engine performs at prefill
    injection, so no stale KV/SSM state from the previous occupant can
    leak into a recycled slot."""
    return jax.tree_util.tree_map(
        lambda dst, src: jnp.where(_slot_axes_mask(slot_mask, dst),
                                   src.astype(dst.dtype), dst),
        dst_blocks, src_blocks)


def zero_slots(blocks, slot_mask):
    """Zero the cache rows of slots where `slot_mask` [B] is True.

    Chunked admission starts a slot's prefill from zero-initialized
    state (recurrent mixers accumulate chunk by chunk), so a freshly
    installed slot must not inherit its previous occupant's SSM/RWKV
    state or cm_shift — the KV rows are zeroed too for hygiene (their
    stale contents are already masked by position validity)."""
    return jax.tree_util.tree_map(
        lambda l: jnp.where(_slot_axes_mask(slot_mask, l),
                            jnp.zeros((), l.dtype), l),
        blocks)


def ring_slot_positions(cache_len: int, window: Optional[int], pos):
    """Absolute position stored in each cache slot at decode step `pos`.

    Full cache (window None): slot i holds position i (valid if i <= pos).
    Ring cache: slot i holds the largest p' <= pos with p' % W == i.

    `pos` may be a scalar (shared position, returns [L]) or a [B] vector
    of per-slot positions (batched decode, returns [B, L])."""
    idx = jnp.arange(cache_len)
    p = jnp.asarray(pos, jnp.int32)[..., None]      # [1] or [B, 1]
    if window is None:
        valid = idx <= p
        k_pos = jnp.broadcast_to(idx, valid.shape)
    else:
        W = cache_len
        k_pos = p - ((p - idx) % W)
        valid = k_pos >= 0
    return k_pos, valid


def token_slot(pos, cache_len: int, window: Optional[int]):
    """Cache slot a single token at `pos` (scalar or [B]) is written to:
    ring caches wrap at pos % L, full caches clamp to the last slot."""
    if window is not None:
        return pos % cache_len
    return jnp.minimum(pos, cache_len - 1)


def write_token_kv(cache_k, cache_v, layer, k_new, v_new, pos,
                   window: Optional[int]):
    """Write one decode token's k/v into layer `layer` of the stacked
    caches, in place.

    cache_k: [R, B, L, KV, hd]; k_new: [B, 1, KV, hd]; `layer` is the
    (traced) repeat index.  Only the [B, 1, KV, hd] token is written —
    no per-layer [B, L, KV, hd] slice is copied out or back — at the
    slot ``write_kv`` would use: a scalar `pos` is one
    dynamic-update-slice, a [B] `pos` a per-row scatter.

    The write goes through the [R, B, KV, L, hd] view of the stack.
    Written in the [R, B, L, KV, hd] view, the token (laid out as the
    k/v projection leaves it) pulls the whole stack into an (L, KV, B)
    layout, and the TPU compiler then copies every layer's slice into
    the layout attention reads; through this view the stack keeps
    attention's layout and attention reads it in place."""
    L = cache_k.shape[2]
    p = jnp.asarray(pos, jnp.int32)
    slot = token_slot(p, L, window)

    def put(cache, new):
        view = jnp.swapaxes(cache, 2, 3)                 # [R, B, KV, L, hd]
        new = jnp.swapaxes(new, 1, 2).astype(cache.dtype)  # [B, KV, 1, hd]
        if p.ndim == 0:
            view = jax.lax.dynamic_update_slice(view, new[None],
                                                (layer, 0, 0, slot, 0))
        else:
            rows = jnp.arange(new.shape[0])
            view = view.at[layer, rows, :, slot].set(new[:, :, 0])
        return jnp.swapaxes(view, 2, 3)

    return put(cache_k, k_new), put(cache_v, v_new)


def write_kv(cache_k, cache_v, k_new, v_new, pos, window: Optional[int],
             valid=None):
    """Write a chunk of Sq consecutive tokens' k/v starting at `pos`.

    cache_k: [B, L, KV, hd]; k_new: [B, Sq, KV, hd].  `pos` is a scalar
    (all rows write at the same start position) or a [B] vector of
    per-row start positions (batched wave decode / chunked prefill:
    every slot writes at its own cursor — token j of row b lands at
    absolute position pos[b] + j).  `valid` optionally masks individual
    chunk tokens ([B, Sq] bool): invalid tokens leave the cache
    untouched (the chunked-prefill partial-last-chunk case).

    Ring caches (window not None) wrap at slot p % L; when a chunk spans
    more than one lap of the ring, only the latest token per slot
    survives (matching sequential writes).  Full caches clamp
    out-of-range positions to the last slot (the single-token decode
    guard), keeping the first such token — for a whole-sequence write
    this is exactly the keep-first-L truncation of the former
    ``prefill_kv`` special case (Sq == S, pos == 0), which this
    function now subsumes."""
    B, C = k_new.shape[:2]
    L = cache_k.shape[1]
    p = jnp.asarray(pos)
    if p.ndim == 0 and valid is None:
        # statically-known scalar start (the one-shot prefill and any
        # within-capacity chunk): a contiguous slice update beats the
        # general scatter — take it whenever the chunk provably neither
        # overflows the cache nor wraps the ring
        try:
            start = int(p)
        except (TypeError, jax.errors.TracerIntegerConversionError):
            start = None
        if start is not None:
            if window is None and start + C <= L:
                slot0 = start
            elif window is not None and start % L + C <= L:
                slot0 = start % L
            else:
                slot0 = None
            if slot0 is not None:
                cache_k = jax.lax.dynamic_update_slice(
                    cache_k, k_new.astype(cache_k.dtype), (0, slot0, 0, 0))
                cache_v = jax.lax.dynamic_update_slice(
                    cache_v, v_new.astype(cache_v.dtype), (0, slot0, 0, 0))
                return cache_k, cache_v
    positions = jnp.broadcast_to(
        jnp.asarray(p, jnp.int32).reshape((-1, 1)) + jnp.arange(C), (B, C))
    if valid is None:
        keep = jnp.ones((B, C), bool)
    else:
        keep = valid
    if window is not None:
        # within-chunk ring overwrites: a token is superseded when a
        # later valid token maps to the same slot (positions congruent
        # mod L) — drop it so scatter order cannot matter
        last = jnp.max(jnp.where(keep, positions, -1), axis=1, keepdims=True)
        keep = keep & (positions + L > last)
        slot = positions % L
    else:
        # full cache: clamp past-the-end positions to the last slot and
        # keep only the first such token per row (sequentially, later
        # clamped writes would land on top — but the one-shot prefill
        # semantics this subsumes keep the first L tokens)
        over = keep & (positions >= L - 1)
        first_over = jnp.min(jnp.where(over, positions, 2 ** 30), axis=1,
                             keepdims=True)
        keep = keep & ((positions < L - 1) | (positions == first_over))
        slot = jnp.minimum(positions, L - 1)
    slot = jnp.where(keep, slot, L)       # out of bounds -> update dropped
    rows = jnp.broadcast_to(jnp.arange(B)[:, None], (B, C))
    cache_k = cache_k.at[rows, slot].set(k_new.astype(cache_k.dtype),
                                         mode="drop")
    cache_v = cache_v.at[rows, slot].set(v_new.astype(cache_v.dtype),
                                         mode="drop")
    return cache_k, cache_v


def prefill_kv(cache_k, cache_v, k, v, window: Optional[int]):
    """Fill cache from a one-shot prefill pass. k: [B, S, KV, hd].

    Thin alias over the generalized ``write_kv`` (chunk write starting
    at position 0): full caches keep the first L tokens, ring caches the
    last L — identical layout to writing the sequence token by token."""
    return write_kv(cache_k, cache_v, k, v, 0, window)


# ---------------------------------------------------------------------------
# Paged KV layout: page pools + per-slot block tables
# ---------------------------------------------------------------------------


def supports_prefix_sharing(cfg: ModelConfig, *, long_mode: bool = False) -> bool:
    """Whether prompt-prefix pages may be shared between slots.

    Sharing is only sound when every layer is full-window attention:

    * a ring (windowed) layer keeps wrapping new tokens onto old slots,
      so a shared prefix page would be clobbered by whichever slot
      decodes first — the other sharers would silently read its tokens;
    * recurrent layers (Mamba/RWKV) summarize the prefix into O(1)
      state, and the mixed wave-step has no way to snapshot that state
      at an arbitrary prompt boundary for a later request to adopt.

    The paged *layout* itself still supports windows and recurrent
    state (each slot's pages stay private); only cross-slot reuse is
    gated on this predicate."""
    return all(
        spec.mixer == Mixer.ATTENTION
        and effective_window(cfg, spec, long_mode) is None
        for spec in cfg.pattern)


def supports_speculative_target(cfg: ModelConfig, *,
                                long_mode: bool = False) -> bool:
    """Whether `cfg` can serve as a speculative-decoding *target*.

    The verify step commits only the accepted prefix of each candidate
    chunk (a validity-masked ``write_kv``), so every layer's state must
    be expressible as positional KV that can simply not-be-written:

    * attention layers qualify — ring windows included, because the
      verify step computes fresh chunk k/v without touching the cache
      and the post-accept commit writes exactly ``accept_len`` entries;
    * recurrent mixers (Mamba/RWKV) fold every token into O(1) state
      that cannot be rolled back past a rejection;
    * the rwkv_channel FFN's ``cm_shift`` is the same problem in the
      channel-mix path."""
    return all(
        spec.mixer == Mixer.ATTENTION and spec.ffn != FFN.RWKV_CHANNEL
        for spec in cfg.pattern)


def supports_speculative_draft(cfg: ModelConfig, *,
                               long_mode: bool = False) -> bool:
    """Whether `cfg` can serve as a speculative-decoding *draft*.

    The draft decodes autoregressively *before* acceptance is known, so
    its cache takes writes that may later be rolled back by resetting
    the position cursor.  That is only sound for full (non-ring)
    attention caches: a stale entry at slot >= pos is masked invalid by
    ``ring_slot_positions`` and overwritten before it is ever attended
    to (``_layer_decode`` writes at pos first), whereas a ring write
    wraps onto older in-window slots that a rollback cannot restore.
    Recurrent state and cm_shift are unrecoverable for the same reason
    as the target."""
    return supports_speculative_target(cfg, long_mode=long_mode) and all(
        effective_window(cfg, spec, long_mode) is None
        for spec in cfg.pattern)


def max_pages_per_slot(cfg: ModelConfig, max_seq: int, page_size: int, *,
                       long_mode: bool = False) -> int:
    """Block-table width: pages needed by the largest attention cache."""
    mps = [-(-kv_cache_len(cfg, spec, max_seq, long_mode) // page_size)
           for spec in cfg.pattern if spec.mixer == Mixer.ATTENTION]
    return max(mps, default=0)


def identity_block_table(n_slots: int, max_pages: int) -> np.ndarray:
    """Block table mapping slot b's page j to pool page b*max_pages + j.

    With a pool of exactly ``n_slots * max_pages`` pages this makes the
    paged layout a pure reshape of the contiguous one — the no-sharing
    parity fallback (and the layout ``init_paged_cache`` defaults to)."""
    return np.arange(n_slots * max_pages, dtype=np.int32).reshape(
        n_slots, max_pages)


def init_paged_cache(cfg: ModelConfig, n_slots: int, max_seq: int, *,
                     page_size: int, n_pages: int = 0,
                     long_mode: bool = False, dtype=jnp.bfloat16):
    """Paged cache pytree: same shape as ``init_cache`` except attention
    k/v leaves are page pools ``[R, n_pages, page_size, KV, hd]`` shared
    by all slots; recurrent state and cm_shift stay per-slot
    ``[R, n_slots, ...]``.  ``n_pages=0`` sizes the pool for the
    identity block table (``n_slots * max_pages_per_slot``)."""
    if n_pages <= 0:
        n_pages = n_slots * max_pages_per_slot(
            cfg, max_seq, page_size, long_mode=long_mode)
    R = cfg.n_pattern_repeats
    hd = cfg.resolved_head_dim
    blocks = {}
    for j, spec in enumerate(cfg.pattern):
        if spec.mixer == Mixer.ATTENTION:
            layer = {
                "k": jnp.zeros((R, n_pages, page_size, cfg.n_kv_heads, hd),
                               dtype),
                "v": jnp.zeros((R, n_pages, page_size, cfg.n_kv_heads, hd),
                               dtype),
            }
        elif spec.mixer == Mixer.MAMBA:
            st = mamba_mod.init_mamba_state(cfg, n_slots)
            layer = {k: jnp.broadcast_to(v, (R,) + v.shape)
                     for k, v in st.items()}
        elif spec.mixer == Mixer.RWKV6:
            st = rwkv_mod.init_rwkv_state(cfg, n_slots)
            layer = {k: jnp.broadcast_to(v, (R,) + v.shape)
                     for k, v in st.items()}
        else:
            raise ValueError(spec.mixer)
        if spec.ffn.value == "rwkv_channel":
            layer["cm_shift"] = jnp.zeros((R, n_slots, cfg.d_model), dtype)
        blocks[f"layer{j}"] = layer
    return {"blocks": blocks, "pos": jnp.zeros((), jnp.int32)}


def paged_view(cfg: ModelConfig, blocks, btab, max_seq: int, *,
               page_size: int, long_mode: bool = False,
               identity: bool = False):
    """Gather each slot's pages into the contiguous blocks view
    (attention leaves ``[R, B, L, KV, hd]``) the existing decode /
    prefill-chunk programs consume.  ``btab`` is ``[B, max_pages]``
    int32; unmapped entries hold the sentinel ``n_pages`` and gather
    zeros (their positions are masked by validity anyway).

    ``identity=True`` is the static fast path for pools that are known
    (at trace time) to be addressed by ``identity_block_table`` forever
    — i.e. paged layout without prefix sharing, where slot ``s`` owns
    pages ``[s*MP, (s+1)*MP)`` by construction.  The gather collapses
    to a reshape+slice of the pool (XLA fuses it into the consumer), so
    the indirection fallback costs ~nothing over the contiguous layout.
    Only valid when the pool holds exactly ``B * MP`` pages."""
    out = {}
    B = btab.shape[0]
    for j, spec in enumerate(cfg.pattern):
        name = f"layer{j}"
        layer = blocks[name]
        if spec.mixer != Mixer.ATTENTION:
            out[name] = layer
            continue
        L = kv_cache_len(cfg, spec, max_seq, long_mode)
        mp = -(-L // page_size)

        def g(pool):
            if identity:
                assert pool.shape[1] == B * btab.shape[1], \
                    "identity view needs a pool of exactly B*MP pages"
                t = pool.reshape(pool.shape[0], B,
                                 btab.shape[1] * page_size, *pool.shape[3:])
                return t[:, :, :L]
            t = jnp.take(pool, btab[:, :mp], axis=1, mode="fill",
                         fill_value=0)
            t = t.reshape(t.shape[0], t.shape[1], mp * page_size,
                          *t.shape[4:])
            return t[:, :, :L]

        new = dict(layer)
        new["k"] = g(layer["k"])
        new["v"] = g(layer["v"])
        out[name] = new
    return out


def _merge_per_slot(layer, new, mask):
    """Emit-masked per-slot merge for non-pool leaves of a layer dict."""
    return {
        kk: jnp.where(_slot_axes_mask(mask, leaf),
                      new[kk].astype(leaf.dtype), leaf)
        for kk, leaf in layer.items()
    }


def paged_update_decode(cfg: ModelConfig, blocks, new_blocks, btab, pos,
                        emit, max_seq: int, *, page_size: int,
                        long_mode: bool = False):
    """Scatter a decode step's per-row token delta back into the pools.

    ``new_blocks`` is the contiguous view *after* ``decode_step`` ran on
    it; ``pos`` is the [B] pre-increment per-slot position the token was
    written at (``token_slot``: ring slot pos % L, full-cache slot
    min(pos, L-1) — the mapping decode wrote with, so the extracted
    value is exact).
    Rows where ``emit`` is False write nothing: a freed page can never
    be corrupted by a retired slot's stale block-table row."""
    B = btab.shape[0]
    rows = jnp.arange(B)
    p = jnp.asarray(pos, jnp.int32)
    out = {}
    for j, spec in enumerate(cfg.pattern):
        name = f"layer{j}"
        layer = blocks[name]
        new = new_blocks[name]
        if spec.mixer != Mixer.ATTENTION:
            out[name] = _merge_per_slot(layer, new, emit)
            continue
        L = kv_cache_len(cfg, spec, max_seq, long_mode)
        slot = token_slot(p, L, effective_window(cfg, spec, long_mode))
        n_pool = layer["k"].shape[1]
        page = btab[rows, slot // page_size]
        page = jnp.where(emit, page, n_pool)   # masked rows -> dropped
        off = slot % page_size

        def u(pool, view):
            val = jnp.take_along_axis(
                view, slot[None, :, None, None, None], axis=2)[:, :, 0]
            return pool.at[:, page, off].set(val.astype(pool.dtype),
                                             mode="drop")

        upd = {kk: leaf for kk, leaf in layer.items()}
        upd["k"] = u(layer["k"], new["k"])
        upd["v"] = u(layer["v"], new["v"])
        for kk in layer:
            if kk not in ("k", "v"):
                upd[kk] = jnp.where(_slot_axes_mask(emit, layer[kk]),
                                    new[kk].astype(layer[kk].dtype),
                                    layer[kk])
        out[name] = upd
    return out


def paged_update_chunk(cfg: ModelConfig, blocks, new_blocks, btab, pcur,
                       n_valid, chunk: int, max_seq: int, *,
                       page_size: int, long_mode: bool = False):
    """Scatter a prefill chunk's token deltas back into the pools.

    Chunk token c of row b sits at absolute position ``pcur[b] + c`` and
    is live when ``c < n_valid[b]``.  Values are read from the
    post-``prefill_chunk_step`` contiguous view at the slot each
    position maps to, so ring-wrap duplicates and full-cache clamps all
    read the *final* value ``write_kv`` left there — duplicate scatter
    targets carry identical payloads and ordering cannot matter."""
    B = btab.shape[0]
    rows = jnp.arange(B)[:, None]
    positions = jnp.asarray(pcur, jnp.int32)[:, None] + jnp.arange(chunk)
    valid = jnp.arange(chunk)[None, :] < jnp.asarray(n_valid)[:, None]
    out = {}
    for j, spec in enumerate(cfg.pattern):
        name = f"layer{j}"
        layer = blocks[name]
        new = new_blocks[name]
        if spec.mixer != Mixer.ATTENTION:
            out[name] = _merge_per_slot(layer, new, jnp.asarray(n_valid) > 0)
            continue
        L = kv_cache_len(cfg, spec, max_seq, long_mode)
        w = effective_window(cfg, spec, long_mode)
        slot = positions % L if w is not None else jnp.minimum(positions,
                                                               L - 1)
        n_pool = layer["k"].shape[1]
        page = btab[jnp.broadcast_to(rows, slot.shape), slot // page_size]
        page = jnp.where(valid, page, n_pool)
        off = slot % page_size

        def u(pool, view):
            val = jnp.take_along_axis(
                view, slot[None, :, :, None, None], axis=2)
            return pool.at[:, page, off].set(val.astype(pool.dtype),
                                             mode="drop")

        upd = {kk: leaf for kk, leaf in layer.items()}
        upd["k"] = u(layer["k"], new["k"])
        upd["v"] = u(layer["v"], new["v"])
        for kk in layer:
            if kk not in ("k", "v"):
                upd[kk] = jnp.where(
                    _slot_axes_mask(jnp.asarray(n_valid) > 0, layer[kk]),
                    new[kk].astype(layer[kk].dtype), layer[kk])
        out[name] = upd
    return out


def copy_pages(cfg: ModelConfig, blocks, src, dst):
    """Device-side page copies: ``pool[dst[i]] = pool[src[i]]`` in every
    attention layer's k and v pool — the copy-on-write step the host
    allocator schedules when an admission diverges mid-page from a
    cached prefix.  ``src``/``dst`` are equal-length int32 vectors;
    entries padded with the sentinel page id are dropped."""
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    out = {}
    for j, spec in enumerate(cfg.pattern):
        name = f"layer{j}"
        layer = blocks[name]
        if spec.mixer != Mixer.ATTENTION:
            out[name] = layer
            continue
        upd = dict(layer)
        for kk in ("k", "v"):
            pool = layer[kk]
            vals = jnp.take(pool, src, axis=1, mode="fill", fill_value=0)
            upd[kk] = pool.at[:, dst].set(vals, mode="drop")
        out[name] = upd
    return out


def zero_paged_slots(cfg: ModelConfig, blocks, slot_mask):
    """Paged analogue of ``zero_slots``: zero only the *per-slot* leaves
    (recurrent state, cm_shift) of slots where ``slot_mask`` is True.
    Page-pool k/v is deliberately left untouched — stale page contents
    are masked by position validity exactly as in the contiguous layout,
    and zeroing through a recycled slot's block-table row could clobber
    a page still referenced by a live sharer."""
    out = {}
    for j, spec in enumerate(cfg.pattern):
        name = f"layer{j}"
        layer = blocks[name]
        upd = {}
        for kk, leaf in layer.items():
            if spec.mixer == Mixer.ATTENTION and kk in ("k", "v"):
                upd[kk] = leaf
            else:
                upd[kk] = jnp.where(_slot_axes_mask(slot_mask, leaf),
                                    jnp.zeros((), leaf.dtype), leaf)
        out[name] = upd
    return out
