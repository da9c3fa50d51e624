"""Wave-stepped continuous-batching decode loop with chunked prefill.

Device-side execution is a small set of jitted, fixed-shape programs per
(model, engine-config, prompt-shape) triple — the device-program table:

  program   inputs (beyond params/state)      what it does
  -------   -------------------------------   ---------------------------
  admit     prompts [W,P], admit_mask [W],    one-shot path
            rows [W], limits [W], key         (``prefill_chunk == 0``):
                                              prefill the whole [W, P]
                                              prompt batch, sample each
                                              admitted request's first
                                              token, scatter fresh cache
                                              rows (``scatter_slots``).
  install   prompts [W,P], admit_mask [W],    chunked path: stage request
            rows [W], limits [W], plens [W]   metadata + prompt rows into
                                              per-slot buffers, zero the
                                              admitted slots' cache rows
                                              (``cache.zero_slots``), set
                                              ``prefill_cursor = 0``.  No
                                              model compute, no sampling.
  mixed     k_decodes [k], k_lands [k]        the **mixed wave-step**: a
                                              scan of k sub-rounds (k <=
                                              ``decode_chunk``, sized by
                                              the host to the pending
                                              prefill work), each ONE
                                              batched ``decode_step``
                                              over decoding slots (cache
                                              rows of admitting slots
                                              protected) plus ONE
                                              ``prefill_chunk_step`` (up
                                              to ``prefill_chunk`` prompt
                                              tokens) over admitting
                                              slots, all masked; a slot
                                              whose final chunk lands
                                              samples its first token
                                              (its sub-round's k_lands
                                              entry) and decodes from the
                                              next sub-round on.
  chunk     keys [decode_chunk]               ``decode_chunk`` wave steps
                                              under ``lax.scan`` (used
                                              when no slot is mid-
                                              prefill): batched decode,
                                              sample, record, retire on
                                              EOS / budget.

With ``spec_k > 0`` the decode half of every sub-round (in both the
``chunk`` and ``mixed`` programs) becomes the **draft/verify
sub-round**: k+1 autoregressive draft-model decode steps propose
d_1..d_k per live slot (the trailing step's proposal is discarded —
it runs so d_k's draft k/v lands for the full-accept-plus-bonus case;
the draft keeps its own small full-attention per-slot cache
``st["dcache"]`` in the slot lifecycle, always at the target's
position), then ONE batched target step over the [W, k+1]
candidate chunk ``[tok, d_1..d_k]`` verifies them
(``transformer.verify_chunk_step`` — all-position logits, cache left
untouched).  Greedy: exact longest-prefix match against the target
argmax; sampled: standard rejection-resampling, so the emitted-token
distribution is exactly the target's.  The accepted prefix plus the
bonus/corrected token (up to k+1 tokens) lands through the chunked
variable-length write machinery — validity-masked ``cache.write_kv``
at per-row cursors, ``paged_update_chunk`` on the paged pool — so ring
windows, GQA, recycled slots and prefix sharing all carry over.  On
the chunked-admission path the draft ingests the *full* prompt in its
own chunk cursor (``st["dcur"]``; it has no radix cache), while a
prefix-cache target skips ahead — ``st["pdelay"]`` idles the target's
prefill so both cursors land on the same sub-round.

Static-shape rules: every program's operand shapes depend only on
(W, P, C, N, n_reqs) plus, for ``mixed``, the scan length k — a value
in {1..decode_chunk}, so at most ``decode_chunk`` program variants
exist, each traced once on first use (decode_chunk=1, the default,
means a single variant).  Membership, prompt raggedness, chunk counts
and landings are masks and scatters — none of them retrace; the
bounded k variants trade a one-time compile each for never running
masked all-idle chunk passes every round.
``decode_path="vmapped"`` selects the legacy W-way vmap of a B=1 decode
for parity testing.

Paged KV (``page_size > 0``, requires chunked admission): attention k/v
live in per-layer page pools addressed through a per-slot block table
``st["btab"]`` (``models.cache.init_paged_cache``).  Each program's
model compute is unchanged — a gather (``cache.paged_view``) materializes
the contiguous per-slot view the decode / prefill-chunk steps consume,
and the freshly written token k/v are scattered back into the pools
(``paged_update_decode`` / ``paged_update_chunk``), always emit-masked
so a retired slot's stale block-table row can never corrupt a reused
page.  Two more paged programs join the table:

  program   inputs (beyond params/state)      what it does
  -------   -------------------------------   ---------------------------
  install   ... + pstarts [W], btab [W,MP]    paged variant: additionally
                                              maps the admitted slots'
                                              block-table rows and starts
                                              each prefill cursor at
                                              ``pstarts`` (after the
                                              cached prefix).
  copy      src [W], dst [W]                  copy-on-write page copies
                                              (``cache.copy_pages``) the
                                              host schedules when an
                                              admission diverges mid-page
                                              from a cached prefix;
                                              sentinel-padded, fixed
                                              shape.

With ``prefix_cache=True`` the host admission loop additionally runs a
radix prefix tree + refcounted page allocator
(``genserve.pagepool``): each admitted prompt is matched against the
tree, whole matching pages are mapped copy-free (refcount++), at most
one partially matching page is copied (COW) — and chunked prefill runs
only on the uncached suffix (the cursor starts at the hit length,
capped at plen-1 so the landing chunk always runs and samples from real
landing logits).  Landed prompts insert their complete pages; retired
slots decref; LRU leaves evict when the free list runs dry.  Sharing
requires every layer to be full-window attention
(``cache.supports_prefix_sharing``) — ring windows would clobber shared
pages and recurrent state cannot be snapshotted at a prompt boundary —
while the paged *layout* itself (with the identity block table) works
for every config and is the exact-parity no-sharing fallback.

The host loop owns dynamic membership: it reads back the ``occupied``
vector after every round, retires finished requests via the
``scheduler.SlotTable``, back-fills freed slots from the admission
queue (FIFO by default; ``admission="sjf"`` admits shortest known
budgets first), and mirrors each slot's prefill cursor (deterministic:
every mixed round advances every prefilling slot by one chunk) so it
knows landings without a device sync.

RNG schedule: the first ``max_new_tokens`` sampling events use
``jax.random.split(rng, max_new_tokens)`` — the exact schedule of
``rl.rollout.generate`` — so a batch that fits into a single wave
reproduces the reference path token-for-token *on both admission
paths*: chunked prefill consumes no keys until the landing round, whose
first-token sample uses ``rngs[0]`` exactly like the one-shot admit.
Late admissions and overflow steps draw from ``fold_in`` side streams.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.genserve.pagepool import PagePool, RadixCache
from repro.genserve.scheduler import FREE, Request, RequestQueue, SlotTable
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.models import attention as attn_mod
from repro.models import cache as cache_mod
from repro.models import sampling
from repro.models import transformer as T
from repro.models.config import ModelConfig


@dataclasses.dataclass(frozen=True)
class GenServeConfig:
    """Engine knobs (hashable: keys the jit cache)."""

    wave: int                        # max concurrently decoding slots (W)
    max_new_tokens: int              # global budget N (output buffer width)
    decode_chunk: int = 1            # decode steps per jitted host round
    temperature: float = 1.0
    eos_token: Optional[int] = None
    greedy: bool = False
    decode_path: str = "batched"     # "batched" | "vmapped" wave decode
    admission: str = "fifo"          # "fifo" | "sjf" queue policy
    prefill_chunk: int = 0           # tokens per mixed-round prefill chunk
    #                                  (0 = one-shot whole-prompt admit)
    measure_ttft: bool = False       # stamp per-request time-to-first-token
    #                                  (one-shot admission pays a device sync
    #                                  per admit batch; chunked stamps ride
    #                                  the round sync for free, but are
    #                                  gated too so the flag means one thing)
    page_size: int = 0               # paged KV: tokens per pool page
    #                                  (0 = contiguous per-slot cache)
    prefix_cache: bool = False       # radix prefix reuse across slots
    #                                  (requires page_size > 0 and a
    #                                  full-attention config)
    pool_pages: int = 0              # page-pool size override (0 = sized
    #                                  automatically: W*MP contiguous-
    #                                  equivalent, 2*W*MP with prefix
    #                                  cache so eviction can always make
    #                                  room for a full admission wave)
    sjf_aging: int = 0               # sjf anti-starvation: admit a
    #                                  passed-over request after at most
    #                                  this many pops (0 = pure sjf)
    spec_k: int = 0                  # speculative decoding: draft tokens
    #                                  proposed per wave sub-round (0 =
    #                                  off).  Requires a draft model
    #                                  (``serve(draft_params=,
    #                                  draft_cfg=)``); the target must
    #                                  satisfy
    #                                  ``cache.supports_speculative_target``
    #                                  and the draft the stricter
    #                                  ``cache.supports_speculative_draft``.

    def validate(self) -> None:
        assert self.wave >= 1 and self.max_new_tokens >= 1
        assert self.decode_chunk >= 1
        assert self.decode_path in ("batched", "vmapped")
        assert self.admission in ("fifo", "sjf")
        assert self.prefill_chunk >= 0
        assert self.page_size >= 0 and self.pool_pages >= 0
        assert self.sjf_aging >= 0
        assert self.spec_k >= 0
        if self.spec_k > 0:
            # the spec sub-round is one natively batched verify step
            assert self.decode_path == "batched", \
                "spec_k > 0 requires the batched decode path"
        if self.page_size > 0:
            # paged KV rides the chunked-admission machinery (per-slot
            # cursors, install/mixed programs)
            assert self.prefill_chunk > 0, \
                "page_size > 0 requires chunked admission (prefill_chunk > 0)"
        if self.prefix_cache:
            assert self.page_size > 0, \
                "prefix_cache requires a paged cache (page_size > 0)"


# ---------------------------------------------------------------------------
# Wave decode: one batched decode_step over all W slots.  The cache
# leaves are already laid out [R, W, ...] — exactly decode_step's batch
# layout — and per-slot cache positions ([W] `pos`) carry each recycled
# slot's own RoPE phase and ring-window validity, so the whole wave
# shares one fused attention call (the Sq == 1 flash-decode path when
# `set_attention_impl("pallas")` is active).  The legacy W-way vmap of a
# B=1 decode_step is kept as the "vmapped" parity path.
# ---------------------------------------------------------------------------

def _wave_decode_batched(params, cfg: ModelConfig, tok, pos, blocks):
    """tok, pos: [W]; blocks: cache leaves [R, W, ...].
    Returns (logits [W, V], new blocks)."""
    logits, new = T.decode_step(params, cfg, tok[:, None],
                                {"blocks": blocks, "pos": pos})
    return logits, new["blocks"]


def _wave_decode_vmapped(params, cfg: ModelConfig, tok, pos, blocks):
    """Per-slot reference: vmap of the B=1 decode_step over the wave."""

    def one_slot(tok_w, pos_w, slot_blocks):
        cache = {"blocks": jax.tree_util.tree_map(lambda l: l[:, None],
                                                  slot_blocks),
                 "pos": pos_w}
        logits, new = T.decode_step(params, cfg, tok_w[None, None], cache)
        return logits[0], jax.tree_util.tree_map(lambda l: l[:, 0],
                                                 new["blocks"])

    return jax.vmap(one_slot, in_axes=(0, 0, 1), out_axes=(0, 1))(
        tok, pos, blocks)


# ---------------------------------------------------------------------------
# Jitted engine programs (cached per (cfg, gcfg, P, n_reqs))
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=64)
def _build_fns(cfg: ModelConfig, gcfg: GenServeConfig, prompt_len: int,
               n_reqs: int, impl: str = "jnp",
               draft_cfg: Optional[ModelConfig] = None,
               mesh=None):
    # `impl` (the active models.attention implementation) is part of the
    # cache key only: tracing reads the global impl at first call, so a
    # cached jitted fn built under "jnp" must not be reused under
    # "pallas" (or vice versa).  `mesh` likewise: the jitted programs
    # bake in the sharding constraints (``parallel.sharding`` hints)
    # active when first traced, so a fn traced for one mesh — or none —
    # must not serve another.
    N = gcfg.max_new_tokens
    eos = gcfg.eos_token
    dummy_row = n_reqs               # output buffers carry a scratch row
    ps = gcfg.page_size
    paged = ps > 0
    max_seq = prompt_len + N
    spec_k = gcfg.spec_k
    spec = spec_k > 0
    k1 = spec_k + 1                  # candidate-chunk width [tok, d_1..d_k]
    assert not spec or draft_cfg is not None, \
        "spec_k > 0 requires a draft model config"
    # without prefix sharing the block table is the identity mapping
    # forever (install always writes identity rows), so the pool view is
    # a pure reshape — no gather, ~zero overhead over contiguous
    identity_pool = (paged and not gcfg.prefix_cache
                     and gcfg.pool_pages == 0)

    def view_of(st):
        """Contiguous per-slot blocks view the model steps consume."""
        if not paged:
            return st["cache"]
        return cache_mod.paged_view(cfg, st["cache"], st["btab"], max_seq,
                                    page_size=ps, identity=identity_pool)

    def sample(key, logits):
        return sampling.sample_tokens(key, logits,
                                      temperature=gcfg.temperature,
                                      greedy=gcfg.greedy)

    def sample_lp(key, logits):
        """(tokens, logprobs) at the engine's temperature — the shared
        sampling point of every program (models.sampling owns the
        temperature/greedy semantics)."""
        return sampling.sample_with_logprobs(key, logits,
                                             temperature=gcfg.temperature,
                                             greedy=gcfg.greedy)

    def admit(params, dparams, state, prompts, admit_mask, rows, limits,
              key):
        """Prefill [W, P] prompts; install admitted slots; sample token 0."""
        out = T.forward(params, cfg, {"tokens": prompts}, return_cache=True,
                        max_cache_len=prompt_len + N, remat=False)
        logits0 = out["logits"][:, -1]
        tok0, lp0 = sample_lp(key, logits0)
        alive0 = sampling.initial_alive(prompts, eos) & admit_mask
        finished0 = limits <= 1
        if eos is not None:
            finished0 |= tok0 == eos

        buf_rows = jnp.where(admit_mask, rows, dummy_row)
        st = dict(state)
        st["gen"] = state["gen"].at[buf_rows, 0].set(tok0)
        st["lp"] = state["lp"].at[buf_rows, 0].set(lp0)
        st["mask"] = state["mask"].at[buf_rows, 0].set(
            alive0.astype(jnp.float32))
        st["cache"] = cache_mod.scatter_slots(state["cache"],
                                              out["cache"]["blocks"],
                                              admit_mask)
        st["pos"] = jnp.where(admit_mask, prompt_len, state["pos"])
        st["tok"] = jnp.where(admit_mask, tok0, state["tok"])
        st["ngen"] = jnp.where(admit_mask, 1, state["ngen"])
        st["req"] = jnp.where(admit_mask, rows, state["req"])
        st["limit"] = jnp.where(admit_mask, limits, state["limit"])
        st["occupied"] = jnp.where(admit_mask, alive0 & ~finished0,
                                   state["occupied"])
        if spec:
            # prime the draft's own per-slot cache on the same prompts:
            # its position cursor stays identical to the target's
            dout = T.forward(dparams, draft_cfg, {"tokens": prompts},
                             return_cache=True,
                             max_cache_len=prompt_len + N, remat=False)
            st["dcache"] = cache_mod.scatter_slots(
                state["dcache"], dout["cache"]["blocks"], admit_mask)
        return st

    wave_decode = (_wave_decode_batched if gcfg.decode_path == "batched"
                   else _wave_decode_vmapped)

    def decode_substep(params, st, key, protect: bool):
        """One batched wave decode step.  With ``protect`` the cache
        writes (KV + recurrent state) of non-emitting rows are merged
        back — required whenever slots may be mid-prefill (the mixed
        wave-step), free to skip on the pure decode path (a free row's
        clobbered cache is replaced wholesale at one-shot re-admission,
        or zeroed + rewritten by chunked re-admission).  Paged caches
        scatter the token delta through the block table instead
        (``paged_update_decode``) — inherently emit-masked, so protect
        is moot and freed pages stay untouched."""
        logits, new_blocks = wave_decode(params, cfg, st["tok"],
                                         st["pos"], view_of(st))
        nxt, lp = sample_lp(key, logits)
        emit = st["occupied"]
        buf_rows = jnp.where(emit, st["req"], dummy_row)
        cols = jnp.where(emit, st["ngen"], 0)
        alive_after = sampling.next_alive(emit, nxt, eos)
        finished = emit & (~alive_after |
                           (st["ngen"] + 1 >= st["limit"]))
        st = dict(st)
        st["gen"] = st["gen"].at[buf_rows, cols].set(nxt)
        st["lp"] = st["lp"].at[buf_rows, cols].set(lp)
        st["mask"] = st["mask"].at[buf_rows, cols].set(
            emit.astype(jnp.float32))
        if paged:
            st["cache"] = cache_mod.paged_update_decode(
                cfg, st["cache"], new_blocks, st["btab"], st["pos"],
                emit, max_seq, page_size=ps)
        else:
            st["cache"] = cache_mod.scatter_slots(st["cache"], new_blocks,
                                                  emit) if protect \
                else new_blocks
        st["pos"] = jnp.where(emit, st["pos"] + 1, st["pos"])
        st["tok"] = jnp.where(emit, nxt, st["tok"])
        st["ngen"] = jnp.where(emit, st["ngen"] + 1, st["ngen"])
        st["occupied"] = emit & ~finished
        return st, jnp.sum(emit.astype(jnp.int32))

    def _commit_accepted(view_blocks, deltas, pos, n_valid):
        """Write the accepted prefix of a verify chunk into the
        contiguous (or contiguous-view) cache: per-layer validity-masked
        ``write_kv`` at per-row cursors.  Rows with ``n_valid == 0`` take
        no writes at all, so mid-prefill / free slots are protected for
        free and rejected speculative k/v never lands."""
        valid = jnp.arange(k1)[None, :] < n_valid[:, None]
        out = {}
        for j, spec_l in enumerate(cfg.pattern):
            w = cache_mod.effective_window(cfg, spec_l, False)
            lk = f"layer{j}"
            nk, nv = jax.vmap(
                lambda a, b, c, d, w=w: cache_mod.write_kv(
                    a, b, c, d, pos, w, valid=valid))(
                view_blocks[lk]["k"], view_blocks[lk]["v"],
                deltas[lk]["k"], deltas[lk]["v"])
            out[lk] = {"k": nk, "v": nv}
        return out

    def spec_substep(params, dparams, st, key, protect: bool):
        """The draft/verify sub-round: k draft decode steps propose
        d_1..d_k per live slot, ONE batched target step over the
        [W, k+1] candidate chunk verifies them, and up to k+1 tokens
        (accepted prefix + bonus/corrected token) land at once through
        the variable-length chunk-write machinery.  The commit is
        inherently emit-masked (``n_valid == 0`` rows take no writes),
        so ``protect`` is moot — mid-prefill rows are safe either way.

        The draft keeps its own full-attention cache at the target's
        position: after committing e tokens, draft positions
        pos..pos+e-1 hold exactly the tokens the target committed, and
        stale entries beyond are masked invalid (and overwritten before
        read) in a full cache — the rollback-free invariant
        ``cache.supports_speculative_draft`` guarantees."""
        emit = st["occupied"]
        pos = st["pos"]
        keys = jax.random.split(key, spec_k + 2)

        def propose(carry, kk):
            tok_c, dblocks, dpos = carry
            logits, new = T.decode_step(dparams, draft_cfg, tok_c[:, None],
                                        {"blocks": dblocks, "pos": dpos})
            nxt = sample(kk, logits)
            return (nxt, new["blocks"], dpos + 1), (nxt, logits)

        # k+1 draft steps for k proposals: the extra step's proposal is
        # discarded, but it writes d_k's draft k/v — without it the
        # full-accept-plus-bonus round (e == k+1) leaves a hole at
        # pos+k that the draft would attend to on every later round
        (_, dblocks, _), (drafts, dlogits) = jax.lax.scan(
            propose, (st["tok"], st["dcache"], pos), keys[:spec_k + 1])
        drafts = jnp.swapaxes(drafts, 0, 1)[:, :spec_k]      # [W, k]
        dlogits = jnp.swapaxes(dlogits, 0, 1)[:, :spec_k]    # [W, k, V]

        view = view_of(st)
        cand_in = jnp.concatenate([st["tok"][:, None], drafts], axis=1)
        vlogits, deltas = T.verify_chunk_step(
            params, cfg, cand_in, {"blocks": view, "pos": pos})
        a, cand = sampling.speculative_accept(
            keys[spec_k + 1], vlogits, drafts, dlogits,
            temperature=gcfg.temperature, greedy=gcfg.greedy)

        # effective emit count e: accepted + bonus, cut at the remaining
        # budget and at the first emitted EOS (the EOS itself is valid)
        e = jnp.minimum(a + 1, jnp.maximum(st["limit"] - st["ngen"], 0))
        if eos is not None:
            first_eos = jnp.min(
                jnp.where(cand == eos, jnp.arange(k1)[None, :], k1 + N),
                axis=1)
            e = jnp.minimum(e, first_eos + 1)
        e = jnp.where(emit, e, 0)
        hit_eos = (first_eos < e) if eos is not None \
            else jnp.zeros_like(emit)
        finished = emit & (hit_eos | (st["ngen"] + e >= st["limit"]))

        ar = jnp.arange(k1)[None, :]
        val = (ar < e[:, None]) & emit[:, None]        # [W, k+1]
        buf_rows = jnp.where(val, st["req"][:, None], dummy_row)
        cols = jnp.where(val, st["ngen"][:, None] + ar, 0)
        lp_all = sampling.token_logprobs(vlogits, cand)
        st = dict(st)
        st["gen"] = st["gen"].at[buf_rows, cols].set(
            jnp.where(val, cand, 0), mode="drop")
        st["lp"] = st["lp"].at[buf_rows, cols].set(
            jnp.where(val, lp_all, 0.0), mode="drop")
        st["mask"] = st["mask"].at[buf_rows, cols].set(
            val.astype(jnp.float32), mode="drop")

        committed = _commit_accepted(view, deltas, pos, e)
        if paged:
            st["cache"] = cache_mod.paged_update_chunk(
                cfg, st["cache"], committed, st["btab"], pos, e, k1,
                max_seq, page_size=ps)
        else:
            st["cache"] = committed
        st["dcache"] = cache_mod.scatter_slots(st["dcache"], dblocks, emit)

        new_tok = jnp.take_along_axis(
            cand, jnp.clip(e - 1, 0, spec_k)[:, None], axis=1)[:, 0]
        st["tok"] = jnp.where(emit, new_tok, st["tok"])
        st["pos"] = pos + e
        st["ngen"] = st["ngen"] + e
        st["occupied"] = emit & ~finished
        emit_n = jnp.sum(emit.astype(jnp.int32))
        return st, (emit_n, jnp.sum(e), emit_n * spec_k,
                    jnp.sum(jnp.where(emit, a, 0)))

    def chunk(params, dparams, state, keys):
        """`decode_chunk` wave steps; returns per-step active counts
        (speculative: (active, emitted-token, proposed, accepted)
        per-step counts)."""
        if spec:
            return jax.lax.scan(
                lambda st, key: spec_substep(params, dparams, st, key,
                                             protect=False),
                state, keys)
        return jax.lax.scan(
            lambda st, key: decode_substep(params, st, key, protect=False),
            state, keys)

    C = max(gcfg.prefill_chunk, 1)

    def install(state, prompts, admit_mask, rows, limits, plens,
                pstarts=None, btab_new=None):
        """Chunked admission: stage request metadata into the admitted
        slots and zero their cache rows — no model compute; the prompt
        is ingested chunk by chunk by subsequent ``mixed`` rounds.
        Paged: additionally merge the admitted slots' block-table rows,
        start each prefill cursor at ``pstarts`` (after the prefix the
        host found cached) and zero only the per-slot leaves — pool
        pages may be shared with live slots and stale page contents are
        masked by position validity anyway."""
        st = dict(state)
        st["prompt"] = jnp.where(admit_mask[:, None], prompts,
                                 state["prompt"])
        st["plen"] = jnp.where(admit_mask, plens, state["plen"])
        st["prefilling"] = state["prefilling"] | admit_mask
        st["req"] = jnp.where(admit_mask, rows, state["req"])
        st["limit"] = jnp.where(admit_mask, limits, state["limit"])
        st["ngen"] = jnp.where(admit_mask, 0, state["ngen"])
        st["occupied"] = state["occupied"] & ~admit_mask
        if paged:
            st["pcur"] = jnp.where(admit_mask, pstarts, state["pcur"])
            st["btab"] = jnp.where(admit_mask[:, None], btab_new,
                                   state["btab"])
            st["cache"] = cache_mod.zero_paged_slots(cfg, state["cache"],
                                                     admit_mask)
        else:
            st["pcur"] = jnp.where(admit_mask, 0, state["pcur"])
            st["cache"] = cache_mod.zero_slots(state["cache"], admit_mask)
        if spec:
            # the draft has no radix cache: it ingests the full prompt
            # from cursor 0, while a prefix-cache target starts after the
            # cached prefix.  Idle the target's prefill for the chunk-
            # count difference so both cursors land on the same sub-round
            # (the landing logits must come from the target's real final
            # chunk).
            st["dcur"] = jnp.where(admit_mask, 0, state["dcur"])
            st["dcache"] = cache_mod.zero_slots(state["dcache"],
                                                admit_mask)
            ncd = (st["plen"] + C - 1) // C
            nct = (st["plen"] - st["pcur"] + C - 1) // C
            st["pdelay"] = jnp.where(admit_mask, ncd - nct,
                                     state["pdelay"])
        return st

    def copy(state, src, dst):
        """COW page copies (paged only): pool[dst] = pool[src] in every
        attention layer; sentinel-padded entries are dropped."""
        st = dict(state)
        st["cache"] = cache_mod.copy_pages(cfg, state["cache"], src, dst)
        return st

    def prefill_substep(params, dparams, st, k_land):
        """The prefill half of one mixed sub-round: one [W, C] prompt
        chunk over the admitting slots (masked), landing any slot whose
        final chunk just arrived (first token sampled from k_land).
        Speculative engines additionally advance the draft's own prompt
        cursor (full prompt, no prefix skip) and hold the target idle
        while ``pdelay > 0`` so both cursors finish together."""
        st = dict(st)
        pf = st["prefilling"]
        pcur = st["pcur"]
        idle = (st["pdelay"] > 0) if spec else jnp.zeros_like(pf)
        n_valid = jnp.where(pf & ~idle,
                            jnp.clip(st["plen"] - pcur, 0, C), 0)
        idx = jnp.clip(pcur[:, None] + jnp.arange(C), 0,
                       st["prompt"].shape[1] - 1)
        chunk_tok = jnp.take_along_axis(st["prompt"], idx, axis=1)
        last_logits, pf_cache = T.prefill_chunk_step(
            params, cfg, chunk_tok, {"blocks": view_of(st), "pos": pcur},
            n_valid=n_valid)
        prow = n_valid > 0
        if paged:
            cache_p = cache_mod.paged_update_chunk(
                cfg, st["cache"], pf_cache["blocks"], st["btab"], pcur,
                n_valid, C, max_seq, page_size=ps)
        else:
            cache_p = cache_mod.scatter_slots(st["cache"],
                                              pf_cache["blocks"], prow)

        land = pf & (pcur + n_valid >= st["plen"])
        if spec:
            dcur = st["dcur"]
            n_valid_d = jnp.where(pf, jnp.clip(st["plen"] - dcur, 0, C), 0)
            idx_d = jnp.clip(dcur[:, None] + jnp.arange(C), 0,
                             st["prompt"].shape[1] - 1)
            dtok = jnp.take_along_axis(st["prompt"], idx_d, axis=1)
            _, d_cache = T.prefill_chunk_step(
                dparams, draft_cfg, dtok,
                {"blocks": st["dcache"], "pos": dcur}, n_valid=n_valid_d)
            st["dcache"] = cache_mod.scatter_slots(
                st["dcache"], d_cache["blocks"], n_valid_d > 0)
            st["dcur"] = dcur + n_valid_d
            st["pdelay"] = jnp.where(pf & idle, st["pdelay"] - 1,
                                     st["pdelay"])
            land = land & (st["dcur"] >= st["plen"])
            prow = prow | (n_valid_d > 0)
        tok0, lp0 = sample_lp(k_land, last_logits)
        last_prompt_tok = jnp.take_along_axis(
            st["prompt"], jnp.clip(st["plen"] - 1, 0, None)[:, None],
            axis=1)[:, 0]
        alive0 = land if eos is None else land & (last_prompt_tok != eos)
        finished0 = st["limit"] <= 1
        if eos is not None:
            finished0 = finished0 | (tok0 == eos)
        buf0 = jnp.where(land, st["req"], dummy_row)
        st["gen"] = st["gen"].at[buf0, 0].set(tok0)
        st["lp"] = st["lp"].at[buf0, 0].set(lp0)
        st["mask"] = st["mask"].at[buf0, 0].set(alive0.astype(jnp.float32))
        st["cache"] = cache_p
        st["pcur"] = jnp.where(pf, pcur + n_valid, pcur)
        st["prefilling"] = pf & ~land
        st["pos"] = jnp.where(land, st["plen"], st["pos"])
        st["tok"] = jnp.where(land, tok0, st["tok"])
        st["ngen"] = jnp.where(land, 1, st["ngen"])
        st["occupied"] = jnp.where(land, alive0 & ~finished0,
                                   st["occupied"])
        return st, jnp.sum(prow.astype(jnp.int32))

    def mixed(params, dparams, state, k_decodes, k_lands):
        """The mixed wave-step: a scan of sub-rounds, each ONE batched
        decode step over decoding slots (cache rows of admitting slots
        protected) plus ONE [W, C] prefill chunk over admitting slots —
        one fixed-shape program per round, so admission of a long prompt
        never stalls the decode wave.  A slot landing at sub-round j
        decodes from sub-round j+1 onward *within the same program*.
        The host sizes the scan (len(k_decodes) sub-rounds, bounded by
        ``decode_chunk``) to the outstanding prefill work so no
        all-masked chunk passes run — each distinct scan length is its
        own jit variant, compiled once (at most ``decode_chunk`` of
        them).  Returns (state, (per-sub-round decode counts,
        per-sub-round prefill counts))."""

        def sub(st, keys2):
            k_d, k_l = keys2
            if spec:
                st, d = spec_substep(params, dparams, st, k_d,
                                     protect=True)
            else:
                st, d = decode_substep(params, st, k_d, protect=True)
            st, p = prefill_substep(params, dparams, st, k_l)
            return st, (d, p)

        st, (d_counts, p_counts) = jax.lax.scan(
            sub, dict(state), (k_decodes, k_lands))
        return st, (d_counts, p_counts)

    if spec:
        return (jax.jit(admit), jax.jit(chunk), jax.jit(install),
                jax.jit(mixed), jax.jit(copy))
    # signature-stable non-speculative programs: no dparams operand, so
    # existing call sites (and the jit caches keyed on them) are
    # untouched when spec_k == 0
    return (
        jax.jit(lambda params, state, prompts, admit_mask, rows, limits,
                key: admit(params, None, state, prompts, admit_mask,
                           rows, limits, key)),
        jax.jit(lambda params, state, keys: chunk(params, None, state,
                                                  keys)),
        jax.jit(install),
        jax.jit(lambda params, state, k_decodes, k_lands: mixed(
            params, None, state, k_decodes, k_lands)),
        jax.jit(copy))


def _pool_pages(cfg: ModelConfig, gcfg: GenServeConfig,
                prompt_len: int) -> Tuple[int, int]:
    """(max pages per slot, pool size) for a paged engine config.

    Default pool: ``W*MP`` without prefix caching (exactly the
    contiguous footprint — the identity block table fills it), ``2*W*MP``
    with it, which guarantees a full admission wave can always be
    satisfied after LRU eviction: live slots pin at most ``W*MP``
    distinct pages, so at least ``W*MP`` are free or tree-only."""
    MP = cache_mod.max_pages_per_slot(cfg, prompt_len + gcfg.max_new_tokens,
                                      gcfg.page_size)
    if gcfg.pool_pages > 0:
        NP = gcfg.pool_pages
    else:
        NP = gcfg.wave * MP * (2 if gcfg.prefix_cache else 1)
    return MP, NP


def _init_state(cfg: ModelConfig, gcfg: GenServeConfig, prompt_len: int,
                n_reqs: int, draft_cfg: Optional[ModelConfig] = None
                ) -> Dict[str, object]:
    W, N = gcfg.wave, gcfg.max_new_tokens
    if gcfg.page_size > 0:
        MP, NP = _pool_pages(cfg, gcfg, prompt_len)
        cache = cache_mod.init_paged_cache(cfg, W, prompt_len + N,
                                           page_size=gcfg.page_size,
                                           n_pages=NP,
                                           dtype=jnp.dtype(cfg.dtype))
        # without prefix sharing the block table is the static identity
        # map (pool == reshaped contiguous layout); with sharing the
        # host assigns rows at admission, so start fully unmapped
        if gcfg.prefix_cache:
            btab = jnp.full((W, MP), NP, jnp.int32)
        else:
            btab = jnp.asarray(cache_mod.identity_block_table(W, MP))
    else:
        cache = cache_mod.init_cache(cfg, W, prompt_len + N,
                                     dtype=jnp.dtype(cfg.dtype))
        btab = None
    st = {
        "tok": jnp.zeros((W,), jnp.int32),
        "pos": jnp.zeros((W,), jnp.int32),
        "occupied": jnp.zeros((W,), bool),
        "req": jnp.full((W,), n_reqs, jnp.int32),
        "ngen": jnp.zeros((W,), jnp.int32),
        "limit": jnp.ones((W,), jnp.int32),
        "cache": cache["blocks"],
        "gen": jnp.zeros((n_reqs + 1, N), jnp.int32),
        "lp": jnp.zeros((n_reqs + 1, N), jnp.float32),
        "mask": jnp.zeros((n_reqs + 1, N), jnp.float32),
    }
    if gcfg.prefill_chunk > 0:
        # chunked-prefill slot state: per-slot prompt buffer, prefill
        # cursor, prompt length and mid-prefill flag (only present when
        # chunked admission is on — the one-shot path's per-round calls
        # should not carry dead operands)
        st.update({
            "prompt": jnp.zeros((W, prompt_len), jnp.int32),
            "pcur": jnp.zeros((W,), jnp.int32),
            "plen": jnp.full((W,), prompt_len, jnp.int32),
            "prefilling": jnp.zeros((W,), bool),
        })
    if btab is not None:
        st["btab"] = btab
    if gcfg.spec_k > 0:
        assert draft_cfg is not None
        # the draft's per-slot state: small contiguous full-attention
        # cache (never paged — it is a fraction of the target's
        # footprint) plus, under chunked admission, its own prompt
        # cursor and the target's prefix-skip idle counter
        st["dcache"] = cache_mod.init_cache(
            draft_cfg, W, prompt_len + N,
            dtype=jnp.dtype(draft_cfg.dtype))["blocks"]
        if gcfg.prefill_chunk > 0:
            st["dcur"] = jnp.zeros((W,), jnp.int32)
            st["pdelay"] = jnp.zeros((W,), jnp.int32)
    return st


# ---------------------------------------------------------------------------
# Host-driven engine loop
# ---------------------------------------------------------------------------

def serve(params, cfg: ModelConfig, prompts, rng, gcfg: GenServeConfig,
          gen_lens: Optional[Sequence[int]] = None,
          prompt_lens: Optional[Sequence[int]] = None,
          slot_failures: Optional[Dict[int, Sequence[int]]] = None,
          cancels: Optional[Dict[int, Sequence[int]]] = None,
          draft_params=None, draft_cfg: Optional[ModelConfig] = None,
          mesh=None, _in_mesh: bool = False
          ) -> Tuple[Dict[str, jnp.ndarray], Dict[str, object]]:
    """Generate for all `prompts` [B, P] with continuous batching.

    Returns (rollout dict — the exact `rl.rollout.generate` contract —
    and an engine-stats dict with the per-round wave timeline, occupancy
    trace and per-request time-to-first-token).  `gen_lens` optionally
    caps each request's budget (used by benchmarks to impose
    output-length distributions).  `prompt_lens` marks each request's
    real prompt length inside the padded [B, P] array: the chunked
    admission path (``gcfg.prefill_chunk > 0``) ingests only the real
    tokens and lands each slot at its own length; the one-shot path
    prefills the padded width (padding-as-content — the reference
    semantics), so cross-path parity holds only for uniform lengths.

    Fault/cancel hooks (both keyed on the 1-based host round index,
    applied at the top of that round):

    * ``slot_failures``: round -> decode-slot ids that die there.  The
      in-flight request is requeued from scratch — slot freed, device
      occupancy/prefill flags cleared, partial output rows zeroed,
      pages decrefed (shared prefix pages survive in the radix tree, so
      re-admission skips the cached prefill) — and the loop runs until
      it completes like any other request.
    * ``cancels``: round -> request ids to retire explicitly (no EOS,
      no budget exhaustion): dequeued if pending, evicted + zeroed if
      in-flight; their output rows are all-zero with an all-zero mask.

    ``mesh`` makes the serve loop mesh-aware: params (and draft params)
    are committed onto the mesh's TP/FSDP shardings and every engine
    program is traced under the mesh with ``parallel.sharding``
    activation hints active, so continuous-batching decode runs sharded
    over the generation group's devices.  The mesh joins the jit-program
    cache key (constraints are baked in at trace time).

    Short-conv (LFM2) models are refused: the engine's caches and
    chunked admission have no short-conv state.  The RL path generates
    them through the single-wave ``rl.rollout.generate``."""
    if cfg.has_shortconv:
        raise NotImplementedError(
            f"genserve does not run {cfg.name}: its short-conv layers' "
            "state has no place in the engine's caches or chunked "
            "admission; generate with repro.rl.rollout.generate (one "
            "wave) instead")
    gcfg.validate()
    if mesh is not None and not _in_mesh and mesh.devices.size > 1:
        from repro.parallel import sharding as sh_mod
        params = jax.device_put(params, sh_mod.named_shardings(
            mesh, sh_mod.param_tree_specs(params), params))
        if draft_params is not None:
            draft_params = jax.device_put(draft_params, sh_mod.named_shardings(
                mesh, sh_mod.param_tree_specs(draft_params), draft_params))
        rules = sh_mod.default_activation_rules(seq_shard=False)
        with mesh, sh_mod.use_hints(rules):
            return serve(params, cfg, prompts, rng, gcfg,
                         gen_lens=gen_lens, prompt_lens=prompt_lens,
                         slot_failures=slot_failures, cancels=cancels,
                         draft_params=draft_params, draft_cfg=draft_cfg,
                         mesh=mesh, _in_mesh=True)
    if mesh is not None and mesh.devices.size <= 1:
        mesh = None
    spec = gcfg.spec_k > 0
    if spec:
        assert draft_params is not None and draft_cfg is not None, \
            "spec_k > 0 requires draft_params and draft_cfg"
        assert cache_mod.supports_speculative_target(cfg), (
            "speculative decoding requires an attention-only target "
            "without rwkv_channel FFNs: recurrent state cannot roll "
            "back rejected tokens")
        assert cache_mod.supports_speculative_draft(draft_cfg), (
            "the draft must be full-window attention-only: its cache "
            "takes pre-acceptance writes that only a full (non-ring) "
            "layout can mask out after rollback")
        assert draft_cfg.vocab_size == cfg.vocab_size, \
            "draft and target must share a vocabulary"
    prompts_np = np.asarray(prompts, np.int32)
    B, P = prompts_np.shape
    N, W = gcfg.max_new_tokens, gcfg.wave
    K = min(gcfg.decode_chunk, N)
    C = gcfg.prefill_chunk
    chunked = C > 0
    if chunked and prompt_lens is not None:
        plens_np = np.clip(np.asarray(prompt_lens, np.int64), 1, P)
    else:
        plens_np = np.full((B,), P, np.int64)
    nchunks = int(np.ceil(P / C)) if chunked else 0

    limits = np.full((B,), N, np.int64) if gen_lens is None \
        else np.clip(np.asarray(gen_lens, np.int64), 1, N)
    queue = RequestQueue([Request(i, int(limits[i])) for i in range(B)],
                         policy=gcfg.admission, aging=gcfg.sjf_aging)
    table = SlotTable(W)
    ps = gcfg.page_size
    paged = ps > 0
    sharing = gcfg.prefix_cache
    if sharing:
        assert cache_mod.supports_prefix_sharing(cfg), (
            "prefix_cache requires every layer to be full-window "
            "attention: ring windows would clobber shared pages and "
            "recurrent state cannot be snapshotted at a prompt boundary "
            "(use page_size without prefix_cache for those configs)")
    if paged:
        MP, NP = _pool_pages(cfg, gcfg, P)
        identity_rows = cache_mod.identity_block_table(W, MP)
    pool = radix = None
    if sharing:
        pool = PagePool(NP, ps)
        radix = RadixCache(pool)
        slot_pages: List[List[int]] = [[] for _ in range(W)]
        slot_tokens: Dict[int, List[int]] = {}
    # measure_ttft is host-only — strip it from the program cache key so
    # flipping instrumentation never recompiles the device programs
    fns_cfg = dataclasses.replace(gcfg, measure_ttft=False)
    admit_fn, chunk_fn, install_fn, mixed_fn, copy_fn = _build_fns(
        cfg, fns_cfg, P, B, attn_mod.get_attention_impl(),
        draft_cfg if spec else None, mesh)
    state = _init_state(cfg, fns_cfg, P, B, draft_cfg if spec else None)
    spec_tokens = spec_proposed = spec_accepted = 0

    # rngs[t] drives the t-th sampling event, mirroring rollout.generate:
    # the first admission consumes rngs[0] (at its landing round when
    # chunked), decode step t consumes rngs[t].
    rngs = jax.random.split(rng, N)
    side = jax.random.fold_in(rng, 0x5EED)
    side_admit = jax.random.fold_in(side, 0)    # late-admission sampling
    side_step = jax.random.fold_in(side, 1)     # decode steps beyond rngs
    next_key = 0
    rounds: List[Tuple[float, float, float, int]] = []
    ttft: Dict[int, float] = {}
    queue_wait: Dict[int, float] = {}    # request admission wait (host)
    n_prefills = 0
    round_idx = 0
    occupied = np.zeros((W,), bool)      # device occupancy, host view
    # host mirror of each slot's remaining prefill chunks — every mixed
    # round advances every prefilling slot by exactly one chunk, so
    # landings are known without a device sync
    prefill_left = np.zeros((W,), np.int64)
    t_start = time.monotonic()

    def evict_slot(s: int) -> None:
        """Clear slot ``s``'s device + host residue after a failure or
        cancel: occupancy/prefill flags down, pages decrefed.  The
        caller has already fixed the table's books."""
        nonlocal state, occupied
        state["occupied"] = state["occupied"].at[s].set(False)
        if chunked:
            state["prefilling"] = state["prefilling"].at[s].set(False)
            prefill_left[s] = 0
        occupied = occupied.copy()  # np.asarray views of jax arrays are
        occupied[s] = False         # read-only

        if sharing and slot_pages[s]:
            pool.decref(slot_pages[s])
            slot_pages[s] = []
            slot_tokens.pop(s, None)

    def zero_request_rows(rid: int) -> None:
        """Wipe a request's partial output (tokens, logprobs, mask) so a
        requeued regeneration — possibly shorter — leaves no stale
        columns, and a cancelled request reads as all-masked."""
        nonlocal state
        for k in ("gen", "lp", "mask"):
            state[k] = state[k].at[rid].set(0)

    while len(queue) or table.active:
        round_idx += 1
        # requeued requests legitimately extend the round budget: each
        # re-admission costs at most one extra full request lifetime
        budget_reqs = B + table.requeued
        assert round_idx <= 2 * budget_reqs * (N + 1) \
            + budget_reqs * (nchunks + 1), \
            "genserve loop did not converge"
        t0 = time.monotonic()
        # span opened/closed manually: the loop body stays un-indented
        # (an aborted round is simply not recorded)
        rspan = obs_trace.span("gen.round", round=round_idx)
        rspan.__enter__()
        if slot_failures and round_idx in slot_failures:
            for s in slot_failures[round_idx]:
                rid = table.fail_slot(int(s))
                if rid == FREE:
                    continue
                evict_slot(int(s))
                zero_request_rows(rid)
                queue.push(Request(rid, int(limits[rid])))
        if cancels and round_idx in cancels:
            for rid in cancels[round_idx]:
                rid = int(rid)
                if queue.cancel(rid):
                    # never admitted: only the cancel is counted
                    table.cancelled += 1
                    obs_metrics.counter("gen.cancelled").inc()
                    continue
                if rid in table.slot_req:
                    s = table.slot_req.index(rid)
                    table.cancel_slot(s)
                    evict_slot(s)
                    zero_request_rows(rid)
        obs_metrics.gauge("gen.queue_depth").set(len(queue))
        admitted = 0
        may_live = False
        reqs: List[Request] = []
        free = table.free_slots()
        if free and len(queue):
            aspan = obs_trace.span("gen.install" if chunked
                                   else "gen.admit")
            aspan.__enter__()
            reqs = queue.pop(len(free))
            slots = free[:len(reqs)]
            pb = np.broadcast_to(prompts_np[reqs[0].rid],
                                 (W, P)).copy()
            admit_mask = np.zeros((W,), bool)
            rows = np.full((W,), B, np.int32)
            lim = np.ones((W,), np.int32)
            pl = np.full((W,), P, np.int32)
            for s, rq in zip(slots, reqs):
                pb[s] = prompts_np[rq.rid]
                admit_mask[s] = True
                rows[s] = rq.rid
                lim[s] = rq.max_new_tokens
                pl[s] = plens_np[rq.rid]
            if chunked:
                pstarts = np.zeros((W,), np.int32)
                btab_new = None
                if paged:
                    btab_new = np.full((W, MP), NP, np.int32)
                if sharing:
                    copy_src: List[int] = []
                    copy_dst: List[int] = []
                    cow_srcs: List[int] = []
                    for s, rq in zip(slots, reqs):
                        p = int(plens_np[rq.rid])
                        toks = prompts_np[rq.rid, :p].tolist()
                        # cap the hit at p-1 tokens: the landing chunk
                        # must always run so first-token logits come
                        # from a real forward pass
                        full, part = radix.match(toks, p - 1)
                        pool.incref(full)          # the slot's refs
                        if part is not None:
                            # keep the COW source alive across eviction
                            # until its copy is scheduled
                            pool.incref([part[0]])
                            cow_srcs.append(part[0])
                        need = MP - len(full)
                        if pool.available() < need:
                            radix.evict(need - pool.available())
                        fresh = pool.alloc(need)
                        assert fresh is not None, "page pool exhausted"
                        row = full + fresh
                        btab_new[s, :len(row)] = row
                        pstart = len(full) * ps
                        if part is not None:
                            copy_src.append(part[0])
                            copy_dst.append(fresh[0])
                            pstart += part[1]
                        pstarts[s] = pstart
                        slot_pages[s] = row
                        slot_tokens[s] = toks
                        table.record_prefix(pstart, p)
                        # speculative: the draft ingests the full prompt
                        # (no radix cache), so its chunk count paces the
                        # slot; the target idles for the difference
                        prefill_left[s] = -(-p // C) if spec \
                            else -(-(p - pstart) // C)
                    if copy_src:
                        src = np.full((W,), NP, np.int32)
                        dst = np.full((W,), NP, np.int32)
                        src[:len(copy_src)] = copy_src
                        dst[:len(copy_dst)] = copy_dst
                        state = copy_fn(state, src, dst)
                    if cow_srcs:
                        pool.decref(cow_srcs)
                else:
                    for s, rq in zip(slots, reqs):
                        p = int(plens_np[rq.rid])
                        if paged:
                            btab_new[s] = identity_rows[s]
                        table.record_prefix(0, p)
                        prefill_left[s] = -(-p // C)
                if paged:
                    state = install_fn(state, pb, admit_mask, rows, lim,
                                       pl, pstarts, jnp.asarray(btab_new))
                else:
                    state = install_fn(state, pb, admit_mask, rows, lim, pl)
            else:
                key = rngs[0] if next_key == 0 \
                    else jax.random.fold_in(side_admit, round_idx)
                if spec:
                    state = admit_fn(params, draft_params, state, pb,
                                     admit_mask, rows, lim, key)
                else:
                    state = admit_fn(params, state, pb, admit_mask, rows,
                                     lim, key)
                next_key = max(next_key, 1)
                if gcfg.measure_ttft:
                    # first tokens exist once the admit program completes
                    # — stamp TTFT on the sampled-token leaf (this sync
                    # serializes admission against the decode chunk, so
                    # it is opt-in)
                    jax.block_until_ready(state["tok"])
                    now = time.monotonic()
                    for rq in reqs:
                        ttft[rq.rid] = now - t_start
                # host-side liveness bound — a synced read of `occupied`
                # here would serialize admission against the decode
                # chunk; this is conservative only for first-token EOS
                # (one chunk of bounded waste in that rare case)
                may_live = any(
                    rq.max_new_tokens > 1
                    and (gcfg.eos_token is None
                         or prompts_np[rq.rid, -1] != gcfg.eos_token)
                    for rq in reqs)
            table.admit(slots, reqs)
            n_prefills += 1
            admitted = len(reqs)
            now_adm = time.monotonic()
            for rq in reqs:
                queue_wait[rq.rid] = now_adm - t_start
            aspan.set("admitted", admitted)
            aspan.__exit__(None, None, None)

        counts = ()
        if chunked and prefill_left.any():
            # mixed wave-step: a scan of (decode step + prefill chunk)
            # sub-rounds, sized to the outstanding prefill work (slots
            # landing mid-scan decode for the remaining sub-rounds)
            decode_live = occupied.any()
            active_left = prefill_left[prefill_left > 0]
            k_len = int(min(K, active_left.max()))
            # reference-schedule bookkeeping: which rngs[t] each decode
            # sub-round consumes.  While nothing decodes, sub-rounds
            # before the first landing (index f) burn side keys only;
            # the first landing takes rngs[0]; decode resumes at rngs[1]
            # right after it — token-exact vs rollout.generate on a
            # single-wave batch.
            if decode_live:
                key_idx = list(range(next_key, next_key + k_len))
                next_key += k_len
                land_r0 = -1
            else:
                f = int(active_left.min()) - 1
                if k_len <= f:          # no landing inside this scan
                    key_idx = [None] * k_len
                    land_r0 = -1
                else:
                    start = max(next_key, 1)
                    key_idx = [None] * (f + 1) + \
                        [start + j for j in range(k_len - f - 1)]
                    land_r0 = f if next_key == 0 else -1
                    next_key = start + (k_len - f - 1)
            keys = jnp.stack([
                jax.random.fold_in(side_step,
                                   1_000_000 + round_idx * (K + 1) + j)
                if i is None
                else (rngs[i] if i < N
                      else jax.random.fold_in(side_step, i))
                for j, i in enumerate(key_idx)])
            k_lands = jnp.stack([
                rngs[0] if j == land_r0
                else jax.random.fold_in(side_admit,
                                        round_idx * (K + 1) + j)
                for j in range(k_len)])
            with obs_trace.span("gen.mixed", subrounds=k_len,
                                spec_k=gcfg.spec_k):
                if spec:
                    state, (d4, p) = mixed_fn(params, draft_params,
                                              state, keys, k_lands)
                    d, toks, props, accs = d4
                    spec_tokens += int(np.asarray(toks).sum())
                    spec_proposed += int(np.asarray(props).sum())
                    spec_accepted += int(np.asarray(accs).sum())
                else:
                    state, (d, p) = mixed_fn(params, state, keys, k_lands)
                counts = np.asarray(d)         # device sync
            table.record_round(counts, np.asarray(p))
            occupied = np.asarray(state["occupied"])
            landed = (prefill_left > 0) & (prefill_left <= k_len)
            if gcfg.measure_ttft:
                # free here: the occupied read above already synced
                now = time.monotonic()
                for s in np.nonzero(landed)[0]:
                    ttft[table.slot_req[s]] = now - t_start
            if sharing:
                # a landed prompt's complete pages enter the radix tree
                # (insert-at-landing: earlier rounds' matches can never
                # map pages whose KV has not been written yet).  The
                # trailing partial page stays private — decode tokens
                # share it with the prompt tail.
                for s in np.nonzero(landed)[0]:
                    toks = slot_tokens[s]
                    radix.insert(toks, slot_pages[s][:len(toks) // ps])
            prefill_left = np.maximum(prefill_left - k_len, 0)
        elif occupied.any() or may_live:
            # decode only when a slot can be occupied: requests that
            # finished at admission (budget 1, prompt-dead) never burn
            # wave steps
            keys = jnp.stack(
                [rngs[i] if i < N else jax.random.fold_in(side_step, i)
                 for i in range(next_key, next_key + K)])
            with obs_trace.span("gen.decode", steps=K,
                                spec_k=gcfg.spec_k):
                if spec:
                    state, (cnt, toks, props, accs) = chunk_fn(
                        params, draft_params, state, keys)
                    spec_tokens += int(np.asarray(toks).sum())
                    spec_proposed += int(np.asarray(props).sum())
                    spec_accepted += int(np.asarray(accs).sum())
                    counts = np.asarray(cnt)   # device sync
                else:
                    state, counts = chunk_fn(params, state, keys)
                    counts = np.asarray(counts)    # device sync
            next_key += K
            table.record_step(counts)
            occupied = np.asarray(state["occupied"])

        if sharing:
            prev_slot_req = list(table.slot_req)
        table.retire_finished(occupied | (prefill_left > 0))
        if sharing:
            # retired slots drop their page references; pages shared
            # with the radix tree or other slots survive the decref
            for s in range(W):
                if prev_slot_req[s] != FREE and table.slot_req[s] == FREE:
                    pool.decref(slot_pages[s])
                    slot_pages[s] = []
                    slot_tokens.pop(s, None)
        t1 = time.monotonic()
        occ = float(np.mean(counts)) if len(counts) else 0.0
        rounds.append((t0, t1, occ, admitted))
        rspan.set("occupancy", occ)
        rspan.__exit__(None, None, None)

    gen = np.asarray(state["gen"])[:B]
    lp = np.asarray(state["lp"])[:B]
    mask = np.asarray(state["mask"])[:B]
    res = {"sequences": jnp.concatenate(
               [jnp.asarray(prompts_np), jnp.asarray(gen)], axis=1),
           "gen_tokens": jnp.asarray(gen),
           "logprobs": jnp.asarray(lp),
           "mask": jnp.asarray(mask)}
    stats = {"engine": "genserve", "wave": W,
             "decode_steps": table.decode_steps,
             "slot_steps": table.slot_steps,
             "mean_occupancy": table.mean_occupancy(),
             "busy_occupancy": table.busy_occupancy(),
             "occupancy_trace": list(table.occupancy_trace),
             "prefill_trace": list(table.prefill_trace),
             "prefill_rounds": table.prefill_rounds,
             "prefill_slot_steps": table.prefill_slot_steps,
             "prefill_chunk": C, "prompt_len": P,
             "max_new_tokens": N,
             "prefill_rounds_per_req":
                 float(np.mean(np.ceil(plens_np / C))) if chunked else 0.0,
             "ttft": ttft, "queue_wait": queue_wait,
             "rounds": rounds, "prefills": n_prefills,
             "admitted": table.admitted, "retired": table.retired,
             "requeued": table.requeued, "cancelled": table.cancelled,
             "page_size": ps, "prefix_cache": sharing,
             "prefix_hit_rate": table.prefix_hit_rate(),
             "prefill_tokens_skipped": table.prefix_hit_tokens,
             "prompt_tokens": table.prompt_tokens,
             "spec_k": gcfg.spec_k,
             "spec_proposed": spec_proposed,
             "spec_accepted": spec_accepted,
             "spec_tokens": spec_tokens,
             "accept_rate": (spec_accepted / spec_proposed)
                 if spec_proposed else 0.0}
    # registry metrics: one batch of updates per serve() call (the hot
    # round loop only touches the queue-depth gauge)
    obs_metrics.counter("gen.tokens").inc(
        spec_tokens if spec else table.slot_steps)
    if spec:
        obs_metrics.counter("gen.spec_proposed").inc(spec_proposed)
        obs_metrics.counter("gen.spec_accepted").inc(spec_accepted)
        obs_metrics.histogram("gen.spec_accept_rate").observe(
            stats["accept_rate"])
    obs_metrics.counter("gen.requests").inc(table.retired)
    obs_metrics.histogram("gen.wave_occupancy").observe(
        table.mean_occupancy())
    obs_metrics.counter("gen.prefix_hit_tokens").inc(table.prefix_hit_tokens)
    obs_metrics.counter("gen.prompt_tokens").inc(table.prompt_tokens)
    ttft_h = obs_metrics.histogram("gen.ttft_s")
    for v in ttft.values():
        ttft_h.observe(v)
    qw_h = obs_metrics.histogram("gen.queue_wait_s")
    for v in queue_wait.values():
        qw_h.observe(v)
    if sharing:
        # teardown invariants: every slot is retired, so remaining page
        # references must be exactly the radix tree's — anything beyond
        # is a leak (warned + counted; raises under REPRO_OBS_STRICT=1)
        obs_metrics.gauge("pagepool.utilization").set(pool.utilization())
        pool.leak_check(expected_refs=radix.page_refs())
        # debug/test handles (host-side structures, no device state)
        stats["_pagepool"] = pool
        stats["_radix"] = radix
    return res, stats
