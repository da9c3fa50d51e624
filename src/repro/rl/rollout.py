"""Actor generation: autoregressive sampling with KV cache.

Prefill the prompt once (forward with return_cache), then lax.scan over
decode steps.  Returns sequences, per-token logprobs and the validity mask
(positions after EOS are masked out).

This is the *single-wave* reference path: every sequence decodes all
``max_new_tokens`` steps, finished-or-not (dead rows are masked, not
retired).  The continuous-batching engine (``repro.genserve``) retires
finished slots and back-fills them from a request queue; when the batch
fits in one decode wave the two paths produce identical masked outputs
under the same rng (genserve's equivalence tests pin this).  EOS and mask
semantics are shared via ``models.sampling``.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import sampling
from repro.models import transformer as T
from repro.models.config import ModelConfig


@dataclasses.dataclass(frozen=True)
class SamplerConfig:
    max_new_tokens: int = 16
    temperature: float = 1.0
    eos_token: Optional[int] = None
    greedy: bool = False


def _sample(rng, logits, cfg: SamplerConfig):
    return sampling.sample_with_logprobs(
        rng, logits, temperature=cfg.temperature, greedy=cfg.greedy)


def generate(params, cfg: ModelConfig, prompts, rng,
             sampler: SamplerConfig) -> Dict[str, jnp.ndarray]:
    """prompts: [B, P] int32.  Returns dict with
    sequences [B, P+N], gen_tokens [B, N], logprobs [B, N], mask [B, N],
    and for an MoE model ``moe_load``: the prefill's and every decode
    step's token-to-held-expert assignments ([n_moe_layers, n_held])."""
    B, P = prompts.shape
    N = sampler.max_new_tokens
    out = T.forward(params, cfg, {"tokens": prompts}, return_cache=True,
                    max_cache_len=P + N, remat=False)
    cache = out["cache"]
    logits0 = out["logits"][:, -1]

    rngs = jax.random.split(rng, N)
    tok0, lp0 = _sample(rngs[0], logits0, sampler)

    moe = cfg.has_moe

    def step(carry, rng_t):
        cache, tok, alive, *load = carry
        logits, cache, *step_load = T.decode_step(
            params, cfg, tok[:, None], cache, with_load=moe)
        nxt, lp = _sample(rng_t, logits, sampler)
        alive_next = sampling.next_alive(alive, tok, sampler.eos_token)
        load = [load[0] + step_load[0]] if moe else []
        return (cache, nxt, alive_next, *load), (nxt, lp, alive_next)

    # a prompt that already ends with EOS starts dead: its first sampled
    # token is recorded but invalid (shared edge semantics with genserve)
    alive0 = sampling.initial_alive(prompts, sampler.eos_token)
    load0 = [out["moe_load"]] if moe else []
    (_, _, _, *load), (toks, lps, alives) = jax.lax.scan(
        step, (cache, tok0, alive0, *load0), rngs[1:])
    gen = jnp.concatenate([tok0[:, None], toks.T], axis=1)       # [B, N]
    logprobs = jnp.concatenate([lp0[:, None], lps.T], axis=1)
    mask = jnp.concatenate([alive0[:, None], alives.T], axis=1)
    sequences = jnp.concatenate([prompts, gen], axis=1)
    ro = {"sequences": sequences, "gen_tokens": gen,
          "logprobs": logprobs, "mask": mask.astype(jnp.float32)}
    if moe:
        ro["moe_load"] = load[0]
    return ro


def sequence_logprobs(params, cfg: ModelConfig, sequences, gen_start: int):
    """Teacher-forced logprobs of the generated part. sequences [B, S].

    Returns logprobs [B, S - gen_start] for tokens at positions
    gen_start..S-1 (each predicted from the previous position)."""
    out = T.forward(params, cfg, {"tokens": sequences}, remat=False)
    logits = out["logits"][:, gen_start - 1:-1]
    targets = sequences[:, gen_start:]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0], out
