"""Operations and bytes of an MoE model's work (the program's
``ModelConfig``: LFM2's short-conv and attention layers, dense and expert
FFNs), counted from shapes and from the expert assignments the program
counts (``moe.assignments.*``).

As in ``bench/flops.py`` these are the least the algorithm needs: an
expert's work is counted for the tokens routed to it, not for the dense
evaluation of every held expert that decode runs; a recomputed pass or a
gathered copy is not counted.
"""
from __future__ import annotations

from repro.models.config import Mixer


def _mixer_weights(cfg, spec) -> int:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    if spec.mixer == Mixer.ATTENTION:
        return d * hd * (2 * cfg.n_heads + 2 * cfg.n_kv_heads)
    return 4 * d * d                          # in_proj (3d) + out_proj


def token_weights(cfg) -> int:
    """Matrix weights every token multiplies through, over all layers:
    mixers, dense FFNs and routers (not the experts, not the head)."""
    d, R = cfg.d_model, cfg.n_pattern_repeats
    per = 0
    for spec in cfg.pattern:
        per += _mixer_weights(cfg, spec)
        per += d * cfg.n_experts if spec.moe else 3 * d * cfg.d_ff
    return per * R


def n_attention(cfg) -> int:
    return sum(s.mixer == Mixer.ATTENTION
               for s in cfg.pattern) * cfg.n_pattern_repeats


def n_conv(cfg) -> int:
    return sum(s.mixer == Mixer.SHORTCONV
               for s in cfg.pattern) * cfg.n_pattern_repeats


def expert_flops(cfg, assignments: float) -> float:
    """One forward pass of the held experts over ``assignments``
    token-to-expert assignments."""
    return 2.0 * 3 * cfg.d_model * cfg.resolved_expert_d_ff * assignments


def forward_flops(cfg, tokens, ctx_sum, head_tokens, assignments) -> float:
    """One forward pass over ``tokens`` tokens whose attention contexts
    sum to ``ctx_sum`` (per attention layer), the conv taps, the experts'
    ``assignments`` and the vocabulary projection of ``head_tokens``."""
    return (2.0 * token_weights(cfg) * tokens
            + 2.0 * cfg.conv_kernel * cfg.d_model * n_conv(cfg) * tokens
            + 4.0 * cfg.n_heads * cfg.resolved_head_dim * ctx_sum
            * n_attention(cfg)
            + expert_flops(cfg, assignments)
            + 2.0 * cfg.vocab_size * cfg.d_model * head_tokens)


def causal_ctx(S: float) -> float:
    return S * (S + 1) / 2.0


def actor_step(cfg, rows, seq_len, gen_len, assignments):
    """(flops, bytes) of one actor update: forward and backward (3x the
    forward's operations) over ``rows`` sequences of ``seq_len`` tokens
    scoring their last ``gen_len``, with the forward's ``assignments``;
    the parameters read twice in bf16, the gradient written and read in
    bf16, Adam's two float32 moments read and written, the parameters
    read and written (``bench.flops.actor_step``'s traffic)."""
    f = 3.0 * forward_flops(cfg, rows * seq_len, rows * causal_ctx(seq_len),
                            rows * gen_len, assignments)
    return f, float(cfg.param_count() * (2 * 2 + 2 * 2 + 4 * 4 + 2 * 2))


def generate(cfg, rows, prompt_len, steps, assignments):
    """Least seconds-worth of work of one single-wave generate call as
    (flops, bytes) per phase: the prefill of ``rows`` prompts, then
    ``steps`` decode steps of all rows, each reading every held weight
    once, the k/v cache up to its position and the conv states (read and
    written, float32).  ``assignments`` (the call's count) are spread
    over the tokens processed.  Returns a list of (flops, bytes)."""
    P, d = prompt_len, cfg.d_model
    per_token = assignments / max(rows * (P + steps), 1)
    weights = 2.0 * cfg.param_count()
    kv_token = 2 * cfg.n_kv_heads * cfg.resolved_head_dim * 2.0 \
        * n_attention(cfg)
    conv_state = 2 * (cfg.conv_kernel - 1) * d * 4.0 * n_conv(cfg)
    phases = [(forward_flops(cfg, rows * P, rows * causal_ctx(P), rows,
                             per_token * rows * P),
               weights + rows * P * kv_token)]
    for s in range(steps):
        ctx = P + s + 1
        phases.append((forward_flops(cfg, rows, rows * ctx, rows,
                                     per_token * rows),
                       weights + rows * ctx * kv_token + rows * conv_state))
    return phases


def rl_iteration_flops(cfg, rows, prompt_len, gen_tokens_valid, gen_assign,
                       train_assign):
    """Model operations of one GRPO iteration: generation (the prompts'
    prefill and one decode step per further valid token), the reference
    model's forward and the actor's forward and backward.  Expert work
    is the counted assignments: generation's, and the actor forward's
    (which the reference forward, over the same sequences, is taken to
    match)."""
    S = prompt_len + gen_tokens_valid / max(rows, 1)
    n = max(gen_tokens_valid - rows, 0)
    gen = forward_flops(cfg, rows * prompt_len, rows * causal_ctx(prompt_len),
                        rows, 0.0) \
        + forward_flops(cfg, n, n * (prompt_len + S) / 2.0, n, 0.0)
    seq = forward_flops(cfg, rows * S, rows * causal_ctx(S),
                        gen_tokens_valid, 0.0)
    return gen + 4.0 * seq + expert_flops(cfg, gen_assign
                                          + 4.0 * train_assign)
