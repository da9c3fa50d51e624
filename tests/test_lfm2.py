"""LFM2-8B-A1B's layers against the plain reference (models/reference.py)
at a small size on seeded random weights, float32 on the CPU.

The small model keeps the cut's structure: the first six of the
published layer types (two dense short-conv layers, then one attention
and three short-conv layers with experts), a 32-wide sigmoid router with
a selection bias, top-4, and 8 held experts from offset 8.

Tolerances: the program and the reference compute the same float32
arithmetic in different orders (fused vs per-sequence products, sorted
grouped matmuls vs masked dense experts), which moves logits of order 1
by about 1e-6; 1e-4 is that with room, and a missing term (a dropped
token, a conv tap, the bias in the weights) moves them by 1e-2 or more.
"""
import dataclasses
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import archs
from repro.models import moe
from repro.models import reference as ref
from repro.models import transformer as T
from repro.optim import adam
from repro.rl.trainer import RLConfig, make_actor_step

KEY = jax.random.PRNGKey(0)
TOL = dict(rtol=1e-4, atol=1e-4)


def small_cfg(**kw):
    base = dict(
        name="lfm2-small", n_layers=6, d_model=64, n_heads=4, n_kv_heads=2,
        head_dim=16, d_ff=128, expert_d_ff=32, vocab_size=256,
        pattern=archs.lfm2_pattern(archs.LFM2_LAYER_TYPES[:6], 2),
        experts_held=8, expert_offset=8, dtype="float32")
    base.update(kw)
    return dataclasses.replace(archs.LFM2_8B_A1B, **base)


def seeded_params(cfg, key=KEY):
    """init_params with the norm scales and the expert bias drawn too,
    so that neither sits at its neutral init."""
    def redraw(path, x):
        name = jax.tree_util.keystr(path)
        k = jax.random.fold_in(jax.random.PRNGKey(1),
                               zlib.crc32(name.encode()) % 2 ** 31)
        if name.endswith("['scale']"):
            return 1.0 + 0.2 * jax.random.normal(k, x.shape)
        if name.endswith("['expert_bias']"):
            return 0.3 * jax.random.normal(k, x.shape)
        return x
    return jax.tree_util.tree_map_with_path(redraw, T.init_params(key, cfg))


def tokens(cfg, B=2, S=24, key=3):
    return jax.random.randint(jax.random.PRNGKey(key), (B, S), 0,
                              cfg.vocab_size, jnp.int32)


def test_forward_logits_match_reference():
    cfg = small_cfg()
    params = seeded_params(cfg)
    toks = tokens(cfg)
    got = T.forward(params, cfg, {"tokens": toks}, remat=False)
    want = ref.logits(params, cfg, toks)
    np.testing.assert_allclose(np.asarray(got["logits"]), np.asarray(want),
                               **TOL)
    # four expert layers, 24 tokens x 2 rows x 4 choices over 32 experts:
    # what lands on the 8 held here
    assert got["moe_load"].shape == (4, 8)
    assert 0 < int(got["moe_load"].sum()) <= 4 * 48 * 4


def test_prefill_then_decode_matches_reference():
    """Prefill 16 tokens into the cache (conv states and k/v), then 8
    decode steps through it: each step's logits are the reference's full
    forward at that position."""
    cfg = small_cfg()
    params = seeded_params(cfg)
    toks = tokens(cfg)
    want = np.asarray(ref.logits(params, cfg, toks))
    P = 16
    out = T.forward(params, cfg, {"tokens": toks[:, :P]}, return_cache=True,
                    max_cache_len=24, remat=False)
    np.testing.assert_allclose(np.asarray(out["logits"][:, -1]),
                               want[:, P - 1], **TOL)
    cache = out["cache"]
    assert cache["blocks"]["layer0"]["conv"].shape == (1, 2, 2, 64)
    assert cache["blocks"]["layer2"]["k"].shape == (1, 2, 24, 2, 16)
    for t in range(P, 24):
        logits, cache = T.decode_step(params, cfg, toks[:, t:t + 1], cache)
        np.testing.assert_allclose(np.asarray(logits), want[:, t], **TOL)


def _norms(tree):
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): float(jnp.linalg.norm(x))
            for p, x in flat}


def test_actor_step_loss_and_gradient_match_reference():
    """One GRPO actor step: the loss, the gradient's global norm and each
    leaf's gradient norm (Adam's first moment after one step is
    (1 - b1) times the clipped gradient) against the reference's; the
    expert bias has no gradient and no optimizer state and stays put."""
    cfg = small_cfg()
    params = seeded_params(cfg)
    B, P, N = 4, 8, 8
    seqs = tokens(cfg, B, P + N, key=5)
    k1, k2 = jax.random.split(jax.random.PRNGKey(6))
    batch = {"sequences": seqs,
             "logp_old": -5.5 + 0.1 * jax.random.normal(k1, (B, N)),
             "advantages": jax.random.normal(k2, (B, N)),
             "mask": jnp.ones((B, N)).at[0, 5:].set(0.0)}
    rl = RLConfig(lr=1e-4, clip_eps=0.2)
    opt = adam.init_adam_state(params, adam.AdamConfig(lr=rl.lr))
    assert "expert_bias" not in opt["m"]["blocks"]["layer2"]["moe"]
    new, opt2, m = jax.jit(make_actor_step(cfg, rl), static_argnums=3)(
        params, opt, batch, P)
    loss, grads = ref.grpo_grad(params, cfg, batch, P, rl.clip_eps)
    np.testing.assert_allclose(float(m["loss"]), float(loss), **TOL)
    gnorm = float(adam.global_norm(grads))
    np.testing.assert_allclose(float(m["grad_norm"]), gnorm, rtol=1e-4)
    scale = min(1.0, 1.0 / gnorm)
    got = _norms(jax.tree_util.tree_map(lambda x: x / 0.1, opt2["m"]))
    want = _norms(jax.tree_util.tree_map(lambda x: x * scale, grads))
    assert got.keys() == want.keys()
    med = float(np.median(list(want.values())))
    for k in want:
        assert abs(got[k] - want[k]) <= 1e-3 * max(want[k], med), k
    np.testing.assert_array_equal(
        np.asarray(new["blocks"]["layer3"]["moe"]["expert_bias"]),
        np.asarray(params["blocks"]["layer3"]["moe"]["expert_bias"]))


def test_expert_shares_sum_to_the_whole_layer():
    """Four chips' shares of 8 of the 32 experts: their outputs (the
    router, which every chip computes alike, counted once) add up to the
    uncut layer holding all 32."""
    whole = small_cfg(experts_held=32, expert_offset=0)
    p = seeded_params(whole)["blocks"]["layer3"]["moe"]
    p = jax.tree_util.tree_map(lambda a: a[0], p)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 12, whole.d_model))
    y_whole, _, load_whole = moe.apply_moe(p, whole, whole.pattern[3].ffn, x)
    parts, loads = 0.0, []
    for c in range(4):
        share = dataclasses.replace(whole, experts_held=8,
                                    expert_offset=8 * c)
        ps = dict(p, experts=jax.tree_util.tree_map(
            lambda a: a[8 * c:8 * c + 8], p["experts"]))
        y, _, load = moe.apply_moe(ps, share, share.pattern[3].ffn, x)
        parts = parts + y
        loads.append(load)
        with jax.default_matmul_precision("highest"):
            np.testing.assert_allclose(
                np.asarray(y[0]),
                np.asarray(ref.experts(ps, share, x[0])), **TOL)
    np.testing.assert_allclose(np.asarray(parts), np.asarray(y_whole), **TOL)
    np.testing.assert_array_equal(np.concatenate(loads),
                                  np.asarray(load_whole))
    assert int(load_whole.sum()) == 2 * 12 * whole.top_k


@pytest.mark.parametrize("S", [1, 12], ids=["decode", "grouped"])
def test_dropless_when_every_token_picks_one_expert(S):
    """A bias that sends every token to held expert 9: it gets all 24
    tokens (at GShard's capacity factor 1.25 it would keep 24 x 4 / 32 x
    1.25 = 3) and the output is the reference's."""
    cfg = small_cfg()
    p = jax.tree_util.tree_map(lambda a: a[0], seeded_params(cfg)[
        "blocks"]["layer3"]["moe"])
    p["expert_bias"] = jnp.zeros((32,)).at[9].set(10.0)
    x = jax.random.normal(jax.random.PRNGKey(4), (24 // S, S, cfg.d_model))
    y, _, load = moe.apply_moe(p, cfg, cfg.pattern[3].ffn, x)
    assert int(load[9 - cfg.expert_offset]) == 24
    with jax.default_matmul_precision("highest"):
        want = ref.experts(p, cfg, x.reshape(24, -1))
    np.testing.assert_allclose(np.asarray(y.reshape(24, -1)),
                               np.asarray(want), **TOL)


def test_grouped_experts_ignore_rows_outside_every_group(monkeypatch):
    """ragged_dot leaves the rows past the last group undefined (on the
    TPU they are uninitialised memory).  With those rows, and the same
    rows of its input gradient, filled with NaN, the grouped layer's
    output and its gradients stay finite and as before."""
    cfg = small_cfg()
    p = jax.tree_util.tree_map(lambda a: a[0], seeded_params(cfg)[
        "blocks"]["layer3"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 12, cfg.d_model))
    real = jax.lax.ragged_dot

    def tail_nan(a, sizes):
        rows = jnp.arange(a.shape[0])[:, None]
        return jnp.where(rows < sizes.sum(), a, jnp.nan)

    @jax.custom_vjp
    def undefined_tail(lhs, rhs, sizes):
        return tail_nan(real(lhs, rhs, sizes), sizes)

    def fwd(lhs, rhs, sizes):
        return undefined_tail(lhs, rhs, sizes), (lhs, rhs, sizes)

    def bwd(res, g):
        lhs, rhs, sizes = res
        _, vjp = jax.vjp(lambda a, b: real(a, b, sizes), lhs, rhs)
        d_lhs, d_rhs = vjp(g)
        return tail_nan(d_lhs, sizes), d_rhs, None
    undefined_tail.defvjp(fwd, bwd)

    def loss(p, x):
        y, _, _ = moe.apply_moe(p, cfg, cfg.pattern[3].ffn, x)
        return jnp.sum(jnp.sin(y)), y

    (_, y0), g0 = jax.value_and_grad(loss, (0, 1), has_aux=True)(p, x)
    monkeypatch.setattr(jax.lax, "ragged_dot",
                        lambda lhs, rhs, sizes: undefined_tail(lhs, rhs,
                                                               sizes))
    (_, y1), g1 = jax.value_and_grad(loss, (0, 1), has_aux=True)(p, x)
    for a, b in zip(jax.tree_util.tree_leaves((y0, g0)),
                    jax.tree_util.tree_leaves((y1, g1))):
        assert bool(jnp.isfinite(b).all())
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), **TOL)


def test_expert_bias_moves_selection_not_weights():
    """The bias decides which experts are chosen; the weights are the
    chosen experts' sigmoid scores, renormalised, without the bias."""
    cfg = small_cfg()
    p = jax.tree_util.tree_map(lambda a: a[0], seeded_params(cfg)[
        "blocks"]["layer3"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(4), (40, cfg.d_model))
    w0, e0, _ = moe.route(dict(p, expert_bias=jnp.zeros(32)), cfg, x)
    w1, e1, _ = moe.route(p, cfg, x)
    assert (np.sort(np.asarray(e0), -1) != np.sort(np.asarray(e1), -1)).any()
    scores = jax.nn.sigmoid(x @ p["router"])
    for w, e in ((w0, e0), (w1, e1)):
        s = jnp.take_along_axis(scores, e, -1)
        np.testing.assert_allclose(
            np.asarray(w), np.asarray(s / (s.sum(-1, keepdims=True) + 1e-6)),
            rtol=1e-6)


def test_genserve_refuses_shortconv():
    from repro.genserve.decoder import GenServeConfig, serve
    cfg = small_cfg()
    with pytest.raises(NotImplementedError, match="short-conv"):
        serve(None, cfg, np.zeros((2, 4), np.int32), KEY,
              GenServeConfig(wave=2, max_new_tokens=2))
