"""Per-architecture smoke tests: reduced variant (<=2 layers, d_model<=512,
<=4 experts) instantiates and runs one forward + one train step on CPU,
asserting output shapes and finiteness. Decode parity for causal archs."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import archs
from repro.configs.shapes import token_splits
from repro.launch import steps as steps_mod
from repro.models import transformer as T
from repro.optim import adam

ALL_ARCHS = sorted(archs.ARCHS)
KEY = jax.random.PRNGKey(0)


def smoke_inputs(cfg, batch=2, seq=32):
    n_feat, n_tok = token_splits(cfg, seq)
    n_feat = min(n_feat, seq // 2) if n_feat else 0
    n_tok = seq - n_feat
    out = {}
    if n_feat:
        out["features"] = jax.random.normal(
            KEY, (batch, n_feat, cfg.feature_dim), jnp.dtype(cfg.dtype))
    if n_tok:
        out["tokens"] = jax.random.randint(KEY, (batch, n_tok), 0,
                                           cfg.vocab_size, jnp.int32)
    return out


@pytest.mark.parametrize("name", ALL_ARCHS)
def test_reduced_config_limits(name):
    cfg = archs.get(name, smoke=True)
    cfg.validate()
    assert cfg.n_layers <= 2
    assert cfg.d_model <= 512
    if cfg.has_moe:
        assert cfg.n_experts <= 4


@pytest.mark.parametrize("name", ALL_ARCHS)
def test_forward_shapes_and_finite(name):
    cfg = archs.get(name, smoke=True)
    params = T.init_params(KEY, cfg)
    B, S = 2, 32
    out = T.forward(params, cfg, smoke_inputs(cfg, B, S))
    assert out["logits"].shape == (B, S, cfg.vocab_size)
    assert bool(jnp.isfinite(out["logits"]).all())
    assert bool(jnp.isfinite(out["aux_loss"]))
    # parameter count within 5% of the analytic config estimate
    actual = T.count_params(params)
    assert abs(actual - cfg.param_count()) / actual < 0.05


@pytest.mark.parametrize("name", ALL_ARCHS)
def test_one_train_step(name):
    cfg = archs.get(name, smoke=True)
    params = T.init_params(KEY, cfg)
    opt_cfg = adam.AdamConfig(lr=1e-3)
    opt_state = adam.init_adam_state(params, opt_cfg)
    B, S = 2, 32
    batch = smoke_inputs(cfg, B, S)
    batch["labels"] = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size,
                                         jnp.int32)
    batch["loss_mask"] = jnp.ones((B, S), jnp.dtype(cfg.dtype))
    step = jax.jit(steps_mod.make_train_step(cfg, opt_cfg))
    new_params, new_opt, metrics = step(params, opt_state, batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    assert bool(jnp.isfinite(metrics["grad_norm"]))
    # parameters actually changed
    diff = sum(float(jnp.abs(a - b).sum()) for a, b in zip(
        jax.tree_util.tree_leaves(params),
        jax.tree_util.tree_leaves(new_params)))
    assert diff > 0


DECODE_ARCHS = [n for n in ALL_ARCHS
                if not archs.get(n, smoke=True).is_encoder_only
                and archs.get(n, smoke=True).frontend == "tokens"]


# cases at 4 layers: two repeats of a two-layer pattern, so the decode
# layer scan writes and reads the carried k/v stacks at a repeat index
# above 0 — gemma2 with its ring-window layer wrapping (pos > 16) beside
# a full-length layer, jamba with attention beside Mamba state
STACKED_DECODE = [("gemma2-27b", 4), ("jamba-1.5-large-398b", 4)]


@pytest.mark.parametrize("name,n_layers", [
    *[pytest.param(n, None, id=n) for n in DECODE_ARCHS],
    *[pytest.param(n, k, id=f"{n}-{k}layers") for n, k in STACKED_DECODE]])
def test_decode_matches_forward(name, n_layers):
    cfg = archs.get(name, smoke=True)
    if n_layers is not None:
        cfg = dataclasses.replace(cfg, n_layers=n_layers)
    params = T.init_params(KEY, cfg)
    B, S, Sp = 2, 24, 20
    toks = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size, jnp.int32)
    full = T.forward(params, cfg, {"tokens": toks}, remat=False)["logits"]
    out = T.forward(params, cfg, {"tokens": toks[:, :Sp]},
                    return_cache=True, max_cache_len=S, remat=False)
    cache = out["cache"]
    for t in range(Sp, S):
        logits, cache = T.decode_step(params, cfg, toks[:, t:t + 1], cache)
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(full[:, t]),
                                   rtol=2e-3, atol=2e-3)
