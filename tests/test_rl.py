"""RL math + end-to-end iteration tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.synthetic import AdditionTask, VOCAB_SIZE, EOS, PAD
from repro.models.config import ModelConfig
from repro.rl import gae, losses, rollout
from repro.rl.trainer import RLConfig, RLTrainer

KEY = jax.random.PRNGKey(0)


def naive_gae(rewards, values, mask, gamma, lam):
    B, T = rewards.shape
    values_next = np.concatenate([values[:, 1:], np.zeros((B, 1))], axis=1)
    deltas = rewards + gamma * values_next * mask - values
    adv = np.zeros_like(rewards)
    for b in range(B):
        run = 0.0
        for t in reversed(range(T)):
            run = deltas[b, t] + gamma * lam * mask[b, t] * run
            adv[b, t] = run
    return adv * mask


def test_gae_matches_naive():
    rng = np.random.default_rng(0)
    B, T = 4, 12
    rewards = rng.normal(size=(B, T)).astype(np.float32)
    values = rng.normal(size=(B, T)).astype(np.float32)
    mask = (rng.random((B, T)) > 0.2).astype(np.float32)
    adv, ret = gae.gae_advantages(jnp.asarray(rewards), jnp.asarray(values),
                                  jnp.asarray(mask), gamma=0.97, lam=0.9)
    expected = naive_gae(rewards, values, mask, 0.97, 0.9)
    np.testing.assert_allclose(np.asarray(adv), expected, rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(ret),
                               expected + values * mask, rtol=1e-5,
                               atol=1e-5)


def test_grpo_advantages_zero_mean_per_group():
    rng = np.random.default_rng(1)
    B, G, T = 12, 4, 6
    rewards = jnp.asarray(rng.normal(size=(B,)).astype(np.float32))
    mask = jnp.ones((B, T), jnp.float32)
    adv = gae.grpo_advantages(rewards, G, mask)
    per_group = np.asarray(adv)[:, 0].reshape(B // G, G)
    np.testing.assert_allclose(per_group.mean(axis=1), 0.0, atol=1e-5)


def test_ppo_loss_at_ratio_one():
    B, T = 3, 5
    logp = jnp.zeros((B, T))
    adv = jnp.asarray(np.random.default_rng(2).normal(size=(B, T)),
                      jnp.float32)
    mask = jnp.ones((B, T))
    out = losses.ppo_policy_loss(logp, logp, adv, mask)
    np.testing.assert_allclose(float(out["loss"]), float(-adv.mean()),
                               rtol=1e-6)
    assert float(out["clip_frac"]) == 0.0


def test_kl_penalised_rewards_places_score_at_last_token():
    B, T = 2, 6
    score = jnp.asarray([1.0, 2.0])
    lp = jnp.zeros((B, T))
    mask = jnp.asarray([[1, 1, 1, 0, 0, 0], [1, 1, 1, 1, 1, 1]],
                       jnp.float32)
    rewards, kl = losses.kl_penalised_rewards(score, lp, lp, mask)
    assert float(rewards[0, 2]) == 1.0
    assert float(rewards[1, 5]) == 2.0
    assert float(kl) == 0.0


def eager_batch(scores, mask, lp_old, lp_ref, values, *, algorithm,
                n_rollouts, kl_beta, whiten, gamma, lam):
    """The advantage preparation as separate eager ops, GRPO's sequence
    reward summed on the host."""
    tok_rewards, kl = losses.kl_penalised_rewards(
        jnp.asarray(scores), lp_old, lp_ref, mask, kl_beta=kl_beta)
    returns = None
    if algorithm == "ppo":
        adv, returns = gae.gae_advantages(tok_rewards, values * mask, mask,
                                          gamma=gamma, lam=lam)
    else:
        seq_reward = np.asarray(tok_rewards).sum(1)
        adv = gae.grpo_advantages(jnp.asarray(seq_reward), n_rollouts, mask)
    if whiten:
        adv = gae.whiten(adv, mask)
    return {"advantages": adv, "returns": returns, "kl": float(kl),
            "gen_len": float(np.asarray(mask).sum(1).mean()),
            "reward_mean": float(scores.mean())}


@pytest.mark.parametrize("whiten", [True, False])
@pytest.mark.parametrize("algorithm", ["grpo", "ppo"])
@pytest.mark.parametrize("masks", ["ragged", "full"])
def test_jitted_advantage_batch_matches_eager(algorithm, whiten, masks):
    """``advantage_batch`` under jit gives the eager formulas' batch to
    float32 rounding: with a row whose mask is all zero (its score lands
    on position 0) and rows valid to their last position."""
    from repro.rl.trainer import advantage_batch
    rng = np.random.default_rng(3)
    B, G, T = 8, 4, 7
    scores = rng.normal(size=(B,)).astype(np.float32)
    lp_old = jnp.asarray(rng.normal(size=(B, T)) - 2.0, jnp.float32)
    lp_ref = jnp.asarray(rng.normal(size=(B, T)) - 2.0, jnp.float32)
    values = jnp.asarray(rng.normal(size=(B, T)), jnp.float32)
    if masks == "ragged":
        lens = np.array([0, T, 3, 1, T, 5, 2, T - 1])
    else:
        lens = np.full((B,), T)
    mask = jnp.asarray(np.arange(T)[None, :] < lens[:, None], jnp.float32)
    kw = dict(algorithm=algorithm, n_rollouts=G, kl_beta=0.05,
              gamma=0.97, lam=0.9)
    run = jax.jit(advantage_batch, static_argnames=(
        "algorithm", "n_rollouts", "kl_beta", "whiten_advantages",
        "gamma", "lam"))
    got = run(jnp.asarray(scores), mask, lp_old, lp_ref,
              values if algorithm == "ppo" else None,
              whiten_advantages=whiten, **kw)
    want = eager_batch(scores, mask, lp_old, lp_ref, values, whiten=whiten,
                       **kw)
    np.testing.assert_allclose(np.asarray(got["advantages"]),
                               np.asarray(want["advantages"]),
                               rtol=1e-5, atol=1e-5)
    if algorithm == "ppo":
        np.testing.assert_allclose(np.asarray(got["returns"]),
                                   np.asarray(want["returns"]),
                                   rtol=1e-5, atol=1e-5)
    else:
        assert got["returns"] is None
    for k in ("kl", "gen_len", "reward_mean"):
        assert float(got[k]) == pytest.approx(want[k], rel=1e-6, abs=1e-7)


def tiny_cfg():
    return ModelConfig(name="tiny", n_layers=2, d_model=64, n_heads=2,
                       n_kv_heads=2, head_dim=32, d_ff=128,
                       vocab_size=VOCAB_SIZE, dtype="float32")


def test_rollout_logprobs_consistent_with_teacher_forcing():
    from repro.models import transformer as T
    cfg = tiny_cfg()
    params = T.init_params(KEY, cfg)
    prompts = jax.random.randint(KEY, (2, 8), 0, cfg.vocab_size, jnp.int32)
    sampler = rollout.SamplerConfig(max_new_tokens=5, greedy=True)
    ro = rollout.generate(params, cfg, prompts, KEY, sampler)
    lp_tf, _ = rollout.sequence_logprobs(params, cfg, ro["sequences"],
                                         gen_start=prompts.shape[1])
    np.testing.assert_allclose(np.asarray(ro["logprobs"]),
                               np.asarray(lp_tf), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("algorithm", ["grpo", "ppo"])
def test_rl_iteration_runs(algorithm):
    cfg = tiny_cfg()
    task = AdditionTask(max_operand=9)
    rl = RLConfig(algorithm=algorithm, n_rollouts=4, max_new_tokens=4)
    trainer = RLTrainer(cfg, rl, task, KEY)
    rng = np.random.default_rng(0)
    prompts, answers = task.sample_batch(rng, 4)
    m = trainer.iteration(prompts, answers, jax.random.PRNGKey(1))
    for k, v in m.items():
        assert np.isfinite(v), f"{k} not finite"
    assert 0.0 <= m["reward_mean"] <= 1.0


def test_grpo_learns_single_digit_addition():
    """A few iterations must visibly increase the reward."""
    cfg = ModelConfig(name="tiny2", n_layers=2, d_model=96, n_heads=4,
                      n_kv_heads=2, head_dim=24, d_ff=192,
                      vocab_size=VOCAB_SIZE, dtype="float32")
    task = AdditionTask(max_operand=4)
    rl = RLConfig(algorithm="grpo", n_rollouts=8, max_new_tokens=3,
                  lr=5e-4, kl_beta=0.0)
    trainer = RLTrainer(cfg, rl, task, KEY)
    rng = np.random.default_rng(3)
    key = jax.random.PRNGKey(9)
    rewards = []
    for it in range(12):
        prompts, answers = task.sample_batch(rng, 12)
        key, k = jax.random.split(key)
        m = trainer.iteration(prompts, answers, k)
        rewards.append(m["reward_mean"])
    assert np.mean(rewards[-3:]) > np.mean(rewards[:3]) + 0.05


def test_reward_partial_credit():
    task = AdditionTask(max_operand=99)
    assert task.reward(12, np.array([1, 2, EOS])) == 1.0
    assert 0 < task.reward(12, np.array([1, 3, EOS])) < 1.0
    assert task.reward(12, np.array([PAD, PAD, PAD])) == 0.0


PROGRAM_TASKS = {"generate": "actor_generation",
                 "ref_logp": "reference_inference",
                 "actor_step": "actor_training",
                 "critic_vals": "critic_inference",
                 "critic_step": "critic_training"}


@pytest.fixture(scope="module")
def ppo_trainer():
    task = AdditionTask(max_operand=9)
    rl = RLConfig(algorithm="ppo", n_rollouts=4, max_new_tokens=4)
    return RLTrainer(tiny_cfg(), rl, task, KEY, devices=jax.devices()[:1])


@pytest.mark.parametrize("name", sorted(PROGRAM_TASKS))
def test_one_device_program_is_plain_and_named(ppo_trainer, name):
    """On a one-device placement each model program is the plain jit
    (no sharding anywhere in its module) and keeps its own name."""
    st = ppo_trainer
    wf = st.wf
    t = next(i for i in range(wf.n_tasks)
             if wf.task(i).name == PROGRAM_TASKS[name])
    placement = st.engine.placements[t]
    assert not placement.sharded and placement.plan_n_devices == 1
    B, P, T = 4, st.task.prompt_len, st.rl.max_new_tokens

    def arr(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype)
    seqs = arr(B, P + T, dtype=jnp.int32)
    batch = {"sequences": seqs, "logp_old": arr(B, T),
             "advantages": arr(B, T), "mask": arr(B, T)}
    args = {
        "generate": (st.gen_params, arr(B, P, dtype=jnp.int32),
                     jax.random.PRNGKey(1)),
        "ref_logp": (st.ref, seqs, P),
        "actor_step": (st.actor, st.actor_opt, batch, P),
        "critic_vals": (st.critic, st.value_head, seqs, P),
        "critic_step": ((st.critic, st.value_head), st.critic_opt,
                        dict(batch, values_old=arr(B, T),
                             returns=arr(B, T)), P),
    }[name]
    text = st.program(name, placement).lower(*args).as_text()
    assert text.startswith(f"module @jit_{name} "), text[:120]
    assert "mhlo.sharding" not in text and "sdy.sharding" not in text
