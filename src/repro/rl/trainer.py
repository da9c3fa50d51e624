"""RL trainers: synchronous PPO (6 tasks) and GRPO (4 tasks).

The iteration structure mirrors the paper's workflow graph exactly:
  actor_generation -> {reward, reference, critic} inference ->
  {actor, critic} training -> weight reshard/sync.

Execution is delegated to the plan-driven engine (``repro.engine``): the
scheduler's ``Plan`` decides which tasks colocate (serialize) and which
GPU groups run concurrently, the async one-step off-policy double buffer
lives in ``engine.pipeline``, and every iteration records a measured
``Event`` timeline comparable with ``core.simulator.simulate``.  The
trainer itself is a thin facade that owns the JAX state (params,
optimizers, jitted step functions) the task executors operate on; its
public API and metrics dict are unchanged from the pre-engine version.
"""
from __future__ import annotations

import dataclasses
import functools
import inspect
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import enumerate as enum_mod, topology, workflow
from repro.data.synthetic import AdditionTask, EOS
from repro.engine.executor import Engine
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.optim import adam
from repro.parallel import sharding as sh
from repro.rl import gae, losses, rewards as rewards_mod, rollout


@dataclasses.dataclass(frozen=True)
class RLConfig:
    algorithm: str = "grpo"          # "ppo" | "grpo"
    clip_eps: float = 0.2
    kl_beta: float = 0.02
    gamma: float = 1.0
    lam: float = 0.95
    value_coef: float = 0.5
    entropy_coef: float = 0.0
    n_rollouts: int = 4              # responses per prompt (GRPO group)
    max_new_tokens: int = 8
    temperature: float = 1.0
    # greedy decode: deterministic argmax sampling — what sharded-vs-
    # unsharded parity checks pin token-identical generation on
    greedy: bool = False
    whiten_advantages: bool = True
    lr: float = 1e-4
    # asynchronous (one-step off-policy) RL: generation for iteration t+1
    # overlaps training on iteration t's rollouts (§2.1); the PPO ratio
    # absorbs the one-step staleness of logp_old
    asynchronous: bool = False
    # GEN executor routing: "auto" takes the jitted single-wave path when
    # the rollout batch fits in one decode wave and the continuous-
    # batching engine (repro.genserve) beyond it; "rollout"/"genserve"
    # force a path
    gen_engine: str = "auto"
    decode_chunk: int = 1            # genserve decode steps per host round
    prefill_chunk: int = 0           # genserve chunked admission (tokens
    #                                  per mixed round; 0 = one-shot)
    # draft-model speculative decoding inside the wave step: k tokens
    # proposed by the draft per round, one batched (k+1)-wide target
    # verify.  0 = off.  ``draft_arch`` names a configs.archs entry for
    # the draft ("" = a scaled-down copy of the target); always routes
    # through the genserve engine.
    spec_k: int = 0
    draft_arch: str = ""


def default_plan(wf: workflow.RLWorkflow, devices: Optional[list] = None):
    """Colocate-all plan over the host devices (what a plan-less trainer
    executes): one task group, every task on every device."""
    devices = list(devices) if devices is not None else jax.devices()
    n = len(devices)
    topo = topology.build_local(devices)
    grouping = (tuple(range(wf.n_tasks)),)
    return topo, enum_mod.build_plan(topo, wf, grouping, [n],
                                     list(range(n)))


def make_actor_step(cfg: ModelConfig, rl: RLConfig) -> Callable:
    """The actor update, unjitted: ``(params, opt_state, batch,
    gen_start) -> (params, opt_state, metrics)``, one clipped-surrogate
    step on a rollout batch (``sequences``, ``logp_old``, ``advantages``,
    ``mask``) followed by Adam.  For an MoE model the metrics hold the
    forward's ``moe_load``."""
    def actor_loss(train, frozen, batch, gen_start):
        lp_new, out = rollout.sequence_logprobs(
            adam.join(train, frozen), cfg, batch["sequences"], gen_start)
        pl = losses.ppo_policy_loss(
            lp_new, batch["logp_old"], batch["advantages"],
            batch["mask"], clip_eps=rl.clip_eps)
        loss = pl["loss"] + out["aux_loss"]
        if rl.entropy_coef:
            logits = out["logits"][:, gen_start - 1:-1]
            loss = loss - rl.entropy_coef * losses.entropy_bonus(
                logits, batch["mask"])
        if "moe_load" in out:
            pl = {**pl, "moe_load": out["moe_load"]}
        return loss, pl

    def actor_step(params, opt_state, batch, gen_start):
        # frozen buffers (adam.FROZEN) are not differentiated
        train, frozen = adam.partition(params)
        (loss, pl), grads = jax.value_and_grad(
            actor_loss, has_aux=True)(train, frozen, batch, gen_start)
        new_params, new_opt, om = adam.adam_update(
            params, grads, opt_state, adam.AdamConfig(lr=rl.lr))
        return new_params, new_opt, {**pl, "loss": loss, **om}
    return actor_step


def advantage_batch(scores, mask, logprobs, lp_ref, values=None, *,
                    algorithm: str, n_rollouts: int, kl_beta: float,
                    whiten_advantages: bool, gamma: float,
                    lam: float) -> Dict[str, object]:
    """The training batch's advantages from the rollouts' sequence
    ``scores`` [B], ``mask``, actor and reference log-probs [B, T] (and
    the critic's ``values`` for PPO): KL-penalised token rewards, then
    GRPO's group-normalised or PPO's GAE advantages, optionally whitened.
    Returns ``advantages``, ``returns`` (PPO; None for GRPO) and the
    scalars ``kl``, ``gen_len`` and ``reward_mean``.  Meant to run under
    ``jax.jit`` with every keyword static: nothing is read to the host."""
    tok_rewards, kl = losses.kl_penalised_rewards(
        scores, logprobs, lp_ref, mask, kl_beta=kl_beta)
    returns = None
    if algorithm == "ppo":
        adv, returns = gae.gae_advantages(tok_rewards, values * mask, mask,
                                          gamma=gamma, lam=lam)
    else:
        adv = gae.grpo_advantages(tok_rewards.sum(1), n_rollouts, mask)
    if whiten_advantages:
        adv = gae.whiten(adv, mask)
    return {"advantages": adv, "returns": returns, "kl": kl,
            "gen_len": mask.sum(1).mean(), "reward_mean": scores.mean()}


class RLTrainer:
    def __init__(self, model_cfg: ModelConfig, rl_cfg: RLConfig,
                 task: AdditionTask, key, plan=None, topo=None, wf=None,
                 devices=None, overlap=None):
        self.cfg = model_cfg
        self.rl = rl_cfg
        self.task = task
        k_actor, k_critic, k_vh = jax.random.split(key, 3)
        self.actor = T.init_params(k_actor, model_cfg)
        self.ref = jax.tree_util.tree_map(jnp.copy, self.actor)
        self.actor_opt = adam.init_adam_state(
            self.actor, adam.AdamConfig(lr=rl_cfg.lr))
        self.gen_params = self.actor  # generation replica (synced weights)
        self.sync_bytes = 0
        self.weight_version = 0
        if rl_cfg.algorithm == "ppo":
            self.critic = T.init_params(k_critic, model_cfg)
            self.value_head = rewards_mod.init_value_head(k_vh, model_cfg)
            self.critic_opt = adam.init_adam_state(
                (self.critic, self.value_head),
                adam.AdamConfig(lr=rl_cfg.lr))
        self.sampler = rollout.SamplerConfig(
            max_new_tokens=rl_cfg.max_new_tokens,
            temperature=rl_cfg.temperature, eos_token=EOS,
            greedy=rl_cfg.greedy)
        self._programs: Dict[Tuple, Callable] = {}   # (name, mesh)
        # device-side expert-load tallies by phase (count_moe_load)
        self._moe_load: Dict[str, jnp.ndarray] = {}
        self._define_programs()

        # plan-driven engine: the plan decides task colocation/concurrency
        # and the sync path; without one, execute the colocate-all default.
        # Pass the workflow the plan was searched with (its global_batch
        # scales the cost model) so measured-vs-predicted compares like
        # with like; otherwise a representative one is built here.
        if wf is not None and wf.algorithm != rl_cfg.algorithm:
            raise ValueError(f"workflow algorithm {wf.algorithm!r} != "
                             f"rl config algorithm {rl_cfg.algorithm!r}")
        self.wf = wf or workflow.make_workflow(
            rl_cfg.algorithm,
            workflow.LLMSpec.from_model_config(model_cfg),
            synchronous=not rl_cfg.asynchronous,
            n_rollouts=rl_cfg.n_rollouts,
            seq_in=getattr(task, "prompt_len", 16),
            seq_out=rl_cfg.max_new_tokens,
            global_batch=1)
        if plan is not None and \
                set(plan.parallel) != set(range(self.wf.n_tasks)):
            raise ValueError(
                f"plan covers tasks {sorted(plan.parallel)} but the "
                f"{rl_cfg.algorithm} workflow has {self.wf.n_tasks} tasks")
        if plan is None:
            host_topo, plan = default_plan(self.wf, devices)
            topo = topo if topo is not None else host_topo
        # ``devices`` restricts the engine to a subset of the host's jax
        # devices (e.g. an unsharded single-device baseline next to a
        # sharded run); ``overlap`` forwards the gen/train wall-clock
        # overlap switch (None = auto on disjoint folded groups).
        self.engine = Engine(self.wf, plan, self, topo=topo,
                             asynchronous=rl_cfg.asynchronous,
                             devices=devices, overlap=overlap)

    @property
    def plan(self):
        """The engine's *live* plan — after an elastic swap
        (``engine.apply_plan``) this tracks the new plan epoch."""
        return self.engine.plan

    # -- checkpointable state (§6: what must survive a plan swap) -------
    def state_tree(self) -> Dict[str, object]:
        """The full live training state as one pytree for
        ``checkpoint.io.save``/``restore``: parameters, optimizer state,
        the generation replica and the weight-sync version counter.
        Execution state (plan, placements, timeline) is deliberately
        excluded — it is rebuilt from the plan on restore."""
        tree: Dict[str, object] = {
            "actor": self.actor, "ref": self.ref,
            "actor_opt": self.actor_opt, "gen_params": self.gen_params,
            "weight_version": jnp.asarray(self.weight_version, jnp.int32),
        }
        if self.rl.algorithm == "ppo":
            tree["critic"] = self.critic
            tree["value_head"] = self.value_head
            tree["critic_opt"] = self.critic_opt
        return tree

    def load_state_tree(self, tree: Dict[str, object]) -> None:
        self.actor = tree["actor"]
        self.ref = tree["ref"]
        self.actor_opt = tree["actor_opt"]
        self.gen_params = tree["gen_params"]
        self.weight_version = int(tree["weight_version"])
        if self.rl.algorithm == "ppo":
            self.critic = tree["critic"]
            self.value_head = tree["value_head"]
            self.critic_opt = tree["critic_opt"]

    # -- model programs (plan -> placement -> one jitted program) --------
    #
    # Each model program is defined once and jitted per placement mesh by
    # ``program``.  On one device it is the plain jit; on a placement of
    # several devices its state carries the placement's shardings (state
    # committed by ``install_placements``, inputs by ``TaskPlacement.put``)
    # and its body traces under the placement's activation hints.

    def _define_programs(self):
        cfg, rl, sampler = self.cfg, self.rl, self.sampler

        def generate(params, prompts, rng):
            return rollout.generate(params, cfg, prompts, rng, sampler)

        def ref_logp(params, sequences, gen_start):
            lp, _ = rollout.sequence_logprobs(params, cfg, sequences,
                                              gen_start)
            return lp

        self._fns = {"generate": generate, "ref_logp": ref_logp,
                     "actor_step": make_actor_step(cfg, rl)}

        def advantages(scores, mask, logprobs, lp_ref, values):
            return advantage_batch(
                scores, mask, logprobs, lp_ref, values,
                algorithm=rl.algorithm, n_rollouts=rl.n_rollouts,
                kl_beta=rl.kl_beta, whiten_advantages=rl.whiten_advantages,
                gamma=rl.gamma, lam=rl.lam)
        self._advantages = jax.jit(advantages)

        if rl.algorithm == "ppo":
            def critic_vals(critic, head, sequences, gen_start):
                return rewards_mod.critic_values(critic, head, cfg,
                                                 sequences, gen_start)

            def critic_loss(cp, batch, gen_start):
                critic, head = cp
                v = rewards_mod.critic_values(critic, head, cfg,
                                              batch["sequences"], gen_start)
                return rl.value_coef * losses.value_loss(
                    v, batch["values_old"], batch["returns"], batch["mask"],
                    clip_eps=rl.clip_eps)

            def critic_step(cp, opt_state, batch, gen_start):
                loss, grads = jax.value_and_grad(critic_loss)(
                    cp, batch, gen_start)
                new_cp, new_opt, _ = adam.adam_update(
                    cp, grads, opt_state, adam.AdamConfig(lr=rl.lr))
                return new_cp, new_opt, loss
            self._fns.update(critic_vals=critic_vals,
                             critic_step=critic_step)

    def program(self, name: str, placement) -> Callable:
        """The jitted model program ``name`` (``generate``, ``ref_logp``,
        ``actor_step``, ``critic_vals`` or ``critic_step``) for
        ``placement``, built once per placement mesh.  It takes the
        program's state, then its inputs, then ``gen_start`` as a
        positional static ``int``; the profiler names it
        ``jit_<name>`` on every placement."""
        key = (name, placement.mesh)
        fn = self._programs.get(key)
        if fn is None:
            fn = self._programs[key] = self._build_program(name, placement)
        return fn

    def _build_program(self, name: str, placement) -> Callable:
        fn = self._fns[name]
        static = tuple(i for i, a in
                       enumerate(inspect.signature(fn).parameters)
                       if a == "gen_start")
        if not placement.sharded:
            return jax.jit(fn, static_argnums=static)
        rules = placement.activation_rules()

        @functools.wraps(fn)
        def hinted(*args):
            with sh.use_hints(rules):
                return fn(*args)
        state = self._state_shardings(name, placement)
        repl = placement.replicated_sharding()
        # a None leaf keeps the sharding ``placement.put`` committed the
        # batch with; generate's rng is replicated
        inputs = (None, repl) if name == "generate" else (None,)
        out = (*state, repl) if name.endswith("_step") else repl
        return jax.jit(hinted, static_argnums=static,
                       in_shardings=(*state, *inputs), out_shardings=out)

    def _state_shardings(self, name: str, placement) -> Tuple:
        """Shardings of the state a program takes ahead of its inputs:
        parameters by ``parallel.sharding``'s rules, the optimizer's
        moments alongside them."""
        ps = placement.param_shardings
        if name == "generate":
            return (ps(self.gen_params),)
        if name == "ref_logp":
            return (ps(self.ref),)
        if name == "actor_step":
            p = ps(self.actor)
            return (p, self._opt_shardings(placement, p))
        cps = (ps(self.critic), ps(self.value_head))
        if name == "critic_vals":
            return cps
        return (cps, self._opt_shardings(placement, cps))

    def _opt_shardings(self, placement, pshard):
        pshard = adam.partition(pshard)[0]
        return {"step": placement.replicated_sharding(),
                "m": pshard, "v": pshard}

    def install_placements(self, placements, wf) -> None:
        """Commit each task's state onto its owning placement.

        Called by the engine whenever placements (re)build — initial
        construction and every elastic plan swap.  Ownership: actor +
        optimizer → actor_training; reference → reference_inference;
        generation replica → actor_generation; critic state →
        critic_training.  Single-host single-device runs are left
        untouched (everything already lives on the only device)."""
        self._programs.clear()
        multi = len({id(d) for pl in placements.values()
                     for d in pl.local_devices}) > 1
        if not multi:
            return
        by_name = {wf.tasks[t].name: pl for t, pl in placements.items()}

        tp = by_name.get("actor_training")
        if tp is not None:
            ps = tp.param_shardings(self.actor)
            self.actor = jax.device_put(self.actor, ps)
            self.actor_opt = jax.device_put(
                self.actor_opt, self._opt_shardings(tp, ps))
        rp = by_name.get("reference_inference")
        if rp is not None:
            self.ref = jax.device_put(self.ref,
                                      rp.param_shardings(self.ref))
        gp = by_name.get("actor_generation")
        if gp is not None:
            self.gen_params = jax.device_put(
                self.gen_params, gp.param_shardings(self.gen_params))
        if self.rl.algorithm == "ppo":
            cp = by_name.get("critic_training") \
                or by_name.get("critic_inference")
            if cp is not None:
                cps = (cp.param_shardings(self.critic),
                       cp.param_shardings(self.value_head))
                self.critic, self.value_head = jax.device_put(
                    (self.critic, self.value_head), cps)
                self.critic_opt = jax.device_put(
                    self.critic_opt, self._opt_shardings(cp, cps))

    # -- engine hooks ---------------------------------------------------
    def prepare_inputs(self, prompts: np.ndarray, answers: np.ndarray,
                       rng) -> Dict[str, object]:
        G = self.rl.n_rollouts
        return {"prompts_rep": jnp.asarray(np.repeat(prompts, G, axis=0)),
                "answers_rep": np.repeat(answers, G, axis=0),
                "gen_start": prompts.shape[1], "rng": rng}

    def before_stage(self, stage_tasks, bb) -> None:
        """Materialize the shared KL/advantage batch before the training
        stage dispatches, so neither training lane's measured duration
        absorbs the cross-task prep (or the other lane's lock wait)."""
        from repro.core.workflow import TaskKind
        from repro.engine.tasks import ensure_train_batch
        if bb.get("bundle") is not None and \
                any(t.kind == TaskKind.TRAIN for t in stage_tasks):
            ensure_train_batch(self, bb)

    def count_moe_load(self, phase: str, load) -> None:
        """Add a program's token-to-held-expert assignments
        ([n_moe_layers, n_held]) to the tally of ``phase`` ("gen" or
        "train") on the device: nothing is read here."""
        prev = self._moe_load.get(phase)
        self._moe_load[phase] = load if prev is None else prev + load

    def publish_moe_load(self) -> Dict[str, np.ndarray]:
        """Read the expert-load tallies once, add them to the metrics
        registry (counters ``moe.assignments.<phase>``, gauge
        ``moe.load_max_over_mean``: the busiest held expert of any layer
        against the mean) and start new tallies.  Returns the loads."""
        from repro.obs import metrics as obs_metrics
        loads = {k: np.asarray(v) for k, v in self._moe_load.items()}
        self._moe_load = {}
        for phase, load in loads.items():
            obs_metrics.counter(f"moe.assignments.{phase}").inc(
                int(load.sum()))
        if loads:
            total = sum(loads.values())
            obs_metrics.gauge("moe.load_max_over_mean").set(
                float(total.max()) / max(float(total.mean()), 1e-9))
        return loads

    def fill_metrics(self) -> Dict[str, float]:
        return {"reward_mean": 0.0, "kl": 0.0, "gen_len": 0.0,
                "loss": 0.0, "pipeline_fill": 1.0, "sync_gb": 0.0}

    # ------------------------------------------------------------------
    def iteration(self, prompts: np.ndarray, answers: np.ndarray,
                  rng) -> Dict[str, float]:
        """One RL iteration over a prompt batch, executed by the engine.

        Synchronous: generate -> infer -> train -> sync (iteration-level
        barrier).  Asynchronous: generate with the PREVIOUS sync's weights
        while training on the PREVIOUS iteration's rollouts (one-step
        off-policy); the first call only produces rollouts."""
        return self.engine.run_iteration(prompts, answers, rng).metrics

    # ------------------------------------------------------------------
    def evaluate(self, prompts: np.ndarray, answers: np.ndarray,
                 rng) -> float:
        sampler = dataclasses.replace(self.sampler, greedy=True)
        ro = rollout.generate(self.gen_params, self.cfg,
                              jnp.asarray(prompts), rng, sampler)
        gen_np = np.asarray(ro["gen_tokens"])
        exact = [self.task.reward(a, g) >= 1.0
                 for a, g in zip(answers, gen_np)]
        return float(np.mean(exact))
