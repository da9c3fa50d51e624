"""Per-task executors for the RL workflow graph.

Each workflow task (GEN / INF / TRAIN) has a registered executor that runs
the real JAX computation for that task against the shared execution state
``st`` (the trainer's parameters, optimizers and jitted functions) and the
iteration blackboard ``bb``.  The engine dispatches them stage by stage as
the plan dictates; executors return the JAX value the engine should block
on when timing the task.

Registration is by (TaskKind, task name), with a (TaskKind, None) fallback
so custom workflows can reuse the generic executor of a kind.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import plan as plan_mod
from repro.core.workflow import Task, TaskKind
from repro.genserve import adapter as genserve
from repro.obs import metrics as obs_metrics, trace as obs_trace
from repro.rl import rewards as rewards_mod

_EXECUTORS: Dict[Tuple[TaskKind, Optional[str]], Callable] = {}


def register(kind: TaskKind, name: Optional[str] = None):
    def deco(fn):
        _EXECUTORS[(kind, name)] = fn
        return fn
    return deco


def executor_for(task: Task) -> Callable:
    fn = _EXECUTORS.get((task.kind, task.name)) \
        or _EXECUTORS.get((task.kind, None))
    if fn is None:
        raise KeyError(f"no executor registered for task "
                       f"{task.name!r} ({task.kind})")
    return fn


# ---------------------------------------------------------------------------
# GEN
# ---------------------------------------------------------------------------

def _draft_model(st):
    """Resolve the speculative-decoding draft model for this trainer,
    once (cached on ``st``): ``rl.draft_arch`` names a configs.archs
    entry (vocab/dtype overridden to the target's so logits align), ""
    derives a scaled-down full-attention copy of the target.  Weights
    are freshly initialized — a deployment would load a trained draft
    checkpoint; acceptance rate, not weight quality, is what the
    repro's benchmarks vary."""
    cached = getattr(st, "_spec_draft", None)
    if cached is not None:
        return cached
    from repro.configs import archs
    from repro.models import transformer as T
    name = getattr(st.rl, "draft_arch", "")
    cfg = st.cfg
    if name:
        dcfg = dataclasses.replace(
            archs.get(name, smoke=cfg.n_layers <= 4),
            vocab_size=cfg.vocab_size, dtype=cfg.dtype)
    else:
        pattern = tuple(dataclasses.replace(s, window=None)
                        for s in cfg.pattern)
        dcfg = dataclasses.replace(
            cfg, name=f"{cfg.name}-draft", pattern=pattern,
            n_layers=len(pattern) * max(cfg.n_pattern_repeats // 4, 1))
    dparams = T.init_params(jax.random.PRNGKey(7), dcfg)
    st._spec_draft = (dcfg, dparams)
    return st._spec_draft


@register(TaskKind.GEN)
def run_generation(st, bb, placement):
    """Actor generation on the generation replica (pre-sync weights).

    Routed through the continuous-batching engine (``repro.genserve``)
    when the rollout batch exceeds the plan's decode wave, so
    ``MAX_DECODE_WAVE`` semantics from the cost model are enforced at
    execution time; a batch that fits in one wave takes the trainer's
    jitted single-wave path (the genserve fast path).  Either way the
    wave stats land in ``bb["gen_stats"]`` for the engine's per-wave
    Event timeline."""
    prompts = bb["prompts_rep"]
    B = int(prompts.shape[0])
    wave = plan_mod.decode_wave(B)
    mode = getattr(st.rl, "gen_engine", "auto")
    # scheduled decode-slot failures (repro.faults) only exist on the
    # engine path — their presence forces it
    injector = bb.get("fault")
    slot_failures = injector.gen_slot_failures() \
        if injector is not None else None
    spec_k = int(getattr(st.rl, "spec_k", 0))
    draft_params, draft_cfg = None, None
    if spec_k > 0:
        draft_cfg, draft_params = _draft_model(st)
    use_engine = mode == "genserve" or (mode == "auto" and B > wave) \
        or slot_failures is not None or spec_k > 0
    with placement.mesh:
        if use_engine:
            ro, stats = genserve.generate(
                st.gen_params, st.cfg, prompts, bb["rng"], st.sampler,
                wave=wave, decode_chunk=getattr(st.rl, "decode_chunk", 1),
                prefill_chunk=getattr(st.rl, "prefill_chunk", 0),
                fast_path=False, slot_failures=slot_failures,
                spec_k=spec_k, draft_params=draft_params,
                draft_cfg=draft_cfg, mesh=placement.mesh)
        else:
            ro = st.program("generate", placement)(
                st.gen_params, placement.put(prompts), bb["rng"])
            # the single-wave path decodes all B rows at once — its wave
            # width is B, whatever the cost model's bound says
            stats = genserve.wave_stats_from_mask(ro["mask"])
    if "moe_load" in ro:
        st.count_moe_load("gen", ro["moe_load"])
    bb["gen_stats"] = stats
    bb["fresh"] = {"rollout": ro, "answers_rep": bb["answers_rep"],
                   "gen_start": bb["gen_start"],
                   "gen_version": st.weight_version}
    return ro["sequences"]


# ---------------------------------------------------------------------------
# INF
# ---------------------------------------------------------------------------

@register(TaskKind.INF, "reward_inference")
def run_reward(st, bb, placement):
    b = bb["bundle"]
    gen_np = np.asarray(b["rollout"]["gen_tokens"])
    scores = st.task.reward_batch(b["answers_rep"], gen_np)
    with bb["lock"]:
        bb["scores"] = scores
    return None


@register(TaskKind.INF, "reference_inference")
def run_reference(st, bb, placement):
    b = bb["bundle"]
    seqs = b["rollout"]["sequences"]
    with placement.mesh:
        lp_ref = st.program("ref_logp", placement)(
            st.ref, placement.put(seqs), int(b["gen_start"]))
    with bb["lock"]:
        # host copy under multi-device: the advantage prep mixes tensors
        # from several meshes, which eager ops refuse to colocate
        bb["lp_ref"] = np.asarray(lp_ref) if bb.get("multidev") else lp_ref
    return lp_ref


@register(TaskKind.INF, "critic_inference")
def run_critic_inference(st, bb, placement):
    b = bb["bundle"]
    seqs = b["rollout"]["sequences"]
    with placement.mesh:
        # the critic's weights live on critic_training's placement
        values = st.program("critic_vals", placement)(
            placement.put_params(st.critic),
            placement.put_params(st.value_head), placement.put(seqs),
            int(b["gen_start"]))
    with bb["lock"]:
        bb["values"] = np.asarray(values) if bb.get("multidev") else values
    return values


# ---------------------------------------------------------------------------
# TRAIN (shared advantage preparation, then per-model update)
# ---------------------------------------------------------------------------

def ensure_train_batch(st, bb):
    """KL-penalised rewards + advantages, computed once per iteration by
    the trainer's one jitted program (``st._advantages``).

    Both training executors may run concurrently on disjoint GPU groups;
    the first through the lock materializes the batch.  On one device
    nothing is read back to the host here: the batch's scalars stay on
    the device until ``run_actor_training`` reads them with the actor
    step's metrics, so the actor step is queued right behind this
    program."""
    with bb["lock"]:
        if "batch" in bb:
            return
        with obs_trace.span("rl.advantages"):
            b = bb["bundle"]
            ro = b["rollout"]
            host_reads = obs_metrics.counter("rl.advantages.host_reads")

            def pull(x):
                # under multi-device execution the rollout, reference and
                # critic tensors are committed to different meshes, which
                # jit refuses to mix as eager ops do; a round trip through
                # host memory drops the commitment, and the training
                # executors re-commit the batch onto their meshes
                if not bb.get("multidev"):
                    return x
                if isinstance(x, jax.Array):
                    host_reads.inc()
                return jnp.asarray(np.asarray(x))
            mask = pull(ro["mask"])
            lp_old = pull(ro["logprobs"])
            values = pull(bb["values"]) if st.rl.algorithm == "ppo" \
                else None
            out = st._advantages(jnp.asarray(bb["scores"]), mask, lp_old,
                                 pull(bb["lp_ref"]), values)
            bb["returns"] = out["returns"]
            bb["batch_metrics"] = {k: out[k] for k in
                                   ("reward_mean", "kl", "gen_len")}
            bb["batch"] = {"sequences": pull(ro["sequences"]),
                           "logp_old": lp_old,
                           "advantages": out["advantages"], "mask": mask}


@register(TaskKind.TRAIN, "actor_training")
def run_actor_training(st, bb, placement):
    ensure_train_batch(st, bb)
    b = bb["bundle"]
    with placement.mesh:
        st.actor, st.actor_opt, am = st.program("actor_step", placement)(
            st.actor, st.actor_opt, placement.put(bb["batch"]),
            int(b["gen_start"]))
    am = dict(am)
    if "moe_load" in am:
        st.count_moe_load("train", am.pop("moe_load"))
    with bb["lock"]:
        # the batch's scalars are read here too, after the actor step has
        # been queued behind the program that made them
        bb["metrics"].update({k: float(v) for k, v in
                              {**bb["batch_metrics"], **am}.items()})
    return st.actor


@register(TaskKind.TRAIN, "critic_training")
def run_critic_training(st, bb, placement):
    ensure_train_batch(st, bb)
    b = bb["bundle"]
    mask = bb["batch"]["mask"]
    values = bb["values"]
    if bb.get("multidev"):
        values = np.asarray(values)
    cbatch = dict(bb["batch"], values_old=values * mask,
                  returns=bb["returns"])
    with placement.mesh:
        (st.critic, st.value_head), st.critic_opt, closs = st.program(
            "critic_step", placement)(
                (st.critic, st.value_head), st.critic_opt,
                placement.put(cbatch), int(b["gen_start"]))
    with bb["lock"]:
        bb["metrics"]["critic_loss"] = float(closs)
    return closs
