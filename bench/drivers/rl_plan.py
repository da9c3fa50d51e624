"""RL cells of configurations that ``bench/model.py`` does not map:
synchronous GRPO iterations through the program's normal path, on the
plan that ``HybridScheduler.search`` finds on the chips present.

The configuration file is the one named by ``ctx["cfg"].name``; its key
``bench_model`` names the module of ``bench/`` that maps it onto the
program and the reference (``bench/model_lfm2.py``):

- ``configs(spec) -> (ModelConfig, spec)``: the program's configuration
  and the file's dict the reference draws from;
- ``program_params(key, spec, cfg)``: the seed's weights in the
  program's tree;
- ``norms(tree)``: per-leaf norms of a program tree, keyed as
  ``reference_norms`` keys the reference's;
- ``reference(spec, cfg, key, prec, **grpo)``: a
  ``bench.reference_grpo.GRPOReference`` from the seed's weights.

Otherwise as ``bench/drivers/rl.py``, whose batch capture and task
timings it uses: set-up follows ``launch/train.run_rl``, the seed's
weights replace the trainer's, the first iterations run through
``RLTrainer.iteration`` and are what the reference follows, batch by
batch; the window then drives the same trainer.  An MoE program's
expert-load tallies are read before the window, when the traced part
ends (in the pause the window leaves out) and after it, so nothing is
read inside an iteration; they go into the record under ``moe``.
"""
from __future__ import annotations

import gc
import importlib
import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench import correct, model as bm, traffic
from bench.drivers.rl import _capture_batches, _task_durations
from bench.tasks import SubsetTask


def model_module(ctx):
    """The configuration file's dict and its ``bench_model`` module."""
    spec = bm.load_config_file(ctx["cfg"].name)
    return spec, importlib.import_module("bench." + spec["bench_model"])


def build(mod, cfg, spec, t, seed, devices):
    from repro.core import topology, workflow
    from repro.core.plan import check_constraints
    from repro.core.sha import HybridScheduler
    from repro.rl.trainer import RLConfig, RLTrainer
    task = SubsetTask(cfg.vocab_size, t["prompt_len"], t["response_len"],
                      seed)
    topo = topology.build_local(devices)
    wf = workflow.make_workflow(
        "grpo", workflow.LLMSpec.from_model_config(cfg),
        synchronous=True, global_batch=t["groups"], n_rollouts=t["k"],
        seq_in=t["prompt_len"], seq_out=t["response_len"])
    r = HybridScheduler(topo, wf, max_groupings=8,
                        max_sizes_per_grouping=4).search(
        budget=t["search_budget"])
    ok, msg = check_constraints(topo, wf, r.plan)
    assert ok, msg
    note = {"searched_ms": r.cost * 1e3, "grouping": str(r.grouping)}
    rl = RLConfig(algorithm="grpo", n_rollouts=t["k"],
                  max_new_tokens=t["response_len"], lr=t["lr"],
                  kl_beta=t["kl_beta"], clip_eps=t["clip_eps"],
                  temperature=t["temperature"])
    trainer = RLTrainer(cfg, rl, task, jax.random.PRNGKey(0), plan=r.plan,
                        topo=topo, wf=wf, devices=devices)
    # the seed's weights in place of the trainer's own draw
    trainer.actor = trainer.ref = trainer.gen_params = None
    gc.collect()
    params = mod.program_params(bm.seed_key(seed), spec, cfg)
    trainer.actor = params
    trainer.gen_params = params
    trainer.ref = jax.tree_util.tree_map(jnp.copy, params)
    trainer.install_placements(trainer.engine.placements, trainer.wf)
    return trainer, task, note


def _loads(trainer):
    """The expert-load tallies since the last read: {"gen": n, "train":
    n} assignments to held experts ({} for a dense model)."""
    return {k: int(v.sum()) for k, v in trainer.publish_moe_load().items()}


def _follow(mod, cfg, spec, task, batches, t, seed, prec):
    """The reference's readings of the checked steps' batches."""
    P = t["prompt_len"]
    r = mod.reference(spec, cfg, bm.seed_key(seed), prec, lr=t["lr"],
                      clip_eps=t["clip_eps"], kl_beta=t["kl_beta"],
                      group=t["k"])
    out = {"loss": [], "logp": []}
    for i, b in enumerate(batches):
        s = r.step(task, b["sequences"], b["mask"], P)
        out["loss"].append(s["loss"])
        out["logp"].append(s["logp"])
        if i == 0:
            out["grad"] = mod.reference_norms(s["grad"])
    out["change"] = mod.reference_norms(r.change())
    return out


def run(ctx) -> Dict:
    t, seed, devices = ctx["traffic"], ctx["seed"], ctx["devices"]
    spec, mod = model_module(ctx)
    cfg, spec = mod.configs(spec)
    trainer, task, plan_note = build(mod, cfg, spec, t, seed, devices)
    ctx["log"](f"built trainer for {cfg.name}, plan {plan_note}")
    B, P, N = t["groups"] * t["k"], t["prompt_len"], t["response_len"]
    answers = np.zeros((t["groups"],), np.int64)
    rng = jax.random.fold_in(bm.seed_key(seed), 7)
    b1 = 0.9

    # checked steps: the window's own call, fed as the window feeds it
    batches, prog = [], {"loss": []}
    restore = _capture_batches(trainer, batches)
    try:
        for step in range(t["checked_steps"]):
            m = trainer.iteration(traffic.rl_prompts(t, cfg.vocab_size,
                                                     seed, step),
                                  answers, jax.random.fold_in(rng, step))
            prog["loss"].append(float(m["loss"]))
            if step == 0:
                # the first gradient as Adam got it: m = (1 - b1) g
                prog["grad"] = {k: v / (1 - b1) for k, v in
                                mod.norms(trainer.actor_opt["m"]).items()}
    finally:
        restore()
    step = len(batches)
    prog["change"] = mod.norms(jax.tree_util.tree_map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        trainer.actor, trainer.ref))
    prog["logp"] = [b["logp_old"] for b in batches]
    prog["mask"] = [b["mask"] for b in batches]
    jax.block_until_ready(trainer.actor)
    _loads(trainer)
    ctx["log"](f"checked steps: losses {prog['loss']}")

    # the window; with --trace 1 its first ``traced_seconds`` are traced
    first = trainer.engine._iter
    iters, loads = [], {}

    def iterate_until(seconds):
        nonlocal step
        while time.perf_counter() - t0 < seconds:
            prompts = traffic.rl_prompts(t, cfg.vocab_size, seed, step)
            s0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.rl_iteration"):
                m = trainer.iteration(prompts, answers,
                                      jax.random.fold_in(rng, step))
            iters.append({"wall_s": time.perf_counter() - s0,
                          "gen_len": float(m["gen_len"])})
            step += 1
    ctx["start_window"]()
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        iterate_until(ctx["traced_seconds"])
    traced = len(iters)
    s0 = time.perf_counter()
    ctx["stop_trace"]()
    loads["traced"] = _loads(trainer)
    t0 += time.perf_counter() - s0      # the window leaves the pause out
    iterate_until(ctx["seconds"])
    window_s = time.perf_counter() - t0
    ctx["end_window"]()
    rest = _loads(trainer)
    loads["window"] = {k: v + rest.get(k, 0)
                       for k, v in loads["traced"].items()}
    durs = _task_durations(trainer, first)
    syncs = trainer.engine.sync_durations[-len(iters):]
    for i, it in enumerate(iters):
        it.update(durs.get(first + i, {}))
        it["sync_s"] = syncs[i] if i < len(syncs) else 0.0
    tokens = sum(B * (P + it["gen_len"]) for it in iters)
    mem = ctx["memory_peak"]()
    ctx["log"](f"window: {len(iters)} iterations in {window_s:.3f} s, "
               f"{tokens / window_s:.1f} tokens/s, peak {mem}; expert "
               f"assignments {loads}; iteration walls (ms) "
               f"{[round(1e3 * it['wall_s'], 1) for it in iters]}")

    # free the program's state, then follow the checked steps
    del trainer
    gc.collect()
    ref = _follow(mod, cfg, spec, task, batches, t, seed, "f32")
    readings = correct.rl_readings(prog, ref)
    ctx["log"](f"readings {readings}")
    keep = correct.moving_leaves(ref["grad"])
    for what in ("grad", "change"):
        ctx["log"](f"worst {what} leaves (name, gap, program, reference): "
                   f"{correct.worst_leaves(prog[what], ref[what], keep)}")
    gc.collect()
    control = None
    if ctx.get("control"):
        ctl = dict(_follow(mod, cfg, spec, task, batches, t, seed, "fp8"),
                   mask=prog["mask"])
        control = correct.rl_readings(ctl, ref)
        gc.collect()

    rec = {
        "kind": "rl", "window_s": window_s, "iters": iters,
        "traced_iters": iters[:traced],
        "e2e": {"rl_tokens_per_s": tokens / window_s},
        "attempted": len(iters), "failed": 0,
        "readings": readings, "memory_peak_bytes": mem,
        "rows": B, "prompt_len": P, "response_len": N,
        "plan": plan_note, "control": control,
    }
    if cfg.has_moe:
        rec["moe"] = {"cfg": cfg, "assignments": loads}
    return rec
