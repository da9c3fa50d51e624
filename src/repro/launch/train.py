"""Training launcher: runs real LM train steps for any --arch on the host
mesh (CPU smoke scale by default, production mesh shapes via dry-run).

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --smoke \
        --steps 20 --batch 8 --seq 128

With ``--rl {grpo,ppo}`` the launcher instead runs the plan-driven RL
path end-to-end: HetRL scheduler search on the chosen testbed scenario →
``Plan`` → engine execution of real RL iterations → measured vs
cost-model iteration time:

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --smoke \
        --rl grpo --steps 10 --batch 8 --search-budget 120

``--drift <scenario>`` additionally runs the §6 elasticity loop: a named
topology drift (see ``core.topology.DRIFT_SCENARIOS``) fires mid-run, the
elastic controller reschedules with a warm-start budget at the iteration
boundary, checkpoints trainer state, and swaps the plan when the
``RedeployDecision`` says so — reporting measured-vs-predicted iteration
time per plan epoch:

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --smoke \
        --rl grpo --steps 12 --batch 8 --drift drop_tail --drift-at 4

``--fault <scenario>`` injects a named *fault* (see
``repro.faults.FAULT_SCENARIOS``) on the engine's iteration clock:
undeclared degradations are detected purely from measured-vs-predicted
divergence (a short calibration warmup arms the monitor), transient
crashes are absorbed by bounded retry, permanent failures escalate to a
forced replan on the survivors.  Composes with ``--drift`` (declared +
undeclared drift in one run); ``--require-recover`` exits non-zero
unless the scenario's recovery mechanism actually engaged:

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --smoke \
        --rl grpo --steps 12 --batch 8 --fault link_throttle \
        --fault-at 6 --require-recover
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import archs
from repro.configs.shapes import token_splits
from repro.data.synthetic import random_lm_batch
from repro.launch import steps as steps_mod
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import transformer as T
from repro.obs import trace as obs_trace
from repro.optim import adam


def make_batch(cfg, key, batch, seq):
    n_feat, n_tok = token_splits(cfg, seq)
    out = {}
    k1, k2 = jax.random.split(key)
    if n_feat:
        out["features"] = jax.random.normal(
            k1, (batch, n_feat, cfg.feature_dim), jnp.dtype(cfg.dtype))
    if n_tok:
        out["tokens"] = jax.random.randint(k2, (batch, n_tok), 0,
                                           cfg.vocab_size, jnp.int32)
    labels = jax.random.randint(k2, (batch, seq), 0, cfg.vocab_size,
                                jnp.int32)
    out["labels"] = labels
    out["loss_mask"] = jnp.ones((batch, seq), jnp.dtype(cfg.dtype))
    return out


def _assert_recovered(scenario: str, trainer, controller, injector) -> None:
    """--require-recover: fail loudly unless the scenario's recovery
    mechanism actually engaged (CI smoke gate)."""
    from repro.obs import metrics as obs_metrics
    snap = obs_metrics.snapshot()
    records = controller.records if controller is not None else []
    if scenario in ("link_throttle", "device_slowdown", "straggler"):
        assert any(r.reactive for r in records), (
            f"{scenario}: divergence monitor never fired a reactive "
            f"replan (records={len(records)})")
        print(f"recovered: reactive replan at iteration "
              f"{next(r.iteration for r in records if r.reactive)}")
    elif scenario == "transient_crash":
        assert snap.get("engine.task_retries", 0) > 0, \
            "transient_crash: no retries recorded"
        assert not snap.get("engine.task_failures"), \
            "transient_crash: failure escaped the retry budget"
        print(f"recovered: {int(snap['engine.task_retries'])} retries "
              f"absorbed the crash")
    elif scenario in ("permanent_crash", "device_drop"):
        assert any(r.forced for r in records), \
            f"{scenario}: no forced replan happened"
        rec = next(r for r in records if r.forced)
        print(f"recovered: forced replan at iteration {rec.iteration} "
              f"-> epoch {rec.epoch}")
    elif scenario in ("ckpt_fail",):
        assert snap.get("checkpoint.retries", 0) > 0, \
            "ckpt_fail: no checkpoint retries recorded"
        print(f"recovered: {int(snap['checkpoint.retries'])} checkpoint "
              f"retries")
    elif scenario == "ckpt_flaky":
        assert snap.get("checkpoint.failures", 0) > 0, \
            "ckpt_flaky: warn-and-continue never engaged"
        print("recovered: checkpoint degraded to warn-and-continue, "
              "training completed")
    elif scenario == "ckpt_corrupt":
        from repro.checkpoint import io as ckpt_io
        assert injector.fired("ckpt_corrupt"), \
            "ckpt_corrupt: corruption never fired"
        tree, path = ckpt_io.load_latest("results/elastic_ckpt",
                                         trainer.state_tree())
        print(f"recovered: load_latest fell back to {path}")
    elif scenario == "slot_failure":
        assert snap.get("gen.slot_failures", 0) > 0, \
            "slot_failure: no slots failed"
        print(f"recovered: {int(snap['gen.slot_failures'])} slot "
              f"failures requeued")
    else:  # chaos
        assert injector is not None and injector.log, \
            f"{scenario}: no fault events fired"
        print(f"recovered: completed under {len(injector.log)} "
              f"injected events")


def run_rl(args):
    """Scheduler search -> Plan -> engine-executed RL iterations.

    Returns the trainer and one metrics dict per iteration (with the
    iteration's wall seconds under ``iter_s``)."""
    from repro.core import topology, workflow
    from repro.core.plan import check_constraints
    from repro.core.sha import HybridScheduler
    from repro.data.synthetic import AdditionTask, PromptDataset, VOCAB_SIZE
    from repro.rl.trainer import RLConfig, RLTrainer

    cfg = archs.get(args.arch, smoke=args.smoke)
    # the addition task's ids 0..VOCAB_SIZE-1 are valid in any vocabulary
    # at least that large; the model keeps its own vocabulary and dtype
    if cfg.vocab_size < VOCAB_SIZE:
        raise ValueError(f"{cfg.name}: vocab {cfg.vocab_size} < the "
                         f"addition task's {VOCAB_SIZE} ids")
    task = AdditionTask(max_operand=9)
    if jax.default_backend() == "tpu":
        # plan on the chips present, priced by their device_kind
        topo = topology.build_local(jax.devices())
    else:
        topo = topology.build_testbed(args.scenario,
                                      counts={"A100": 4, "L4": 4})
    print(f"planning on {topo.n} x "
          f"{'/'.join(sorted({d.spec.name for d in topo.devices}))}")
    spec = workflow.LLMSpec.from_model_config(cfg)
    wf = workflow.make_workflow(args.rl, spec,
                                synchronous=not args.asynchronous,
                                global_batch=args.batch, n_rollouts=4,
                                seq_in=task.prompt_len,
                                seq_out=task.max_answer_len)
    sched = HybridScheduler(topo, wf, max_groupings=8,
                            max_sizes_per_grouping=4)
    r = sched.search(budget=args.search_budget)
    ok, msg = check_constraints(topo, wf, r.plan)
    assert ok, msg
    print(f"plan: grouping={r.grouping} sizes={r.sizes} "
          f"predicted {r.cost * 1e3:.3f}ms/iter ({r.evals} evals)")

    rl = RLConfig(algorithm=args.rl, n_rollouts=4,
                  max_new_tokens=task.max_answer_len, lr=args.lr,
                  asynchronous=args.asynchronous,
                  spec_k=args.spec_k, draft_arch=args.draft_arch)
    trainer = RLTrainer(cfg, rl, task, jax.random.PRNGKey(0), plan=r.plan,
                        topo=topo, wf=wf)

    # fault injection (undeclared degradations + crash scenarios)
    injector = None
    warmup = 0
    if args.fault:
        from repro.core import retry
        from repro.core.workflow import TaskKind
        from repro.faults import FaultInjector, fault_scenario
        warmup = min(3, max(args.steps - 2, 1))
        fault_at = args.fault_at if args.fault_at is not None \
            else max(args.steps // 2, warmup + 1)
        gen_task = next(t for t in range(wf.n_tasks)
                        if wf.task(t).kind == TaskKind.GEN)
        train_task = next(t for t in range(wf.n_tasks)
                          if wf.task(t).kind == TaskKind.TRAIN
                          and wf.task(t).name.startswith("actor"))
        fplan = fault_scenario(args.fault, at=fault_at, topo=topo,
                               gen_task=gen_task, train_task=train_task,
                               n_tasks=wf.n_tasks)
        print(f"faults: {fplan.describe()}")
        injector = FaultInjector(fplan)
        trainer.engine.attach_fault_injector(injector)
        trainer.engine.set_task_retry(
            retry.RetryPolicy(max_attempts=3, base_delay_s=0.05))

    controller = None
    if args.drift or args.fault:
        from repro.engine.elastic import ElasticConfig, ElasticController
        if args.drift:
            drift_at = args.drift_at if args.drift_at is not None \
                else max(args.steps // 2, 1)
            feed = topology.drift_scenario(args.drift, topo, at=drift_at)
        else:
            # constant feed: any reaction must come from the divergence
            # monitor or failure escalation, never from a declared drift
            feed = lambda it: topo  # noqa: E731
        controller = ElasticController(
            trainer, feed,
            ElasticConfig(budget=args.search_budget,
                          ckpt_dir="results/elastic_ckpt"))

    from repro.engine.executor import TaskExecutionError

    ds = iter(PromptDataset(task, batch=args.batch, seed=1))
    key = jax.random.PRNGKey(42)
    history = []
    for step in range(args.steps):
        prompts, answers = next(ds)
        key, k = jax.random.split(key)
        t0 = time.time()
        try:
            m = trainer.iteration(prompts, answers, k)
        except TaskExecutionError as e:
            if controller is None:
                raise
            print(f"iter {step:4d} TASK FAILURE: {e}")
            rec = controller.handle_failure(step, e)
            print(f"  escalated: dropped dead devices, forced replan -> "
                  f"epoch={rec.epoch} "
                  f"new={rec.decision.new_cost * 1e3:.3f}ms/iter")
            m = trainer.iteration(prompts, answers, k)
        m = dict(m, iter_s=time.time() - t0)
        history.append(m)
        print(f"iter {step:4d} reward={m['reward_mean']:.3f} "
              f"kl={m['kl']:.3f} sync={m['sync_gb'] * 1e3:.1f}MB "
              f"({m['iter_s']:.2f}s)")
        if args.fault and step + 1 == warmup:
            # calibration warmup done: arm the divergence monitor and
            # calibrated deadlines — undeclared faults are detectable
            # from here on
            from repro.obs import calibrate as obs_cal
            cal = obs_cal.fit_from_engine(trainer.engine)
            monitor = obs_cal.DivergenceMonitor(threshold=2.0, sustain=2)
            trainer.engine.attach_divergence_monitor(monitor, cal)
            trainer.engine.set_task_deadlines(cal, slack=5.0)
            controller.monitor = monitor
            print(f"  calibrated ({cal.n_samples} samples); divergence "
                  f"monitor + deadlines armed")
        if args.fault and args.fault.startswith("ckpt"):
            controller.checkpoint_now(step)
        if controller is not None:
            rec = controller.poll(step)
            if rec is not None:
                d = rec.decision
                print(f"  drift{' (reactive)' if rec.reactive else ''}: "
                      f"reschedule in {rec.reschedule_s:.1f}s -> "
                      f"switch={d.switch} old={d.old_cost * 1e3:.3f}ms "
                      f"new={d.new_cost * 1e3:.3f}ms "
                      f"trans={d.transition_cost_s * 1e3:.3f}ms "
                      f"epoch={rec.epoch} "
                      f"ckpt={rec.ckpt_bytes / 1e6:.1f}MB")
    if injector is not None:
        fired = [r for r in injector.log if r["what"] != "activate"]
        print(f"faults fired: {len(injector.log)} events "
              f"({len(fired)} raises/corruptions)")
    if args.fault and args.require_recover:
        _assert_recovered(args.fault, trainer, controller, injector)
    if controller is not None:
        for row in trainer.engine.epoch_report():
            print(f"epoch {row['epoch']}: {row['iterations']} iters, "
                  f"measured {row['measured_iter_s'] * 1e3:.1f}ms/iter vs "
                  f"predicted {row['predicted_iter_s'] * 1e3:.3f}ms/iter")
    cmp = trainer.engine.compare_with_simulator()
    print(f"measured {cmp['measured_iter_s'] * 1e3:.1f}ms/iter vs "
          f"cost-model {cmp['predicted_iter_s'] * 1e3:.3f}ms/iter "
          f"(ratio {cmp['ratio']:.2f})")
    if args.calibrate:
        from repro.obs import calibrate as obs_cal
        c = obs_cal.fit_from_engine(trainer.engine)
        ccmp = trainer.engine.compare_with_simulator(
            cost_model=c.cost_model(trainer.engine.topo, trainer.wf))
        print(f"calibrated ({c.n_samples} samples, global scale "
              f"{c.global_scale:.3g}): ratio {ccmp['ratio']:.2f}")
    print("done")
    return trainer, history


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--rl", choices=["grpo", "ppo"], default=None,
                    help="run the plan-driven RL path instead of LM steps")
    ap.add_argument("--async", dest="asynchronous", action="store_true",
                    help="one-step off-policy RL execution (with --rl)")
    ap.add_argument("--scenario", default="single_region",
                    help="GPU testbed scenario the scheduler plans "
                         "against off the TPU (on a TPU it plans on the "
                         "chips present)")
    ap.add_argument("--search-budget", type=int, default=120,
                    help="scheduler budget in cost-model evaluations")
    ap.add_argument("--drift", default=None,
                    help="inject a named topology drift mid-run and react "
                         "elastically (with --rl); see "
                         "core.topology.DRIFT_SCENARIOS")
    ap.add_argument("--drift-at", type=int, default=None,
                    help="iteration the drift fires at (default steps//2)")
    ap.add_argument("--fault", default=None,
                    help="inject a named fault scenario mid-run (with "
                         "--rl); see repro.faults.FAULT_SCENARIOS; "
                         "composes with --drift")
    ap.add_argument("--fault-at", type=int, default=None,
                    help="iteration the fault fires at (default steps//2, "
                         "after the calibration warmup)")
    ap.add_argument("--require-recover", action="store_true",
                    help="exit non-zero unless the fault scenario's "
                         "recovery mechanism engaged (CI gate)")
    ap.add_argument("--calibrate", action="store_true",
                    help="fit cost-model calibration from the measured "
                         "timeline and report the corrected measured-vs-"
                         "predicted ratio (with --rl)")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="draft-model speculative decoding in GEN: "
                         "draft tokens per wave round (0 = off; "
                         "forces the genserve engine path)")
    ap.add_argument("--draft-arch", default="",
                    help="configs.archs entry for the speculative "
                         "draft ('' = scaled-down copy of --arch)")
    ap.add_argument("--trace", default=None, metavar="FILE",
                    help="write a Chrome-trace JSON of the run "
                         "(view in Perfetto / chrome://tracing)")
    return ap


def main():
    args = build_parser().parse_args()
    enable_compile_cache()
    if args.trace:
        obs_trace.enable()

    if args.rl:
        run_rl(args)
        if args.trace:
            obs_trace.export_chrome(args.trace)
            print(f"trace -> {args.trace}")
        return

    cfg = archs.get(args.arch, smoke=args.smoke)
    mesh = make_host_mesh()
    print(f"arch={cfg.name} params~{cfg.param_count():,} "
          f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))}")

    key = jax.random.PRNGKey(0)
    params = T.init_params(key, cfg)
    opt_cfg = adam.AdamConfig(lr=args.lr, total_steps=args.steps)
    opt_state = adam.init_adam_state(params, opt_cfg)
    train_step = jax.jit(steps_mod.make_train_step(cfg, opt_cfg))

    with mesh:
        for step in range(args.steps):
            key, k = jax.random.split(key)
            batch = make_batch(cfg, k, args.batch, args.seq)
            t0 = time.time()
            with obs_trace.span("train.step", step=step):
                params, opt_state, metrics = train_step(params, opt_state,
                                                        batch)
                loss = float(metrics["loss"])
            dt = time.time() - t0
            print(f"step {step:4d} loss={loss:.4f} "
                  f"gnorm={float(metrics['grad_norm']):.3f} ({dt:.2f}s)")
    if args.trace:
        obs_trace.export_chrome(args.trace)
        print(f"trace -> {args.trace}")
    print("done")


if __name__ == "__main__":
    main()
