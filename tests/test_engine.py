"""Plan-driven execution engine tests.

- engine-vs-simulator parity: same (workflow, plan) → the engine's
  measured timeline has the same stage ordering and colocation
  serialization as ``simulate``'s predicted timeline;
- plan-driven serialization: colocate-all plans serialize every task,
  disaggregated plans start disjoint groups simultaneously;
- async pipeline: iteration-t+1 generation uses pre-sync weights
  (one-step off-policy staleness is exactly one weight version).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import enumerate as enum_mod, simulator, topology, workflow
from repro.core.costmodel import CostModel
from repro.core.plan import check_constraints
from repro.data.synthetic import AdditionTask, VOCAB_SIZE
from repro.engine import placement as placement_mod
from repro.rl.trainer import RLConfig, RLTrainer

KEY = jax.random.PRNGKey(0)


def tiny_cfg():
    from repro.models.config import ModelConfig
    return ModelConfig(name="eng-tiny", n_layers=2, d_model=64, n_heads=2,
                       n_kv_heads=2, head_dim=32, d_ff=128,
                       vocab_size=VOCAB_SIZE, dtype="float32")


def disaggregated_setup(algorithm="grpo", asynchronous=False):
    """Trainer driven by a gen|rest plan on the 8-GPU reference pool."""
    cfg = tiny_cfg()
    task = AdditionTask(max_operand=9)
    rl = RLConfig(algorithm=algorithm, n_rollouts=4, max_new_tokens=4,
                  asynchronous=asynchronous)
    topo = topology.build_testbed("single_region",
                                  counts={"A100": 4, "L4": 4})
    spec = workflow.LLMSpec.from_model_config(cfg)
    wf = workflow.make_workflow(algorithm, spec,
                                synchronous=not asynchronous,
                                n_rollouts=rl.n_rollouts,
                                seq_in=task.prompt_len,
                                seq_out=rl.max_new_tokens, global_batch=1)
    grouping = next(g for g in enum_mod.priority_groupings(wf)
                    if len(g) == 2 and any(
                        wf.task(t).kind == workflow.TaskKind.GEN
                        for t in min(g, key=len)))
    sizes = enum_mod.proportional_sizes(wf, grouping, topo.n)
    plan = enum_mod.build_plan(topo, wf, grouping, sizes,
                               list(range(topo.n)))
    ok, msg = check_constraints(topo, wf, plan)
    assert ok, msg
    trainer = RLTrainer(cfg, rl, task, KEY, plan=plan, topo=topo, wf=wf)
    return trainer, topo, plan


def run_iters(trainer, n, batch=4, seed=0):
    task = trainer.task
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(7)
    out = []
    for _ in range(n):
        prompts, answers = task.sample_batch(rng, batch)
        key, k = jax.random.split(key)
        out.append(trainer.iteration(prompts, answers, k))
    return out


def device_sequences(timeline, plan):
    """Per plan-device sequence of (iteration, task) starts, time order."""
    seq = {}
    for e in sorted(timeline, key=lambda e: (e.time, e.iteration, e.task)):
        if e.kind != "start":
            continue
        for d in plan.assignment[e.task].reshape(-1):
            seq.setdefault(int(d), []).append((e.iteration, e.task))
    return seq


def intervals(timeline):
    """(iteration, task) -> (start, end)."""
    out = {}
    for e in timeline:
        k = (e.iteration, e.task)
        s, t = out.get(k, (None, None))
        out[k] = (e.time, t) if e.kind == "start" else (s, e.time)
    return out


def test_engine_simulator_parity():
    trainer, topo, plan = disaggregated_setup()
    n_iters = 4
    run_iters(trainer, n_iters)
    engine = trainer.engine
    sim = simulator.simulate(topo, trainer.wf, plan, n_iterations=n_iters)

    # same event set
    meas = engine.measured_result()
    assert {(e.iteration, e.task, e.kind) for e in meas.timeline} == \
        {(e.iteration, e.task, e.kind) for e in sim.timeline}

    # per-device start ordering identical (colocation serialization and
    # stage ordering match the simulator's schedule exactly)
    assert device_sequences(meas.timeline, plan) == \
        device_sequences(sim.timeline, plan)

    # dependencies respected in the measured timeline
    iv = intervals(meas.timeline)
    for it in range(n_iters):
        for t in range(trainer.wf.n_tasks):
            for d in trainer.wf.task(t).depends_on:
                assert iv[(it, t)][0] >= iv[(it, d)][1] - 1e-12

    # disjoint groups start concurrently in BOTH timelines: reward and
    # reference inference depend only on generation and sit in different
    # scheduling lanes than... (reward shares the non-gen group here, so
    # compare generation of iter t+1 overlapping nothing in sync mode)
    siv = intervals(sim.timeline)
    for it in range(n_iters):
        # both inference tasks become ready when generation ends
        assert iv[(it, 1)][0] >= iv[(it, 0)][1] - 1e-12
        assert siv[(it, 1)][0] >= siv[(it, 0)][1] - 1e-12


def test_colocated_plan_serializes_everything():
    cfg = tiny_cfg()
    task = AdditionTask(max_operand=9)
    rl = RLConfig(algorithm="grpo", n_rollouts=4, max_new_tokens=4)
    trainer = RLTrainer(cfg, rl, task, KEY)   # default colocate-all plan
    run_iters(trainer, 2)
    iv = intervals(trainer.engine.measured_result().timeline)
    keys = sorted(iv)
    for a in keys:
        for b in keys:
            if a >= b:
                continue
            s1, e1 = iv[a]
            s2, e2 = iv[b]
            assert e1 <= s2 + 1e-12 or e2 <= s1 + 1e-12, \
                f"colocated tasks {a} and {b} overlap"


def test_disaggregated_plan_overlaps_groups():
    """The plan demonstrably changes execution: with gen | rest groups,
    inference starts exactly when generation ends (its only dependency),
    not later — there is no whole-pool barrier between the groups — and
    the colocated reward/reference pair serializes back-to-back inside
    its lane."""
    trainer, topo, plan = disaggregated_setup()
    run_iters(trainer, 3)
    iv = intervals(trainer.engine.measured_result().timeline)
    gen_devs = {int(d) for d in plan.assignment[0].reshape(-1)}
    inf_devs = {int(d) for d in plan.assignment[1].reshape(-1)}
    assert not gen_devs & inf_devs
    for it in range(3):
        s1, e1 = iv[(it, 1)]
        s2, e2 = iv[(it, 2)]
        # reward becomes ready at gen end and its (disjoint) device
        # group is idle by then: any later start would be a barrier
        assert s1 == pytest.approx(iv[(it, 0)][1], abs=1e-9)
        # colocated lane: reference starts exactly at reward end
        assert s2 == pytest.approx(e1, abs=1e-9)
        assert e1 <= s2 + 1e-12 or e2 <= s1 + 1e-12


def test_async_generation_uses_pre_sync_weights():
    trainer, topo, plan = disaggregated_setup(asynchronous=True)
    metrics = run_iters(trainer, 4)
    assert metrics[0].get("pipeline_fill") == 1.0
    assert all("pipeline_fill" not in m for m in metrics[1:])
    recs = trainer.engine.pipeline.records
    assert len(recs) == 3          # fill iteration trains nothing
    # iteration 1 trains the fill rollouts (version 0, no sync yet)
    assert (recs[0].gen_version, recs[0].weight_version) == (0, 0)
    # steady state: the trained rollouts are exactly one sync behind
    for r in recs[1:]:
        assert r.weight_version - r.gen_version == 1, r
    # and the weight version advances once per trained iteration
    assert trainer.weight_version == 3


def test_sync_mode_trains_fresh_rollouts():
    trainer, topo, plan = disaggregated_setup(asynchronous=False)
    run_iters(trainer, 2)
    for r in trainer.engine.pipeline.records:
        assert r.gen_version == r.weight_version


def test_measured_vs_predicted_comparison():
    trainer, topo, plan = disaggregated_setup()
    run_iters(trainer, 4)
    cmp = trainer.engine.compare_with_simulator()
    assert cmp["measured_iter_s"] > 0
    assert cmp["predicted_iter_s"] > 0
    assert np.isfinite(cmp["ratio"])


def test_placement_put_moves_inputs_only_when_the_plan_spans_devices():
    """On a one-device plan ``put`` and ``put_params`` hand the inputs
    back untouched (no transfer, no commitment); a one-device placement
    in a plan over several devices commits them onto its device."""
    trainer, topo, plan = disaggregated_setup()
    pl = trainer.engine.placements[trainer.engine.ctx.actor_train]
    tree = {"x": jnp.ones((4, 3)), "y": np.ones(2, np.float32)}
    assert not pl.sharded and pl.plan_n_devices == 1
    assert pl.put(tree) is tree and pl.put_params(tree) is tree
    pl.plan_n_devices = 2          # as the engine sets it for such a plan
    for out in (pl.put(tree), pl.put_params(tree)):
        for leaf in out.values():
            assert leaf.committed
            assert leaf.sharding.device_set == set(pl.local_devices)


def test_advantage_prep_reads_nothing_to_the_host(monkeypatch):
    """One-device GRPO: ``ensure_train_batch`` reads no device array to
    the host (the transfer guard holds it on an accelerator; on the CPU,
    where a read is no transfer, fetching any ``jax.Array``'s value fails
    instead), ``rl.advantages.host_reads`` stays 0, and the iteration's
    kl, gen_len and reward_mean are the eager formulas' as floats."""
    from repro.engine import tasks as tasks_mod
    from repro.obs import metrics as obs_metrics
    from repro.rl import losses
    trainer, topo, plan = disaggregated_setup()
    assert not trainer.engine.ctx.multidev
    orig = tasks_mod.ensure_train_batch
    seen = []

    def fetch(self):
        raise AssertionError("a device array was read to the host")

    def guarded(st, bb):
        if "batch" not in bb:
            seen.append(bb)
        with monkeypatch.context() as m:
            m.setattr(type(jnp.zeros(())), "_value", property(fetch))
            with jax.transfer_guard_device_to_host("disallow"):
                orig(st, bb)
    monkeypatch.setattr(tasks_mod, "ensure_train_batch", guarded)
    reads = obs_metrics.counter("rl.advantages.host_reads")
    before = reads.value
    # the second iteration's actor differs from the reference: kl != 0
    out = run_iters(trainer, 2)[-1]
    assert len(seen) == 2 and reads.value == before
    bb = seen[-1]
    ro = bb["bundle"]["rollout"]
    _, kl = losses.kl_penalised_rewards(
        jnp.asarray(bb["scores"]), ro["logprobs"], bb["lp_ref"],
        ro["mask"], kl_beta=trainer.rl.kl_beta)
    want = {"kl": float(kl),
            "gen_len": float(np.asarray(ro["mask"]).sum(1).mean()),
            "reward_mean": float(bb["scores"].mean())}
    assert want["kl"] != 0.0
    for k, v in want.items():
        assert type(out[k]) is float
        assert out[k] == pytest.approx(v, rel=1e-6, abs=1e-7), k


def test_device_folding_deterministic():
    local = ["devA", "devB", "devC"]
    folded = placement_mod.fold_devices([0, 1, 2, 3, 4, 5, 6, 7], local)
    assert folded == ["devA", "devB", "devC"]
    assert placement_mod.fold_devices([5, 1], local) == ["devC", "devB"]
    # colliding plan ids collapse to one real device
    assert placement_mod.fold_devices([5, 2], local) == ["devC"]
    # stable: same plan devices -> same folding
    assert placement_mod.fold_devices([5, 1], local) == \
        placement_mod.fold_devices([5, 1], local)


def test_placement_mesh_axes():
    trainer, topo, plan = disaggregated_setup()
    for t, pl in trainer.engine.placements.items():
        assert pl.mesh.axis_names == ("data", "model")
        n = len(pl.local_devices)
        assert int(np.prod(pl.mesh_shape)) == n
        assert n <= jax.device_count()


def test_trainer_rejects_mismatched_plan():
    cfg = tiny_cfg()
    task = AdditionTask(max_operand=9)
    topo = topology.build_testbed("single_region",
                                  counts={"A100": 4, "L4": 4})
    spec = workflow.LLMSpec.from_model_config(cfg)
    ppo_wf = workflow.make_ppo(spec, global_batch=1)
    grouping = (tuple(range(ppo_wf.n_tasks)),)
    plan = enum_mod.build_plan(topo, ppo_wf, grouping, [topo.n],
                               list(range(topo.n)))
    rl = RLConfig(algorithm="grpo", n_rollouts=4, max_new_tokens=4)
    with pytest.raises(ValueError):
        RLTrainer(cfg, rl, task, KEY, plan=plan, topo=topo)
