"""Scheduler unit + integration tests: cost model, constraints, SHA-EA,
ILP optimality on tiny instances, baselines, simulator consistency."""
import math

import numpy as np
import pytest

from repro.core import baselines, enumerate as enum_mod, loadbalance, \
    simulator, topology, workflow
from repro.core.costmodel import CostModel, ring_cost
from repro.core.ilp import ilp_scheduler
from repro.core.plan import check_constraints, memory_overflow
from repro.core.sha import HybridScheduler


@pytest.fixture(scope="module")
def small_topo():
    return topology.build_testbed("single_region",
                                  counts={"A100": 4, "L4": 4})


@pytest.fixture(scope="module")
def big_topo():
    return topology.build_testbed("multi_country")


@pytest.fixture(scope="module")
def grpo_wf():
    return workflow.make_grpo(workflow.QWEN_1_7B, global_batch=64)


@pytest.fixture(scope="module")
def ppo_wf():
    return workflow.make_ppo(workflow.QWEN_8B)


def test_set_partitions_bell_numbers():
    # B_1..B_6 = 1, 2, 5, 15, 52, 203
    for n, bell in [(1, 1), (2, 2), (3, 5), (4, 15), (5, 52), (6, 203)]:
        parts = enum_mod.set_partitions(range(n))
        assert len(parts) == bell
        for p in parts:
            flat = sorted(x for b in p for x in b)
            assert flat == list(range(n))


def test_workflow_structure():
    ppo = workflow.make_ppo(workflow.QWEN_4B)
    assert ppo.n_tasks == 6
    stages = ppo.stages()
    assert stages == [[0], [1, 2, 3], [4, 5]]
    grpo = workflow.make_grpo(workflow.QWEN_4B)
    assert grpo.n_tasks == 4
    assert grpo.stages() == [[0], [1, 2], [3]]


def test_ring_cost_properties(small_topo):
    devs = [0, 1, 2, 3]
    c1 = ring_cost(small_topo, devs, 1e6)
    c2 = ring_cost(small_topo, devs, 1e9)
    assert 0 < c1 < c2          # monotone in volume
    assert ring_cost(small_topo, [0], 1e9) == 0.0
    # exact two-device cost
    expected = small_topo.alpha(0, 1) + 1e9 / (small_topo.beta(0, 1) * 1e9)
    assert abs(ring_cost(small_topo, [0, 1], 1e9) - expected) < 1e-12


def test_plan_constraints_catch_violations(small_topo, grpo_wf):
    grouping = (tuple(range(grpo_wf.n_tasks)),)
    plan = enum_mod.build_plan(small_topo, grpo_wf, grouping, [8],
                               list(range(8)))
    ok, msg = check_constraints(small_topo, grpo_wf, plan)
    assert ok, msg
    # break it: duplicate device in a task's assignment
    t0 = list(plan.assignment)[0]
    plan.assignment[t0] = np.zeros_like(plan.assignment[t0])
    ok, msg = check_constraints(small_topo, grpo_wf, plan)
    assert not ok


def test_cost_model_monotonic_in_compute(small_topo, grpo_wf):
    grouping = (tuple(range(grpo_wf.n_tasks)),)
    plan = enum_mod.build_plan(small_topo, grpo_wf, grouping, [8],
                               list(range(8)))
    base = CostModel(small_topo, grpo_wf).cost(plan)
    # double every device's TFLOPS -> cost strictly decreases
    fast = topology.Topology(
        [topology.Device(d.id, topology.GPUSpec(
            d.spec.name, d.spec.fp16_tflops * 2, d.spec.mem_gb,
            d.spec.hbm_gbps, d.spec.intra_node_gbps),
            d.machine, d.zone, d.region) for d in small_topo.devices],
        small_topo.latency_s, small_topo.bandwidth_gbps)
    faster = CostModel(fast, grpo_wf).cost(plan)
    assert faster < base


def test_cost_model_monotonic_in_bandwidth(small_topo, grpo_wf):
    grouping = (tuple(range(grpo_wf.n_tasks)),)
    plan = enum_mod.build_plan(small_topo, grpo_wf, grouping, [8],
                               list(range(8)))
    base = CostModel(small_topo, grpo_wf).cost(plan)
    slow = topology.Topology(small_topo.devices, small_topo.latency_s,
                             small_topo.bandwidth_gbps * 0.1)
    slower = CostModel(slow, grpo_wf).cost(plan)
    assert slower >= base


def test_sha_ea_beats_baselines(big_topo, ppo_wf):
    sched = HybridScheduler(big_topo, ppo_wf, max_groupings=8,
                            max_sizes_per_grouping=4)
    r = sched.search(budget=120)
    assert r.plan is not None
    ok, msg = check_constraints(big_topo, ppo_wf, r.plan)
    assert ok, msg
    r_verl = baselines.verl_scheduler(big_topo, ppo_wf)
    assert r.cost <= r_verl.cost * 1.01


def test_sha_improves_with_budget(big_topo, ppo_wf):
    costs = []
    for budget in (40, 400):
        sched = HybridScheduler(big_topo, ppo_wf, max_groupings=8,
                                max_sizes_per_grouping=4, seed=3)
        costs.append(sched.search(budget=budget).cost)
    assert costs[1] <= costs[0]


def test_ilp_not_worse_than_sha(small_topo, grpo_wf):
    r_ilp = ilp_scheduler(small_topo, grpo_wf, max_seconds=90,
                          max_nodes=500_000)
    assert r_ilp.plan is not None
    sched = HybridScheduler(small_topo, grpo_wf, max_groupings=15,
                            max_sizes_per_grouping=6)
    r_sha = sched.search(budget=1200)
    assert r_ilp.cost <= r_sha.cost * 1.001
    # SHA-EA near-optimality (paper: within 1%; we allow 8% at this budget)
    assert r_sha.cost <= r_ilp.cost * 1.08


def test_streamrl_and_deap_run(big_topo, ppo_wf):
    r_srl = baselines.streamrl_scheduler(big_topo, ppo_wf, budget=512)
    assert r_srl.plan is not None and math.isfinite(r_srl.cost)
    r_deap = baselines.deap_scheduler(big_topo, ppo_wf, budget=60)
    assert math.isfinite(r_deap.cost)


def test_simulator_matches_costmodel_sync(big_topo, ppo_wf):
    sched = HybridScheduler(big_topo, ppo_wf, max_groupings=6,
                            max_sizes_per_grouping=3)
    r = sched.search(budget=60)
    sim = simulator.simulate(big_topo, ppo_wf, r.plan)
    # event-driven timeline vs closed-form composition: within 20%
    assert abs(sim.iteration_time - r.cost) / r.cost < 0.20


def test_async_faster_than_sync(big_topo):
    wf_sync = workflow.make_ppo(workflow.QWEN_8B, synchronous=True)
    wf_async = workflow.make_ppo(workflow.QWEN_8B, synchronous=False)
    grouping = enum_mod.priority_groupings(wf_sync)[2]  # gen | rest
    sizes = enum_mod.proportional_sizes(wf_sync, grouping, big_topo.n)
    plan = enum_mod.build_plan(big_topo, wf_sync, grouping, sizes,
                               list(range(big_topo.n)))
    ok, _ = check_constraints(big_topo, wf_sync, plan)
    if ok:
        c_sync = CostModel(big_topo, wf_sync).cost(plan)
        c_async = CostModel(big_topo, wf_async).cost(plan)
        assert c_async <= c_sync


def test_load_balancing_helps_or_neutral(big_topo, ppo_wf):
    grouping = (tuple(range(ppo_wf.n_tasks)),)
    plan = enum_mod.build_plan(big_topo, ppo_wf, grouping, [big_topo.n],
                               list(range(big_topo.n)))
    cm = CostModel(big_topo, ppo_wf)
    base = cm.cost(plan)
    balanced = loadbalance.balance(big_topo, ppo_wf, plan)
    assert cm.cost(balanced) <= base * 1.001


def test_memory_overflow_metric(small_topo, grpo_wf):
    grouping = (tuple(range(grpo_wf.n_tasks)),)
    plan = enum_mod.build_plan(small_topo, grpo_wf, grouping, [8],
                               list(range(8)))
    over = memory_overflow(small_topo, grpo_wf, plan)
    ok, msg = check_constraints(small_topo, grpo_wf, plan)
    assert (over == 0.0) == ok


def test_scenarios_build():
    for scen in topology.SCENARIOS:
        topo = topology.build_testbed(scen)
        assert topo.n == 64
        assert (topo.bandwidth_gbps > 0).all()
        assert (topo.latency_s >= 0).all()
    tpu = topology.build_tpu_pool()
    assert tpu.n == 48


def test_async_trainer_pipeline():
    """Async RL: first call fills the pipeline, later calls train on the
    previous rollouts; reward still climbs."""
    import jax
    from repro.data.synthetic import AdditionTask, PromptDataset, VOCAB_SIZE
    from repro.models.config import ModelConfig
    from repro.rl.trainer import RLConfig, RLTrainer

    cfg = ModelConfig(name="async-t", n_layers=2, d_model=96, n_heads=4,
                      n_kv_heads=2, head_dim=24, d_ff=192,
                      vocab_size=VOCAB_SIZE, dtype="float32")
    task = AdditionTask(max_operand=4)
    rl = RLConfig(algorithm="grpo", n_rollouts=8, max_new_tokens=3,
                  lr=5e-4, kl_beta=0.0, asynchronous=True)
    trainer = RLTrainer(cfg, rl, task, jax.random.PRNGKey(0))
    import numpy as np
    ds = iter(PromptDataset(task, batch=12, seed=1))
    key = jax.random.PRNGKey(9)
    rewards = []
    for it in range(10):
        prompts, answers = next(ds)
        key, k = jax.random.split(key)
        m = trainer.iteration(prompts, answers, k)
        if it == 0:
            assert m.get("pipeline_fill") == 1.0
        else:
            rewards.append(m["reward_mean"])
    assert np.mean(rewards[-3:]) >= np.mean(rewards[:3]) - 0.02


def test_online_redeployment():
    """§6: network degradation triggers a beneficial reschedule at the
    checkpoint boundary; a no-op change keeps the incumbent."""
    import numpy as np
    from repro.core import redeploy
    topo = topology.build_testbed("single_region")
    wf = workflow.make_grpo(workflow.QWEN_8B)
    sched = HybridScheduler(topo, wf, max_groupings=8,
                            max_sizes_per_grouping=4)
    r = sched.search(budget=120)

    # same topology: switching should not be (meaningfully) beneficial
    d_same = redeploy.reschedule(topo, wf, r.plan, budget=60)
    assert d_same.old_cost <= d_same.new_cost * 1.5

    # degrade half the cluster's links 20x: expect a valid decision with
    # finite costs either way
    topo2 = topology.Topology(topo.devices, topo.latency_s * 10,
                              topo.bandwidth_gbps * 0.05)
    d = redeploy.reschedule(topo2, wf, r.plan, budget=120)
    assert np.isfinite(d.new_cost)
    assert d.plan is not None
    ok, _ = check_constraints(topo2, wf, d.plan)
    assert ok


class _Dev:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


def test_local_topology_prices_chips_by_device_kind():
    topo = topology.build_local([_Dev("tpu", "TPU v5 lite")] * 4)
    assert topo.n == 4
    assert all(d.spec is topology.TPU_V5E for d in topo.devices)
    assert topo.locality(0, 3) == 3               # one machine
    cpu = topology.build_local([_Dev("cpu", "cpu")] * 2)
    assert all(d.spec is topology.A100 for d in cpu.devices)
    with pytest.raises(ValueError, match="no spec for device kind"):
        topology.build_local([_Dev("tpu", "TPU v99")])


def test_gen_rest_plan_disjoint_groups():
    wf = workflow.make_grpo(workflow.QWEN_1_7B, global_batch=8)
    topo = topology.build_host(4, topology.TPU_V5E)
    kinds = workflow.TaskKind
    plan = enum_mod.gen_rest_plan(
        topo, wf, [0, 1], [2, 3],
        {kinds.GEN: (1, 1, 2), kinds.INF: (1, 1, 2), kinds.TRAIN: (1, 1, 2)})
    gen = next(t for t in range(wf.n_tasks) if wf.task(t).kind == kinds.GEN)
    assert plan.group_of(gen).devices == (0, 1)
    for t in range(wf.n_tasks):
        assert plan.parallel[t] == (1, 1, 2)
        want = [0, 1] if t == gen else [2, 3]
        assert plan.assignment[t].reshape(-1).tolist() == want


def test_one_chip_plan_for_the_lfm2_cut():
    """LLMSpec learns the expert width, the experts held and the layer
    mix: the six-layer LFM2-8B-A1B cut with 8 of 32 experts held counts
    669M weights (the published 32 experts x d_ff 7168 in every layer
    would be 8.5B), and the scheduler finds a one-chip GRPO plan for the
    cell's batch on a v5e."""
    import dataclasses
    from repro.configs import archs
    cfg = archs.LFM2_8B_A1B
    cut = dataclasses.replace(cfg, n_layers=6, pattern=cfg.pattern[:6],
                              experts_held=8)
    m = workflow.LLMSpec.from_model_config(cut)
    assert (m.moe_layers, m.attn_layers, m.experts_held, m.expert_h2) == \
        (4, 1, 8, 1792)
    held = m.layer_weight_count * m.n_layers
    assert 5.2e8 < held < 5.6e8          # layers only; 669M with embedding
    assert m.layer_active_count < m.layer_weight_count
    wf = workflow.make_grpo(m, global_batch=2, n_rollouts=8, seq_in=64,
                            seq_out=128)
    topo = topology.build_host(1, topology.TPU_V5E)
    r = HybridScheduler(topo, wf, max_groupings=8,
                        max_sizes_per_grouping=4).search(budget=40)
    ok, msg = check_constraints(topo, wf, r.plan)
    assert ok, msg
    assert np.isfinite(r.cost)
