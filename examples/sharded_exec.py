"""Sharded plan execution on a forced 8-device host (CPU CI analogue).

Forces ``--xla_force_host_platform_device_count=8`` *before* importing
jax, builds a gen|rest disaggregated plan whose training group runs
DP=2/TP=2 on its own 4 devices while generation runs DP=2/TP=2 on the
other 4, and validates the sharded execution path end to end:

- group-aware folding is injective: GEN and TRAIN land on disjoint real
  device sets with zero collisions;
- the DP=2/TP=2 sharded train step matches an unsharded single-device
  run numerically (loss within tolerance) and greedy generation is
  token-identical;
- async mode runs the GEN lane wall-clock concurrent with the training
  stages (``overlap_active``), the one-step-staleness invariant intact;
- ``compare_with_simulator`` prices the realized parallelization.

Run:  python examples/sharded_exec.py [--iters 4] [--json]
"""
import argparse
import json
import os
import sys

_FLAG = "--xla_force_host_platform_device_count=8"
if "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = \
        (os.environ.get("XLA_FLAGS", "") + " " + _FLAG).strip()

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "src"))

import jax                                              # noqa: E402
import numpy as np                                      # noqa: E402

from repro.core import enumerate as enum_mod            # noqa: E402
from repro.core import topology, workflow               # noqa: E402
from repro.core.plan import check_constraints           # noqa: E402
from repro.data.synthetic import AdditionTask, VOCAB_SIZE  # noqa: E402
from repro.models.config import ModelConfig             # noqa: E402
from repro.rl.trainer import RLConfig, RLTrainer        # noqa: E402

KEY = jax.random.PRNGKey(0)


def tiny_cfg():
    return ModelConfig(name="sharded-tiny", n_layers=2, d_model=64,
                       n_heads=2, n_kv_heads=2, head_dim=32, d_ff=128,
                       vocab_size=VOCAB_SIZE, dtype="float32")


def build_plan_8dev(wf):
    """gen | rest over 8 plan devices: generation on 0-3, the inference
    and training tasks on 4-7, actor training explicitly DP=2/TP=2."""
    topo = topology.build_testbed("single_region",
                                  counts={"A100": 4, "L4": 4})
    kinds = workflow.TaskKind
    plan = enum_mod.gen_rest_plan(
        topo, wf, range(4), range(4, 8),
        {kinds.GEN: (2, 1, 2), kinds.TRAIN: (2, 1, 2), kinds.INF: (4, 1, 1)})
    ok, msg = check_constraints(topo, wf, plan)
    assert ok, msg
    return topo, plan


def make_trainer(devices=None, greedy=False):
    cfg = tiny_cfg()
    task = AdditionTask(max_operand=9)
    # whitening off: it normalizes by the in-group advantage std, which
    # amplifies TP reduction-order noise (~1e-6) to O(1) when a group's
    # rewards are nearly uniform — parity would compare amplified noise
    rl = RLConfig(algorithm="grpo", n_rollouts=4, max_new_tokens=4,
                  asynchronous=True, greedy=greedy,
                  whiten_advantages=False)
    wf = workflow.make_workflow("grpo", workflow.LLMSpec.from_model_config(cfg),
                                synchronous=False, n_rollouts=rl.n_rollouts,
                                seq_in=task.prompt_len,
                                seq_out=rl.max_new_tokens, global_batch=1)
    topo, plan = build_plan_8dev(wf)
    trainer = RLTrainer(cfg, rl, task, KEY, plan=plan, topo=topo, wf=wf,
                        devices=devices)
    return trainer, topo, plan


def run(trainer, iters, batch=4, seed=0):
    """Same prompt/rng stream for every trainer — the runs are
    numerically comparable iteration by iteration."""
    task = trainer.task
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(7)
    metrics, gen_tokens = [], []
    for _ in range(iters):
        prompts, answers = task.sample_batch(rng, batch)
        key, k = jax.random.split(key)
        metrics.append(trainer.iteration(prompts, answers, k))
        pend = trainer.engine.pipeline._pending
        gen_tokens.append(np.asarray(pend["rollout"]["gen_tokens"])
                          if pend is not None else None)
    return metrics, gen_tokens


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--iters", type=int, default=4)
    # first trained iteration matches to ~1e-6; later iterations carry
    # compounded float32 TP reduction-order drift through the updated
    # params (observed ~1% by iteration 3 on the tiny model)
    ap.add_argument("--loss-rtol", type=float, default=5e-2)
    ap.add_argument("--loss-atol", type=float, default=1e-4)
    ap.add_argument("--json", action="store_true",
                    help="emit a machine-readable summary on stdout")
    args = ap.parse_args()

    n_dev = jax.device_count()
    assert n_dev >= 8, \
        f"need 8 forced host devices, got {n_dev} (XLA_FLAGS lost?)"

    # sharded run on all 8 devices, unsharded baseline pinned to one
    sharded, topo, plan = make_trainer()
    baseline, _, _ = make_trainer(devices=[jax.devices()[0]])

    eng = sharded.engine
    gen_t, train_t = eng.ctx.gen_task, eng.ctx.actor_train

    # -- placement: disjoint groups, zero collisions, DP=2/TP=2 --------
    folding = eng.ctx.folding
    assert folding.n_collisions == 0, folding.collisions
    assert not folding.oversubscribed
    gen_pl, train_pl = eng.placements[gen_t], eng.placements[train_t]
    gen_ids = {d.id for d in gen_pl.local_devices}
    train_ids = {d.id for d in train_pl.local_devices}
    assert gen_ids.isdisjoint(train_ids), (gen_ids, train_ids)
    assert train_pl.mesh_shape == (2, 2), train_pl.mesh_shape
    assert (train_pl.dp_eff, train_pl.tp_eff) == (2, 2)
    assert gen_pl.mesh_shape == (2, 2)
    assert eng.overlap_active(), "disjoint async groups must overlap"
    assert not baseline.engine.overlap_active()
    base_pl = baseline.engine.placements[train_t]
    assert not base_pl.sharded and base_pl.n_devices == 1

    # -- numerics: sharded == unsharded --------------------------------
    # temperature sampling: in-group reward variance makes the GRPO
    # advantages (and so the train-step loss) non-trivially nonzero —
    # the parity below actually exercises the DP=2/TP=2 update
    m_sh, g_sh = run(sharded, args.iters)
    m_bl, g_bl = run(baseline, args.iters)

    assert m_sh[0].get("pipeline_fill") == 1.0   # async fill iteration
    for it, (a, b) in enumerate(zip(g_sh, g_bl)):
        assert a is not None and b is not None
        assert np.array_equal(a, b), \
            f"iter {it}: sampled generation diverged between meshes"
    losses_sh = [m["loss"] for m in m_sh[1:]]
    losses_bl = [m["loss"] for m in m_bl[1:]]
    assert any(abs(x) > 1e-6 for x in losses_sh), \
        "degenerate run: every loss is zero, parity would be vacuous"
    np.testing.assert_allclose(losses_sh, losses_bl,
                               rtol=args.loss_rtol, atol=args.loss_atol)
    rewards_sh = [m["reward_mean"] for m in m_sh[1:]]
    rewards_bl = [m["reward_mean"] for m in m_bl[1:]]
    np.testing.assert_allclose(rewards_sh, rewards_bl, rtol=1e-6)

    # -- greedy decode: token-identical across meshes ------------------
    greedy_sh, _, _ = make_trainer(greedy=True)
    greedy_bl, _, _ = make_trainer(devices=[jax.devices()[0]], greedy=True)
    _, gg_sh = run(greedy_sh, 2)
    _, gg_bl = run(greedy_bl, 2)
    for it, (a, b) in enumerate(zip(gg_sh, gg_bl)):
        assert np.array_equal(a, b), \
            f"iter {it}: greedy generation diverged between meshes"

    # async one-step staleness intact under the overlapped walk
    for r in eng.pipeline.records[1:]:
        assert r.weight_version - r.gen_version == 1, r

    cmp = eng.compare_with_simulator()
    occ = eng.wave_occupancy_summary()
    summary = {
        "devices": n_dev,
        "gen_devices": sorted(gen_ids),
        "train_devices": sorted(train_ids),
        "train_mesh": list(train_pl.mesh_shape),
        "folding_collisions": folding.n_collisions,
        "overlap_active": eng.overlap_active(),
        "loss_sharded": losses_sh,
        "loss_baseline": losses_bl,
        "tokens_identical": True,
        "measured_iter_s": cmp["measured_iter_s"],
        "predicted_iter_s": cmp["predicted_iter_s"],
        "predicted_iter_realized_s": cmp["predicted_iter_realized_s"],
        "tp_shrunk": cmp["tp_shrunk"],
        "overlap_honest": occ.get("overlap_honest", 1.0),
    }
    if args.json:
        print(json.dumps(summary))
    else:
        print(f"devices: {n_dev}  gen on {sorted(gen_ids)}, "
              f"train on {sorted(train_ids)} "
              f"(mesh {train_pl.mesh_shape}, collisions "
              f"{folding.n_collisions}, overlap {eng.overlap_active()})")
        for it, (ls, lb) in enumerate(zip(losses_sh, losses_bl), start=1):
            print(f"iter {it}: loss sharded={ls:+.6f} "
                  f"baseline={lb:+.6f}  delta={ls - lb:+.2e}")
        print("greedy generation token-identical across all iterations")
        print(f"measured {cmp['measured_iter_s']:.4f}s/iter, "
              f"predicted {cmp['predicted_iter_s']:.4f}s "
              f"(realized {cmp['predicted_iter_realized_s']:.4f}s, "
              f"tp_shrunk={bool(cmp['tp_shrunk'])})")
        print("sharded execution parity OK")
    return summary


if __name__ == "__main__":
    main()
