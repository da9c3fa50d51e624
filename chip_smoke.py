"""Bring-up check of the main path on a TPU, at the published width of
qwen3-0.6b, through the entry points a user calls.

One process holds the chip and runs these phases in order, each printing
one line:

  rl       ``launch/train.py``'s RL path: scheduler search -> Plan ->
           engine, 3 synchronous GRPO iterations over 16 prompts x 4
           rollouts (64 rollouts exceed one decode wave, so generation
           runs through the genserve decoder).
  serve    ``launch/serve.py``'s path: 16 requests of 32 prompt tokens
           and 16 new tokens, chunked prefill and the paged prefix cache.
  kernels  the flash and decode attention Pallas kernels, compiled for
           the chip at qwen3-0.6b head shapes in bf16, against ref.py.

``--four-chips`` runs only the four-chip phase instead: an asynchronous
GRPO run on a gen|rest plan (generation TP=2 on chips 0-1, inference and
training TP=2 on chips 2-3), compared with the one-chip run of the same
configuration in this process.

The last line of standard output is one JSON object naming the device.
Without a TPU, or when a phase fails, the script exits non-zero and does
not print it.

    python chip_smoke.py
    python chip_smoke.py --four-chips
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax                                                  # noqa: E402
import jax.numpy as jnp                                     # noqa: E402
import numpy as np                                          # noqa: E402

from repro.configs import archs                             # noqa: E402
from repro.core import enumerate as enum_mod                # noqa: E402
from repro.core import topology, workflow                   # noqa: E402
from repro.core.plan import check_constraints               # noqa: E402
from repro.data.synthetic import AdditionTask               # noqa: E402
from repro.engine import tasks as tasks_mod                 # noqa: E402
from repro.kernels.decode_attention.ops import decode_attention  # noqa: E402
from repro.kernels.decode_attention.ref import decode_attention_ref  # noqa
from repro.kernels.flash_attention.ops import flash_attention  # noqa: E402
from repro.kernels.flash_attention.ref import flash_attention_ref  # noqa
from repro.launch import serve as serve_mod                 # noqa: E402
from repro.launch import train as train_mod                 # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.rl.trainer import RLConfig, RLTrainer            # noqa: E402

ARCH = "qwen3-0.6b"
RL_STEPS, RL_BATCH, N_ROLLOUTS = 3, 16, 4
SERVE = dict(batch=16, prompt_len=32, new_tokens=16)
# bf16 tolerances of tests/test_kernels.py
KERNEL_TOL = dict(rtol=2e-2, atol=2e-2)
# four-chip vs one-chip: the first trained iteration's loss.  At random
# init the task reward is zero, so the whitened advantages come from the
# bf16 rounding of the KL term and the loss is -mean((ratio - 1) * A):
# a few bf16 ulps (2**-8) on either side of zero in both runs.
LOSS_ATOL = 5e-2
# greedy tokens equal between the TP=2 generation group and one chip; a
# broken mesh gives unrelated tokens (share ~ 0), bf16 reduction-order
# ties flip only a few argmaxes
MIN_GREEDY_SHARE = 0.5


class PhaseFailed(RuntimeError):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise PhaseFailed(msg)


def device_info(devices=None) -> dict:
    devices = list(devices) if devices is not None else jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_rl(arch: str = ARCH, smoke: bool = False,
             steps: int = RL_STEPS, batch: int = RL_BATCH) -> dict:
    argv = ["--arch", arch, "--rl", "grpo", "--steps", str(steps),
            "--batch", str(batch), "--search-budget", "40"]
    args = train_mod.build_parser().parse_args(
        argv + (["--smoke"] if smoke else []))
    trainer, history = train_mod.run_rl(args)
    cfg = trainer.cfg
    del trainer
    gc.collect()         # free the trainer's device state for the next phase
    losses = [m["loss"] for m in history]
    rollouts = batch * N_ROLLOUTS
    check(len(history) == steps, f"rl: {len(history)} of {steps} iterations")
    check(all(np.isfinite(losses)), f"rl: non-finite loss {losses}")
    # genserve's decode wave is bounded below the rollout count; the
    # single-wave fast path would report the whole batch as its wave
    check(all(m["gen_wave"] < rollouts for m in history),
          f"rl: generation did not run through genserve "
          f"(waves {[m['gen_wave'] for m in history]} for {rollouts})")
    out = {"arch": cfg.name, "layers": cfg.n_layers, "d_model": cfg.d_model,
           "vocab": cfg.vocab_size, "dtype": cfg.dtype, "rollouts": rollouts,
           "gen_wave": history[-1]["gen_wave"], "loss": losses,
           "reward": [m["reward_mean"] for m in history],
           "iter_s": [m["iter_s"] for m in history],
           "device_kind": jax.devices()[0].device_kind,
           # None where the backend keeps no allocator statistics (CPU)
           "peak_bytes_in_use": (jax.devices()[0].memory_stats()
                                 or {}).get("peak_bytes_in_use")}
    print("rl:", json.dumps(out))
    return out


def phase_serve(arch: str = ARCH, smoke: bool = False) -> dict:
    argv = ["--arch", arch, "--batch", str(SERVE["batch"]), "--wave", "4",
            "--prompt-len", str(SERVE["prompt_len"]),
            "--new-tokens", str(SERVE["new_tokens"]),
            "--prefix-cache", "--shared-prompts", "16",
            "--expect-prefix-hits"]
    args = serve_mod.build_parser().parse_args(
        argv + (["--smoke"] if smoke else []))
    ro, stats = serve_mod.run_serve(args)
    per_req = np.asarray(ro["mask"]).sum(axis=1)
    check(ro["gen_tokens"].shape == (SERVE["batch"], SERVE["new_tokens"]),
          f"serve: gen_tokens shape {ro['gen_tokens'].shape}")
    check(bool((per_req == SERVE["new_tokens"]).all()),
          f"serve: tokens per request {per_req.tolist()}")
    out = {"requests": int(per_req.size), "tokens": int(per_req.sum()),
           "engine": stats["engine"], "prefill_chunk": args.prefill_chunk,
           "page_size": args.page_size,
           "prefix_hit_rate": float(stats.get("prefix_hit_rate", 0.0))}
    print("serve:", json.dumps(out))
    return out


def _max_err(out, ref) -> float:
    return float(np.max(np.abs(np.asarray(out, np.float32)
                               - np.asarray(ref, np.float32))))


def phase_kernels(smoke: bool = False, interpret: bool = False) -> dict:
    """Both attention kernels against ref.py at qwen3-0.6b head shapes
    (16 query heads, 8 kv heads, head_dim 128) in bf16."""
    cfg = archs.get(ARCH)
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    B, S = (1, 128) if smoke else (2, 1024)
    Bd, L = (2, 256) if smoke else (32, 2048)
    ks = jax.random.split(jax.random.PRNGKey(3), 7)
    dt = jnp.bfloat16
    q = jax.random.normal(ks[0], (B, S, H, hd), dt)
    k = jax.random.normal(ks[1], (B, S, KV, hd), dt)
    v = jax.random.normal(ks[2], (B, S, KV, hd), dt)
    qd = jax.random.normal(ks[3], (Bd, H, hd), dt)
    kd = jax.random.normal(ks[4], (Bd, L, KV, hd), dt)
    vd = jax.random.normal(ks[5], (Bd, L, KV, hd), dt)
    bias = jnp.where(jax.random.bernoulli(ks[6], 0.75, (Bd, L)),
                     0.0, -1e30).astype(jnp.float32)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=interpret)

    def decode(q, k, v, b):
        return decode_attention(q, k, v, b, interpret=interpret)

    out = {"mode": "interpret" if interpret else "compiled"}
    for name, fn, ref, args in [
            ("flash", flash, flash_attention_ref, (q, k, v)),
            ("decode", decode, decode_attention_ref, (qd, kd, vd, bias))]:
        if not interpret:
            hlo = jax.jit(fn).lower(*args).compile().as_text()
            check("tpu_custom_call" in hlo,
                  f"kernels: {name} compiled without a Pallas kernel")
        got = jax.block_until_ready(fn(*args))
        want = ref(*args)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   **KERNEL_TOL)
        out[name] = {"shapes": [list(a.shape) for a in args[:2]],
                     "max_abs_err": _max_err(got, want)}
    print("kernels:", json.dumps(out))
    return out


def _greedy_tokens(trainer, prompts, answers) -> np.ndarray:
    """The GEN executor's rollout with greedy sampling on the trainer's
    current generation weights and placement."""
    sampler = trainer.sampler
    trainer.sampler = dataclasses.replace(sampler, greedy=True)
    try:
        bb = trainer.prepare_inputs(prompts, answers, jax.random.PRNGKey(5))
        placement = trainer.engine.placements[trainer.engine.ctx.gen_task]
        tasks_mod.run_generation(trainer, bb, placement)
    finally:
        trainer.sampler = sampler
    return np.asarray(bb["fresh"]["rollout"]["gen_tokens"])


def _run(trainer, steps: int, batch: int):
    """Greedy tokens on the initial weights, then ``steps`` sampled async
    iterations on a fixed prompt/rng stream (identical for every run)."""
    rng = np.random.default_rng(0)
    prompts, answers = trainer.task.sample_batch(rng, batch)
    greedy = _greedy_tokens(trainer, prompts, answers)
    key = jax.random.PRNGKey(7)
    history = []
    for _ in range(steps):
        prompts, answers = trainer.task.sample_batch(rng, batch)
        key, k = jax.random.split(key)
        history.append(trainer.iteration(prompts, answers, k))
    return greedy, history


def phase_four_chips(arch: str = ARCH, smoke: bool = False,
                     steps: int = RL_STEPS, batch: int = RL_BATCH) -> dict:
    devices = jax.devices()
    check(len(devices) >= 4, f"four-chips: {len(devices)} devices")
    devices = devices[:4]
    cfg = archs.get(arch, smoke=smoke)
    task = AdditionTask(max_operand=9)
    rl = RLConfig(algorithm="grpo", n_rollouts=N_ROLLOUTS,
                  max_new_tokens=task.max_answer_len, asynchronous=True)
    wf = workflow.make_workflow(
        "grpo", workflow.LLMSpec.from_model_config(cfg), synchronous=False,
        global_batch=batch, n_rollouts=N_ROLLOUTS, seq_in=task.prompt_len,
        seq_out=task.max_answer_len)
    topo = topology.build_local(devices)
    kinds = workflow.TaskKind
    plan = enum_mod.gen_rest_plan(
        topo, wf, [0, 1], [2, 3],
        {kinds.GEN: (1, 1, 2), kinds.INF: (1, 1, 2), kinds.TRAIN: (1, 1, 2)})
    ok, msg = check_constraints(topo, wf, plan)
    check(ok, f"four-chips: plan infeasible: {msg}")

    trainer = RLTrainer(cfg, rl, task, jax.random.PRNGKey(0), plan=plan,
                        topo=topo, wf=wf, devices=devices)
    eng = trainer.engine
    gen_pl = eng.placements[eng.ctx.gen_task]
    train_pl = eng.placements[eng.ctx.actor_train]
    gen_ids = sorted(d.id for d in gen_pl.local_devices)
    rest_ids = sorted({d.id for t, pl in eng.placements.items()
                       if t != eng.ctx.gen_task for d in pl.local_devices})
    meshes = {"gen": list(gen_pl.mesh_shape),
              "train": list(train_pl.mesh_shape)}
    collisions = eng.ctx.folding.n_collisions
    overlap = eng.overlap_active()
    greedy4, hist4 = _run(trainer, steps, batch)
    del trainer, eng, gen_pl, train_pl
    gc.collect()         # chip 0 must be free for the one-chip run

    base = RLTrainer(cfg, rl, task, jax.random.PRNGKey(0), wf=wf,
                     devices=devices[:1])
    greedy1, hist1 = _run(base, steps, batch)
    del base
    gc.collect()

    check(collisions == 0, f"four-chips: {collisions} folding collisions")
    check(not set(gen_ids) & set(rest_ids),
          f"four-chips: gen {gen_ids} and train {rest_ids} share chips")
    check(overlap, "four-chips: overlap_active() is false")
    check(hist4[0].get("pipeline_fill") == 1.0,
          "four-chips: iteration 0 was not the pipeline fill")
    loss4, loss1 = float(hist4[1]["loss"]), float(hist1[1]["loss"])
    check(np.isfinite(loss4) and np.isfinite(loss1),
          f"four-chips: non-finite loss {loss4} / {loss1}")
    check(abs(loss4 - loss1) <= LOSS_ATOL,
          f"four-chips: loss {loss4} vs one chip {loss1} "
          f"(atol {LOSS_ATOL})")
    share = float(np.mean(greedy4 == greedy1))
    check(share >= MIN_GREEDY_SHARE,
          f"four-chips: greedy tokens identical {share:.3f} "
          f"< {MIN_GREEDY_SHARE}")
    out = {"arch": cfg.name, "gen_chips": gen_ids, "train_chips": rest_ids,
           "meshes": meshes,
           "collisions": collisions, "overlap_active": overlap,
           "loss_four_chips": loss4, "loss_one_chip": loss1,
           "loss_abs_diff": abs(loss4 - loss1),
           "greedy_identical_share": share,
           "iterations": len(hist4)}
    print("four-chips:", json.dumps(out))
    return out


# ---------------------------------------------------------------------------

class CompileClock:
    """Sums the backend (XLA) compile seconds JAX reports."""

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event: str, duration: float, **kwargs) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration


def timed(name: str, clock: CompileClock, fn) -> None:
    """Run one phase; print its wall seconds and the compile seconds in
    them (set-up facts of this run, not speeds)."""
    t0, c0 = time.monotonic(), clock.seconds
    fn()
    print(f"{name} time:", json.dumps({
        "wall_s": time.monotonic() - t0, "compile_s": clock.seconds - c0}))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip gen|rest phase and its "
                         "one-chip comparison")
    args = ap.parse_args(argv)
    dev = device_info()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev['platform']!r}, "
              f"{dev['count']} x {dev['kind']})", file=sys.stderr)
        return 2
    print("compile cache:", enable_compile_cache())
    clock = CompileClock()
    if args.four_chips:
        timed("four-chips", clock, phase_four_chips)
        dev = device_info(jax.devices()[:4])
    else:
        timed("rl", clock, phase_rl)
        timed("serve", clock, phase_serve)
        timed("kernels", clock, phase_kernels)
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
