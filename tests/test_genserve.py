"""Continuous-batching generation subsystem tests.

- single-wave equivalence: batch <= wave, same rng -> genserve reproduces
  ``rollout.generate`` token-for-token (valid positions; sampled and
  greedy, chunked and unchunked);
- slot-recycling correctness: batch >> wave under greedy decoding ->
  every recycled request's output equals the single-wave reference
  (per-slot cache positions, scatter injection, ring windows);
- EOS edge: a prompt already ending in EOS yields an all-invalid mask on
  both paths (the shared ``models.sampling`` aliveness helper);
- occupancy parity: uniform lengths -> measured slot-table occupancy
  equals ``core.plan`` predictions exactly; skewed budgets stay within
  the ideal bound;
- engine integration: the TaskKind.GEN executor produces per-wave Event
  timeline entries comparable against the cost model's decode_wave.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import plan as plan_mod
from repro.data.synthetic import AdditionTask, EOS, VOCAB_SIZE
from repro.genserve import adapter as genserve
from repro.genserve.decoder import GenServeConfig, serve
from repro.genserve.scheduler import FREE, Request, RequestQueue, SlotTable
from repro.models import transformer as T
from repro.models.config import LayerSpec, Mixer, ModelConfig
from repro.rl import rollout
from repro.rl.trainer import RLConfig, RLTrainer

KEY = jax.random.PRNGKey(0)
P, N = 8, 6


def tiny_cfg(window=None):
    return ModelConfig(name=f"gs-tiny-w{window}", n_layers=2, d_model=64,
                       n_heads=2, n_kv_heads=2, head_dim=32, d_ff=128,
                       vocab_size=VOCAB_SIZE, dtype="float32",
                       pattern=(LayerSpec(window=window),))


def hybrid_cfg(window=4):
    """Two repeats of a ring-window attention layer beside a Mamba layer."""
    return dataclasses.replace(
        tiny_cfg(window), name=f"gs-hybrid-w{window}", n_layers=4,
        pattern=(LayerSpec(window=window), LayerSpec(mixer=Mixer.MAMBA)))


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_cfg()
    return cfg, T.init_params(KEY, cfg)


def prompts_for(n, key=3, cfg=None):
    return jax.random.randint(jax.random.PRNGKey(key), (n, P), 0,
                              (cfg or tiny_cfg()).vocab_size, jnp.int32)


def assert_rollout_equal(ref, got, atol=1e-4):
    mr, mg = np.asarray(ref["mask"]), np.asarray(got["mask"])
    np.testing.assert_array_equal(mr, mg)
    np.testing.assert_array_equal(
        np.asarray(ref["gen_tokens"]) * mr.astype(np.int32),
        np.asarray(got["gen_tokens"]) * mg.astype(np.int32))
    np.testing.assert_allclose(np.asarray(ref["logprobs"]) * mr,
                               np.asarray(got["logprobs"]) * mg,
                               rtol=1e-4, atol=atol)
    np.testing.assert_array_equal(
        np.asarray(ref["sequences"])[:, :P], np.asarray(got["sequences"])[:, :P])


@pytest.mark.parametrize("chunk", [1, 3])
def test_single_wave_equivalence_sampled(setup, chunk):
    """batch == wave, same rng -> identical sequences/logprobs/mask."""
    cfg, params = setup
    prompts = prompts_for(4)
    sampler = rollout.SamplerConfig(max_new_tokens=N, temperature=1.0,
                                    eos_token=EOS)
    ref = rollout.generate(params, cfg, prompts, jax.random.PRNGKey(7),
                           sampler)
    gcfg = GenServeConfig(wave=4, max_new_tokens=N, decode_chunk=chunk,
                          eos_token=EOS)
    got, stats = serve(params, cfg, prompts, jax.random.PRNGKey(7), gcfg)
    assert_rollout_equal(ref, got)
    assert stats["prefills"] == 1 and stats["admitted"] == 4


def test_single_wave_equivalence_greedy_padded_wave(setup):
    """batch < wave: padded prefill rows must not disturb real requests."""
    cfg, params = setup
    prompts = prompts_for(3)
    sampler = rollout.SamplerConfig(max_new_tokens=N, greedy=True,
                                    eos_token=EOS)
    ref = rollout.generate(params, cfg, prompts, jax.random.PRNGKey(2),
                           sampler)
    gcfg = GenServeConfig(wave=5, max_new_tokens=N, greedy=True,
                          eos_token=EOS)
    got, _ = serve(params, cfg, prompts, jax.random.PRNGKey(2), gcfg)
    assert_rollout_equal(ref, got)


@pytest.mark.parametrize("window", [None, 4])
def test_slot_recycling_matches_reference(window):
    """batch >> wave, greedy: recycled slots (fresh cache rows, per-slot
    positions — including ring-buffer windows) reproduce the single-wave
    reference for every request."""
    cfg = tiny_cfg(window=window)
    params = T.init_params(KEY, cfg)
    prompts = prompts_for(14, key=5, cfg=cfg)
    sampler = rollout.SamplerConfig(max_new_tokens=N, greedy=True,
                                    eos_token=3)
    ref = rollout.generate(params, cfg, prompts, jax.random.PRNGKey(1),
                           sampler)
    gcfg = GenServeConfig(wave=4, max_new_tokens=N, greedy=True, eos_token=3)
    got, stats = serve(params, cfg, prompts, jax.random.PRNGKey(1), gcfg)
    assert_rollout_equal(ref, got)
    assert stats["admitted"] == stats["retired"] == 14
    assert stats["prefills"] >= 2          # slots were actually recycled
    assert stats["wave"] == 4
    assert max(stats["occupancy_trace"]) <= 4


def test_prompt_ending_in_eos_starts_dead(setup):
    """Shared EOS edge: prompt's last token == EOS -> whole mask invalid
    on both the reference path and genserve."""
    cfg, params = setup
    prompts = np.array(prompts_for(4))
    # pin the alive/dead split instead of trusting the random prompts:
    # rows 0/2 must not end in EOS by luck of the PRNG stream
    prompts[0, -1] = prompts[2, -1] = 0
    prompts[1, -1] = EOS
    prompts[3, -1] = EOS
    sampler = rollout.SamplerConfig(max_new_tokens=N, eos_token=EOS)
    ref = rollout.generate(params, cfg, jnp.asarray(prompts),
                           jax.random.PRNGKey(4), sampler)
    gcfg = GenServeConfig(wave=4, max_new_tokens=N, eos_token=EOS)
    got, _ = serve(params, cfg, prompts, jax.random.PRNGKey(4), gcfg)
    for out in (ref, got):
        m = np.asarray(out["mask"])
        assert m[1].sum() == 0 and m[3].sum() == 0
        assert m[0, 0] == 1 and m[2, 0] == 1
    assert_rollout_equal(ref, got)


def test_per_request_budgets_and_skewed_occupancy(setup):
    """gen_lens caps each request; measured occupancy stays within the
    ideal continuous-batching bound from core.plan.predicted_occupancy."""
    cfg, params = setup
    B, W = 12, 4
    lens = [1, 1, 2, 2, 3, 3, N, N, N, N, N, N]
    prompts = prompts_for(B, key=9)
    gcfg = GenServeConfig(wave=W, max_new_tokens=N, greedy=True)
    got, stats = serve(params, cfg, prompts, KEY, gcfg, gen_lens=lens)
    np.testing.assert_array_equal(np.asarray(got["mask"]).sum(1), lens)
    ideal = plan_mod.predicted_occupancy(B, wave=W, gen_lens=lens)
    assert 0 < stats["mean_occupancy"] <= ideal + 1e-9
    # genserve does strictly less decode work than ceil(B/W) full waves
    assert stats["decode_steps"] < int(np.ceil(B / W)) * N


def test_no_decode_steps_when_all_finish_at_admission(setup):
    """Budget-1 requests finish with their prefill-sampled token; the
    engine must not burn any wave decode steps on them."""
    cfg, params = setup
    prompts = prompts_for(8, key=13)
    gcfg = GenServeConfig(wave=4, max_new_tokens=N, greedy=True,
                          decode_chunk=3)
    got, stats = serve(params, cfg, prompts, KEY, gcfg,
                       gen_lens=[1] * 8)
    np.testing.assert_array_equal(np.asarray(got["mask"]).sum(1),
                                  np.ones(8))
    assert stats["decode_steps"] == 0
    assert stats["prefills"] == 2 and stats["retired"] == 8


def test_uniform_occupancy_matches_decode_wave(setup):
    """No EOS, uniform budgets: every wave is full -> measured slot-table
    occupancy equals the cost model's decode_wave exactly."""
    cfg, params = setup
    B, W = 12, 4
    prompts = prompts_for(B, key=11)
    gcfg = GenServeConfig(wave=W, max_new_tokens=N, greedy=True)
    got, stats = serve(params, cfg, prompts, KEY, gcfg)
    assert np.asarray(got["mask"]).sum() == B * N
    assert stats["mean_occupancy"] == pytest.approx(
        plan_mod.predicted_occupancy(B, wave=W))
    assert stats["mean_occupancy"] == pytest.approx(
        float(plan_mod.decode_wave(B * W / B)))  # = W: full waves


@pytest.mark.parametrize("window,hybrid", [
    pytest.param(None, False, id="None"), pytest.param(4, False, id="4"),
    pytest.param(4, True, id="hybrid-mamba")])
def test_batched_decode_matches_vmapped_per_slot(window, hybrid):
    """Tentpole parity: the batched wave decode (per-slot positions in
    one decode_step) is token-for-token identical to the legacy vmapped
    per-slot path, including recycled slots at distinct positions, ring
    windows and a hybrid attention + Mamba pattern."""
    cfg = hybrid_cfg(window) if hybrid else tiny_cfg(window=window)
    params = T.init_params(KEY, cfg)
    prompts = prompts_for(14, key=5, cfg=cfg)
    kw = dict(wave=4, max_new_tokens=N, greedy=True, eos_token=3)
    ref, _ = serve(params, cfg, prompts, jax.random.PRNGKey(1),
                   GenServeConfig(decode_path="vmapped", **kw))
    got, stats = serve(params, cfg, prompts, jax.random.PRNGKey(1),
                       GenServeConfig(decode_path="batched", **kw))
    assert_rollout_equal(ref, got)
    assert stats["prefills"] >= 2          # slots were actually recycled


def test_batched_decode_matches_vmapped_gqa_softcap():
    """Same parity on a GQA + softcap config (the flash-decode kernel's
    hard cases), sampled rng path."""
    cfg = ModelConfig(name="gs-gqa", n_layers=2, d_model=64, n_heads=4,
                      n_kv_heads=2, head_dim=16, d_ff=128,
                      vocab_size=VOCAB_SIZE, dtype="float32",
                      attn_softcap=30.0)
    params = T.init_params(KEY, cfg)
    prompts = prompts_for(9, key=8, cfg=cfg)
    kw = dict(wave=3, max_new_tokens=N, eos_token=EOS)
    ref, _ = serve(params, cfg, prompts, jax.random.PRNGKey(6),
                   GenServeConfig(decode_path="vmapped", **kw))
    got, _ = serve(params, cfg, prompts, jax.random.PRNGKey(6),
                   GenServeConfig(decode_path="batched", **kw))
    assert_rollout_equal(ref, got)


@pytest.mark.parametrize("window", [None, 4])
def test_batched_decode_pallas_kernel_parity(window):
    """End-to-end: the batched wave decode under the pallas impl (the
    Sq == 1 flash-decode kernel seeing the whole wave, plus the prefill
    flash kernel) reproduces the jnp path on recycled slots."""
    from repro.models import attention as attn
    cfg = tiny_cfg(window=window)
    params = T.init_params(KEY, cfg)
    prompts = prompts_for(10, key=5, cfg=cfg)
    gcfg = GenServeConfig(wave=4, max_new_tokens=N, greedy=True,
                          eos_token=3)
    ref, _ = serve(params, cfg, prompts, jax.random.PRNGKey(1), gcfg)
    try:
        attn.set_attention_impl("pallas")
        got, stats = serve(params, cfg, prompts, jax.random.PRNGKey(1),
                           gcfg)
    finally:
        attn.set_attention_impl("jnp")
    assert_rollout_equal(ref, got, atol=1e-3)
    assert stats["prefills"] >= 2


def test_sjf_admission_policy():
    """admission="sjf": shortest budgets admitted first (queue order),
    greedy outputs still equal the FIFO run request-for-request."""
    q = RequestQueue([Request(0, 5), Request(1, 2), Request(2, 5),
                      Request(3, 1), Request(4, 2)], policy="sjf")
    order = [r.rid for r in q.pop(5)]
    assert order == [3, 1, 4, 0, 2]        # budget asc, arrival tie-break

    cfg = tiny_cfg()
    params = T.init_params(KEY, cfg)
    B, W = 10, 3
    lens = [N, 1, N, 2, 1, N, 2, N, 1, N]
    prompts = prompts_for(B, key=21)
    fifo, s_fifo = serve(params, cfg, prompts, KEY,
                         GenServeConfig(wave=W, max_new_tokens=N,
                                        greedy=True), gen_lens=lens)
    sjf, s_sjf = serve(params, cfg, prompts, KEY,
                       GenServeConfig(wave=W, max_new_tokens=N,
                                      greedy=True, admission="sjf"),
                       gen_lens=lens)
    assert_rollout_equal(fifo, sjf)
    np.testing.assert_array_equal(np.asarray(sjf["mask"]).sum(1), lens)
    assert s_sjf["admitted"] == s_sjf["retired"] == B


def test_sjf_aging_anti_starvation():
    """``aging=K`` bounds SJF starvation: a long request passed over K
    times jumps ahead of every shorter newcomer (starved requests drain
    in arrival order); ``aging=0`` reproduces the pure static order;
    and greedy outputs still equal the FIFO run request-for-request —
    admission order changes, per-request results do not."""
    reqs = [Request(0, 5), Request(1, 1), Request(2, 1), Request(3, 1),
            Request(4, 1)]
    q = RequestQueue(list(reqs), policy="sjf", aging=2)
    order = [q.pop(1)[0].rid for _ in range(5)]
    # rid 0 (budget 5) is skipped twice, then admitted before rids 3, 4
    assert order == [1, 2, 0, 3, 4]
    q0 = RequestQueue(list(reqs), policy="sjf", aging=0)
    assert [r.rid for r in q0.pop(5)] == [1, 2, 3, 4, 0]

    cfg = tiny_cfg()
    params = T.init_params(KEY, cfg)
    B, W = 10, 3
    lens = [N, 1, N, 2, 1, N, 2, N, 1, N]
    prompts = prompts_for(B, key=21)
    fifo, _ = serve(params, cfg, prompts, KEY,
                    GenServeConfig(wave=W, max_new_tokens=N, greedy=True),
                    gen_lens=lens)
    aged, s_aged = serve(params, cfg, prompts, KEY,
                         GenServeConfig(wave=W, max_new_tokens=N,
                                        greedy=True, admission="sjf",
                                        sjf_aging=1),
                         gen_lens=lens)
    assert_rollout_equal(fifo, aged)
    assert s_aged["admitted"] == s_aged["retired"] == B


# ---------------------------------------------------------------------------
# Chunked prefill (mixed wave-step admission)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chunk,dchunk", [(1, 1), (3, 1), (5, 1), (8, 1),
                                          (3, 3), (2, 4)])
def test_chunked_admission_single_wave_exact(setup, chunk, dchunk):
    """Tentpole pin: chunked admission on a single-wave batch is
    token-exact vs the reference path under sampling — the landing
    round's first-token draw consumes rngs[0] exactly like the one-shot
    admit, no decode key is burned during prefill rounds, and decode
    resumes at rngs[1] whatever the mixed-scan length (dchunk > 1 pins
    the multi-sub-round key bookkeeping)."""
    cfg, params = setup
    prompts = prompts_for(4)
    sampler = rollout.SamplerConfig(max_new_tokens=N, temperature=1.0,
                                    eos_token=EOS)
    ref = rollout.generate(params, cfg, prompts, jax.random.PRNGKey(7),
                           sampler)
    gcfg = GenServeConfig(wave=4, max_new_tokens=N, eos_token=EOS,
                          prefill_chunk=chunk, decode_chunk=dchunk,
                          measure_ttft=True)
    got, stats = serve(params, cfg, prompts, jax.random.PRNGKey(7), gcfg)
    assert_rollout_equal(ref, got)
    assert stats["prefill_slot_steps"] == 4 * -(-P // chunk)
    assert all(t > 0 for t in stats["ttft"].values())


def _random_trace_case(rng, case):
    """One random admission trace: mixed windows/GQA, random budgets,
    random EOS, prompts longer than the chunk."""
    window = rng.choice([None, 4])
    gqa = bool(rng.integers(0, 2))
    cfg = ModelConfig(name=f"gs-prop-{case}", n_layers=2, d_model=64,
                      n_heads=4 if gqa else 2, n_kv_heads=2,
                      head_dim=16 if gqa else 32, d_ff=128,
                      vocab_size=VOCAB_SIZE, dtype="float32",
                      pattern=(LayerSpec(window=window),))
    params = T.init_params(jax.random.PRNGKey(case), cfg)
    B = int(rng.integers(6, 12))
    W = int(rng.integers(2, 5))
    chunk = int(rng.integers(1, P))          # prompts exceed the chunk
    dchunk = int(rng.integers(1, 5))         # mixed scans span sub-rounds
    eos = int(rng.integers(0, VOCAB_SIZE)) if rng.integers(0, 2) else None
    lens = rng.integers(1, N + 1, B).tolist() if rng.integers(0, 2) \
        else None
    prompts = jax.random.randint(jax.random.PRNGKey(100 + case), (B, P),
                                 0, cfg.vocab_size, jnp.int32)
    return cfg, params, prompts, B, W, chunk, dchunk, eos, lens


@pytest.mark.parametrize("case", range(4))
def test_chunked_admission_random_traces(case):
    """Property-style pin: over random admission traces (recycling,
    ring windows, GQA, random EOS, random budgets, prompts longer than
    ``prefill_chunk``) chunked admission reproduces the one-shot admit
    path token-for-token under greedy decoding."""
    rng = np.random.default_rng(1234 + case)
    cfg, params, prompts, B, W, chunk, dchunk, eos, lens = \
        _random_trace_case(rng, case)
    kw = dict(wave=W, max_new_tokens=N, greedy=True, eos_token=eos)
    ref, s_ref = serve(params, cfg, prompts, KEY, GenServeConfig(**kw),
                       gen_lens=lens)
    got, s_got = serve(params, cfg, prompts, KEY,
                       GenServeConfig(prefill_chunk=chunk,
                                      decode_chunk=dchunk,
                                      measure_ttft=True, **kw),
                       gen_lens=lens)
    assert_rollout_equal(ref, got)
    assert s_got["admitted"] == s_got["retired"] == B
    assert s_got["prefill_slot_steps"] >= B * (P // chunk)
    # every request saw a first token
    assert len(s_got["ttft"]) == B


def test_chunked_admission_ragged_prompts(setup):
    """Per-request prompt lengths: each request's outputs equal its own
    unpadded reference rollout (per-slot landing positions)."""
    cfg, params = setup
    B = 6
    pl = [8, 3, 5, 8, 2, 6]
    prompts = np.array(prompts_for(B, key=17))
    gcfg = GenServeConfig(wave=3, max_new_tokens=N, greedy=True,
                          prefill_chunk=3)
    got, stats = serve(params, cfg, prompts, KEY, gcfg, prompt_lens=pl)
    for i, L in enumerate(pl):
        ref = rollout.generate(
            params, cfg, jnp.asarray(prompts[i:i + 1, :L]), KEY,
            rollout.SamplerConfig(max_new_tokens=N, greedy=True))
        np.testing.assert_array_equal(np.asarray(ref["gen_tokens"])[0],
                                      np.asarray(got["gen_tokens"])[i])
    # short prompts land in fewer rounds than the padded width implies
    assert stats["prefill_slot_steps"] \
        == sum(-(-l // 3) for l in pl)


def test_mixed_rounds_honest_occupancy(setup):
    """Satellite pin: prefill-only rounds are recorded as zero decode
    progress (mean_occupancy is honest), prefill work is credited in
    busy_occupancy, and the measured busy figure respects the
    prefill-aware predicted_occupancy bound."""
    cfg, params = setup
    B, W, C = 10, 4, 2
    lens = [1, 2, N, 3, N, 1, 2, N, 3, N]
    prompts = prompts_for(B, key=23)
    gcfg = GenServeConfig(wave=W, max_new_tokens=N, greedy=True,
                          prefill_chunk=C)
    got, stats = serve(params, cfg, prompts, KEY, gcfg, gen_lens=lens)
    np.testing.assert_array_equal(np.asarray(got["mask"]).sum(1), lens)
    # trace lengths agree: every mixed round contributed to both traces
    assert stats["prefill_rounds"] <= stats["decode_steps"]
    assert stats["prefill_slot_steps"] == B * -(-P // C)
    ideal = plan_mod.predicted_occupancy(
        B, wave=W, gen_lens=lens,
        prefill_rounds=plan_mod.prefill_rounds(P, C))
    assert 0 < stats["busy_occupancy"] <= ideal + 1e-9
    # prefill-only rounds drag decode occupancy below the zero-cost
    # admission ideal — the honesty the satellite fix is about
    assert stats["mean_occupancy"] < plan_mod.predicted_occupancy(
        B, wave=W, gen_lens=lens)


def test_predicted_occupancy_prefill_rounds():
    """Unit pins for the prefill-aware occupancy model."""
    # zero prefill rounds: unchanged historical behavior
    assert plan_mod.predicted_occupancy(8, wave=4) == pytest.approx(4.0)
    assert plan_mod.prefill_rounds(8, 3) == 3
    assert plan_mod.prefill_rounds(8, 0) == 0
    # uniform lens with prefill rounds need max_new_tokens
    with pytest.raises(AssertionError):
        plan_mod.predicted_occupancy(8, wave=4, prefill_rounds=2)
    # work bound: 8 requests x (4 decode + 2 prefill) rounds over 4
    # slots -> 12 rounds, occupancy 48/12
    occ = plan_mod.predicted_occupancy(8, wave=4, prefill_rounds=2,
                                       max_new_tokens=4)
    assert occ == pytest.approx(48 / 12)
    # chain bound: one long request dominates
    occ = plan_mod.predicted_occupancy(2, wave=4, gen_lens=[10, 1],
                                       prefill_rounds=3)
    assert occ == pytest.approx((10 + 1 + 6) / 13)
    # per-request prefill rounds: the chain bound must track the worst
    # (len + rounds) pair, not the mean — a short-prompt long-gen
    # request finishing in 11 rounds yields busy 17/11, and the bound
    # covers it (the scalar-mean form would not)
    occ = plan_mod.predicted_occupancy(2, wave=4, gen_lens=[10, 1],
                                       prefill_rounds=[1, 5])
    assert occ == pytest.approx(17 / 11)


def test_costmodel_gen_prefill_chunk():
    """The mixed-round prefill price is positive for GEN, zero for other
    tasks, and scales with the chunk width."""
    from repro.core.costmodel import CostModel
    from repro.core import topology, workflow
    from repro.core.enumerate import build_plan
    topo = topology.build_host(2)
    wf = workflow.make_grpo(workflow.QWEN_1_7B, global_batch=64)
    plan = build_plan(topo, wf, (tuple(range(wf.n_tasks)),), [2], [0, 1])
    cm = CostModel(topo, wf)
    gen_t = 0
    c16 = cm.gen_prefill_chunk(plan, gen_t, chunk=16)
    c64 = cm.gen_prefill_chunk(plan, gen_t, chunk=64)
    assert 0 < c16 < c64
    train_t = wf.n_tasks - 1
    assert cm.gen_prefill_chunk(plan, train_t, chunk=16) == 0.0


def test_cache_gather_scatter_roundtrip():
    """[R, B, ...] cache rows move wholesale: scatter(src at mask) then
    gather returns src rows exactly; unmasked rows untouched."""
    from repro.models import cache as cache_mod
    rng = np.random.default_rng(0)
    blocks = {"layer0": {"k": jnp.asarray(rng.normal(size=(2, 4, 3, 2, 5)),
                                          jnp.float32),
                         "conv": jnp.asarray(rng.normal(size=(2, 4, 7)),
                                             jnp.float32)}}
    src = jax.tree_util.tree_map(lambda l: l + 100.0, blocks)
    mask = jnp.asarray([True, False, True, False])
    out = cache_mod.scatter_slots(blocks, src, mask)
    got = cache_mod.gather_slots(out, jnp.asarray([0, 2]))
    want = cache_mod.gather_slots(src, jnp.asarray([0, 2]))
    kept = cache_mod.gather_slots(out, jnp.asarray([1, 3]))
    orig = cache_mod.gather_slots(blocks, jnp.asarray([1, 3]))
    for a, b in ((got, want), (kept, orig)):
        for la, lb in zip(jax.tree_util.tree_leaves(a),
                          jax.tree_util.tree_leaves(b)):
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def test_scheduler_slot_table_invariants():
    table = SlotTable(3)
    q = RequestQueue([Request(i, 4) for i in range(5)])
    reqs = q.pop(len(table.free_slots()))
    table.admit(table.free_slots(), reqs)
    assert table.active == 3 and len(q) == 2
    with pytest.raises(AssertionError):
        table.admit([0], q.pop(1))           # slot already occupied
    done = table.retire_finished(np.array([True, False, True]))
    assert done == [1] and table.active == 2
    assert table.slot_req[1] == FREE
    table.record_step([3, 2, 2])
    assert table.decode_steps == 3 and table.slot_steps == 7
    assert table.mean_occupancy() == pytest.approx(7 / 3)


def test_adapter_fast_path_stats(setup):
    cfg, params = setup
    prompts = prompts_for(4)
    sampler = rollout.SamplerConfig(max_new_tokens=N, eos_token=EOS)
    ro, stats = genserve.generate(params, cfg, prompts,
                                  jax.random.PRNGKey(7), sampler, wave=8)
    assert stats["engine"] == "single-wave"
    assert stats["decode_steps"] == N
    assert stats["slot_steps"] == int(np.asarray(ro["mask"]).sum())
    ref = rollout.generate(params, cfg, prompts, jax.random.PRNGKey(7),
                           sampler)
    assert_rollout_equal(ref, ro)


def test_engine_gen_executor_chunked_prefill_parity():
    """TaskKind.GEN with chunked admission: the engine's measured-vs-
    predicted occupancy covers prefill rounds (busy accounting on the
    measured side, prefill_rounds on the prediction side) instead of
    assuming admission free."""
    cfg = tiny_cfg()
    task = AdditionTask(max_operand=9)
    rl = RLConfig(algorithm="grpo", n_rollouts=4, max_new_tokens=4,
                  gen_engine="genserve", decode_chunk=2, prefill_chunk=2)
    trainer = RLTrainer(cfg, rl, task, KEY)
    rng = np.random.default_rng(0)
    prompts, answers = task.sample_batch(rng, 3)
    m = trainer.iteration(prompts, answers, jax.random.PRNGKey(7))
    assert m["gen_prefill_rounds"] >= 1
    assert 0 < m["gen_busy_occupancy"] <= m["gen_wave"]
    summary = trainer.engine.wave_occupancy_summary()
    assert summary["measured_occupancy"] > 0
    assert summary["predicted_occupancy"] > 0
    # prediction charges admission: never above the free-admission ideal
    # (equal exactly when batch == wave — every slot busy throughout)
    free = plan_mod.predicted_occupancy(12, wave=m["gen_wave"])
    assert summary["predicted_occupancy"] <= free
    assert np.isfinite(summary["ratio"])


def test_engine_gen_executor_emits_wave_events():
    """TaskKind.GEN through genserve: per-wave Event entries with
    occupancy annotations, comparable against decode_wave predictions."""
    cfg = tiny_cfg()
    task = AdditionTask(max_operand=9)
    rl = RLConfig(algorithm="grpo", n_rollouts=4, max_new_tokens=4,
                  gen_engine="genserve", decode_chunk=2)
    trainer = RLTrainer(cfg, rl, task, KEY)
    rng = np.random.default_rng(0)
    key = jax.random.PRNGKey(7)
    for _ in range(2):
        prompts, answers = task.sample_batch(rng, 3)
        key, k = jax.random.split(key)
        m = trainer.iteration(prompts, answers, k)
    assert m["gen_wave"] >= 1
    assert 0 < m["gen_wave_occupancy"] <= m["gen_wave"]
    events = trainer.engine.wave_timeline
    assert events and all(e.occupancy is not None and e.wave is not None
                          for e in events)
    assert {e.kind for e in events} == {"start", "end"}
    assert {e.iteration for e in events} == {0, 1}
    summary = trainer.engine.wave_occupancy_summary()
    assert summary["measured_occupancy"] > 0
    assert summary["predicted_occupancy"] > 0
    assert np.isfinite(summary["ratio"])
