"""The ``rl_plan`` driver, the LFM2 reference and the MoE readers on the
CPU at a small size.

``plan_run`` drives the LFM2 cell through ``bench.run.run_cell`` as
``conftest.small_run`` does a dense cell: a six-layer LFM2 of width 64
(32 experts of width 32 in the router, 8 held; ``model_lfm2.configs``
replaced for the run), short prompts and responses, one CPU device."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import limits_at_test_size
from bench import correct

LFM2 = "lfm2-8b-a1b.grpo-sync"


def small_lfm2(dtype="bfloat16"):
    from repro.configs import archs
    return dataclasses.replace(
        archs.LFM2_8B_A1B, name="lfm2-8b-a1b", n_layers=6, d_model=64,
        n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128, expert_d_ff=32,
        vocab_size=512,
        pattern=archs.lfm2_pattern(archs.LFM2_LAYER_TYPES[:6], 2),
        experts_held=8, expert_offset=0, dtype=dtype)


def plan_run(monkeypatch, seed=2 ** 33 + 11, control=False):
    from bench import model_lfm2
    from bench import run as R
    cfg = small_lfm2()
    monkeypatch.setattr(model_lfm2, "configs", lambda spec: (
        cfg, model_lfm2.spec_at(cfg, spec)))
    cell, spec, traffic = R.cell_spec(LFM2)
    traffic = dict(traffic, prompt_len=8, response_len=8, search_budget=8)
    return R.run_cell(cell, spec, traffic, seed, 0.5, False,
                      jax.devices()[:1], control=control)


def test_sound_run_passes_and_control_fails(monkeypatch):
    """Judged by the limits at this size; the expert tallies count both
    phases in the traced part and the whole window."""
    res = plan_run(monkeypatch, control=True)
    rec = res["_record"]
    small = limits_at_test_size(LFM2)
    assert correct.judge(rec["readings"], small)[0], rec["readings"]
    assert not correct.judge(rec["control"], small)[0], rec["control"]
    a = rec["moe"]["assignments"]
    assert a["window"]["train"] >= a["traced"]["train"] > 0
    assert a["window"]["gen"] >= a["traced"]["gen"] > 0


def test_lfm2_reference_matches_the_program_in_float32():
    """The benchmark's own LFM2 (weights drawn in its own layout, its own
    forward) against the program's forward on the same weights mapped
    into the program's tree, both float32: the two implementations
    agree to rounding."""
    from bench import model_lfm2, model as bm, reference_lfm2 as R
    from repro.models import transformer as T
    cfg = small_lfm2("float32")
    spec = model_lfm2.spec_at(cfg, bm.load_config_file("lfm2-8b-a1b"))
    key = jax.random.PRNGKey(5)
    theta = R.weights(spec, key, jnp.float32)
    params = model_lfm2.program_tree(spec, theta)
    tok = jax.random.randint(key, (2, 20), 0, cfg.vocab_size)
    got = T.forward(params, cfg, {"tokens": tok}, remat=False)["logits"]
    trained, bias = R.split_bias(theta)
    with jax.default_matmul_precision("highest"):
        want = R.make_forward(spec)(trained, bias, tok)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    names = set(R.leaf_norms(trained))
    assert names == set(model_lfm2.flatten(model_lfm2.leaf_norms(params)))


def test_configuration_file_is_the_preset_cut():
    """The file maps onto the program's LFM2-8B-A1B preset with only the
    cut applied: six layers, 8 of the 32 experts held."""
    from bench import model_lfm2, model as bm
    from repro.configs import archs
    spec = bm.load_config_file("lfm2-8b-a1b")
    assert set(spec["reduced"]) == {"num_hidden_layers", "layer_types",
                                    "num_experts"}
    got = model_lfm2.program_config(spec)
    p = archs.LFM2_8B_A1B
    want = dataclasses.replace(p, n_layers=6, pattern=p.pattern[:6],
                               experts_held=8, source=spec["source"])
    assert got == want
    assert 6.6e8 < got.param_count() < 6.8e8


def test_flops_moe_hand_counts():
    from bench import flops_moe
    cfg = small_lfm2()
    d, f, fe, V = 64, 128, 32, 512
    # layers: conv, conv, attention, conv, conv, conv; 2 dense, 4 expert
    mixers = 5 * 4 * d * d + d * 16 * (2 * 4 + 2 * 2)
    ffn = 2 * 3 * d * f + 4 * d * 32
    assert flops_moe.token_weights(cfg) == mixers + ffn
    one = flops_moe.forward_flops(cfg, 1, 1, 1, 4)
    assert one == (2 * (mixers + ffn) + 2 * 3 * d * 5 + 4 * 4 * 16
                   + 2 * 3 * d * fe * 4 + 2 * V * d)
    fl, by = flops_moe.actor_step(cfg, 2, 3, 2, 10)
    assert fl == 3 * flops_moe.forward_flops(cfg, 6, 12, 4, 10)
    assert by == 28 * cfg.param_count()
    phases = flops_moe.generate(cfg, rows=2, prompt_len=3, steps=2,
                                assignments=40)
    assert len(phases) == 3
    w = 2 * cfg.param_count()
    kv = 2 * 2 * 16 * 2
    assert phases[0][1] == w + 6 * kv
    assert phases[2][1] == w + 2 * 5 * kv + 2 * 2 * 2 * d * 4 * 5


def test_moe_readers_from_a_record():
    """The three readers on a record as the driver and harness build it;
    nothing to read (None) where the record has no ``moe``."""
    import importlib.util
    import os
    from bench import flops_moe
    from conftest import ROOT
    cfg = small_lfm2()

    def reader(name):
        path = os.path.join(ROOT, "bench", "metrics", name + ".py")
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read
    pk = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9}
    iters = [{"gen_len": 8.0}, {"gen_len": 6.0}]
    rec = {"iters": iters, "traced_iters": iters[:1], "rows": 2,
           "prompt_len": 8, "response_len": 8, "window_s": 2.0, "chips": 1,
           "peaks": pk,
           "trace": {"modules": {"jit_actor_step": 0.5,
                                 "jit_generate": 0.25}},
           "moe": {"cfg": cfg, "assignments": {
               "traced": {"gen": 100, "train": 60},
               "window": {"gen": 200, "train": 120}}}}
    f = sum(flops_moe.rl_iteration_flops(cfg, 2, 8, 2 * it["gen_len"],
                                         100, 60) for it in iters)
    assert reader("rl_mfu.moe")(rec) == pytest.approx(100 * f / 2e12)
    fa, ba = flops_moe.actor_step(cfg, 2, 16, 8, 60)
    assert reader("actor_roofline.moe")(rec) == pytest.approx(
        100 * max(fa / 1e12, ba / 1e9) / 0.5)
    least = sum(max(a / 1e12, b / 1e9)
                for a, b in flops_moe.generate(cfg, 2, 8, 7, 100))
    assert reader("gen_roofline.moe")(rec) == pytest.approx(
        100 * least / 0.25)
    del rec["moe"]
    for name in ("rl_mfu.moe", "actor_roofline.moe", "gen_roofline.moe"):
        assert reader(name)(rec) is None
