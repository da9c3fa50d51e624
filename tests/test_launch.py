"""Launch-layer tests: host-mesh training, sharding specs, input specs,
skip rules, HLO analyzer (on a small local program)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import archs
from repro.configs.shapes import SHAPES, input_specs, skip_reason
from repro.launch import hlo_analysis, steps as steps_mod
from repro.launch.mesh import make_host_mesh
from repro.models import transformer as T
from repro.optim import adam
from repro.parallel import sharding as sh


def test_host_mesh_train_step_runs():
    cfg = archs.get("qwen3-0.6b", smoke=True)
    mesh = make_host_mesh()
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    opt_cfg = adam.AdamConfig()
    opt = adam.init_adam_state(params, opt_cfg)
    step = jax.jit(steps_mod.make_train_step(cfg, opt_cfg))
    B, S = 2, 32
    batch = {
        "tokens": jnp.zeros((B, S), jnp.int32),
        "labels": jnp.zeros((B, S), jnp.int32),
        "loss_mask": jnp.ones((B, S), jnp.float32),
    }
    with mesh:
        _, _, m = step(params, opt, batch)
    assert bool(jnp.isfinite(m["loss"]))


def test_param_specs_cover_all_leaves():
    cfg = archs.get("jamba-1.5-large-398b", smoke=True)
    params = jax.eval_shape(
        lambda: T.init_params(jax.random.PRNGKey(0), cfg))
    specs = sh.param_tree_specs(params)
    n_params = len(jax.tree_util.tree_leaves(params))
    n_specs = len(jax.tree_util.tree_leaves(
        specs, is_leaf=lambda s: isinstance(s, P)))
    assert n_params == n_specs
    # blocks leaves carry the leading stacked dim as None
    flat = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda s: isinstance(s, P))[0]
    for path, spec in flat:
        if sh.path_str(path).startswith("blocks/"):
            assert list(spec)[0] is None


def test_skip_rules():
    rules = {
        ("hubert-xlarge", "decode_32k"): True,
        ("hubert-xlarge", "long_500k"): True,
        ("phi3-medium-14b", "long_500k"): True,
        ("pixtral-12b", "long_500k"): True,
        ("qwen3-0.6b", "long_500k"): True,
        ("nemotron-4-15b", "long_500k"): True,
        ("granite-moe-3b-a800m", "long_500k"): True,
        ("mixtral-8x7b", "long_500k"): False,   # native SWA
        ("gemma2-27b", "long_500k"): False,     # long-mode window
        ("rwkv6-3b", "long_500k"): False,
        ("jamba-1.5-large-398b", "long_500k"): False,
        ("phi3-medium-14b", "train_4k"): False,
        ("lfm2-8b-a1b", "long_500k"): True,     # full attention layers
        ("lfm2-8b-a1b", "train_4k"): False,
    }
    for (arch, shape), should_skip in rules.items():
        reason = skip_reason(archs.get(arch), SHAPES[shape])
        assert (reason is not None) == should_skip, \
            f"{arch}/{shape}: {reason}"
    # total runnable pairs: 36 of 44
    runnable = sum(1 for a in archs.ARCHS for s in SHAPES
                   if skip_reason(archs.get(a), SHAPES[s]) is None)
    assert runnable == 36


def test_input_specs_match_real_batches():
    """ShapeDtypeStructs must be consumable by the real step functions
    (verified structurally on the smoke config)."""
    cfg = archs.get("mixtral-8x7b")
    specs = input_specs(cfg, SHAPES["train_4k"])
    assert specs["tokens"].shape == (256, 4096)
    assert specs["labels"].shape == (256, 4096)
    dspecs = input_specs(cfg, SHAPES["decode_32k"])
    assert dspecs["tokens"].shape == (128, 1)
    kv = dspecs["cache"]["blocks"]["layer0"]["k"]
    # mixtral SWA: ring cache bounded by the 4096 window
    assert kv.shape[2] == 4096
    long = input_specs(cfg, SHAPES["long_500k"])
    assert long["cache"]["blocks"]["layer0"]["k"].shape[2] == 4096


def test_sanitize_spec_examples():
    class FakeMesh:
        axis_names = ("data", "model")

        class devices:
            shape = (16, 16)
    # 40 heads not divisible by 16 -> dropped
    assert sh.sanitize_spec(P(None, "model"), (10, 40), FakeMesh) \
        == P(None, None)
    assert sh.sanitize_spec(P("data", "model"), (64, 32), FakeMesh) \
        == P("data", "model")
    assert sh.sanitize_spec(P(("data", "model"),), (64,), FakeMesh) \
        == P("data")


def test_hlo_analysis_counts_loops():
    """A scanned matmul must count trip_count * per-iteration flops."""
    R, M = 7, 64

    def f(x, w):
        def body(h, wi):
            return h @ wi, None
        h, _ = jax.lax.scan(body, x, w)
        return h

    x = jnp.ones((M, M))
    w = jnp.ones((R, M, M))
    compiled = jax.jit(f).lower(x, w).compile()
    cost = hlo_analysis.analyze(compiled.as_text())
    expected = 2 * M * M * M * R
    assert expected * 0.9 <= cost.flops <= expected * 1.6, \
        f"flops={cost.flops:.3e} expected~{expected:.3e}"


def test_roofline_terms():
    from repro.launch.roofline import roofline_terms
    rec = {
        "n_devices": 256, "phase": "train", "seq_len": 4096,
        "global_batch": 256, "active_params": int(1e9),
        "flops_per_device": 1e13, "bytes_per_device": 1e11,
        "collective_bytes_per_device": {"all-reduce": 5e9},
    }
    t = roofline_terms(rec)
    assert t["dominant"] in ("compute", "memory", "collective")
    assert t["compute_s"] > 0 and t["memory_s"] > 0
    assert 0 < t["useful_ratio"] < 10


def test_hlo_profile_and_slice_accounting():
    """hlo_profile attributes loop-aware contributions; fused
    dynamic-slice params charge slice bytes, not the full operand."""
    import jax
    import jax.numpy as jnp
    from repro.launch.hlo_profile import op_contributions

    R, M = 64, 32

    def f(x, w):
        def body(h, wi):
            return jnp.tanh(h @ wi), None
        h, _ = jax.lax.scan(body, x, w)
        return h

    x = jnp.ones((M, M))
    w = jnp.ones((R, M, M))
    hlo = jax.jit(f).lower(x, w).compile().as_text()
    rows = op_contributions(hlo)
    flops = sum(r[0] for r in rows)
    expected = (2 * M ** 3 + M * M) * R
    assert expected * 0.9 <= flops <= expected * 1.8
    # bytes must NOT scale as (full stacked weight) x (iterations): the
    # fused dynamic-slice rule charges only the per-iteration slice
    total_bytes = sum(r[1] for r in rows)
    tile = M * M * 4
    honest_per_iter = 12 * tile          # h in/out + w slice + chain slack
    overcount = R * R * tile             # full stack read per iteration
    assert total_bytes < min(R * honest_per_iter, overcount // 2)


def test_loadbalance_guard_never_regresses():
    from repro.core import enumerate as enum_mod, loadbalance, topology, \
        workflow
    from repro.core.costmodel import CostModel
    topo = topology.build_testbed("multi_continent")
    wf = workflow.make_ppo(workflow.QWEN_4B)
    grouping = (tuple(range(wf.n_tasks)),)
    plan = enum_mod.build_plan(topo, wf, grouping, [topo.n],
                               list(range(topo.n)))
    cm = CostModel(topo, wf)
    assert cm.cost(loadbalance.balance(topo, wf, plan)) \
        <= cm.cost(plan) * (1 + 1e-9)
