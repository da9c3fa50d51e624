"""parallel.sharding + engine.placement unit tests.

Edge cases the sharded execution path leans on: spec sanitization for
non-dividing dims and tuple axis entries, stacked-block param specs,
``hint`` as identity outside a mesh/rules context, and group-aware
device folding (injective when the plan fits, collision-reported d%L
when oversubscribed).
"""
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import enumerate as enum_mod, topology, workflow
from repro.engine import placement as placement_mod
from repro.models.config import ModelConfig
from repro.parallel import sharding as sh


def fake_mesh(shape_by_axis):
    """Duck-typed mesh: sanitize_spec only reads axis_names/devices.shape."""
    names = tuple(shape_by_axis)
    return SimpleNamespace(
        axis_names=names,
        devices=np.empty(tuple(shape_by_axis[n] for n in names)))


# -- sanitize_spec ---------------------------------------------------------

def test_sanitize_spec_non_dividing_axis_replicates():
    mesh = fake_mesh({"data": 4, "model": 2})
    # dim 0 = 6 not divisible by data=4 -> replicated; dim 1 = 8 by 2 ok
    assert sh.sanitize_spec(P("data", "model"), (6, 8), mesh) \
        == P(None, "model")
    # both divide -> untouched
    assert sh.sanitize_spec(P("data", "model"), (8, 8), mesh) \
        == P("data", "model")


def test_sanitize_spec_tuple_entry_keeps_dividing_prefix():
    mesh = fake_mesh({"data": 4, "model": 2})
    # ("data","model") wants 8-way: dim 8 keeps both, dim 4 keeps only
    # data, dim 2 keeps... data=4 does not divide 2 -> drops, then model
    # alone is not attempted past a dropped prefix member (greedy prefix)
    assert sh.sanitize_spec(P(("data", "model"),), (8,), mesh) \
        == P(("data", "model"))
    assert sh.sanitize_spec(P(("data", "model"),), (4,), mesh) == P("data")
    assert sh.sanitize_spec(P(("data", "model"),), (3,), mesh) == P(None)


def test_sanitize_spec_trims_to_rank():
    mesh = fake_mesh({"data": 2, "model": 2})
    # spec longer than the shape's rank is trimmed, not an error
    assert sh.sanitize_spec(P("data", None, "model"), (4, 4), mesh) \
        == P("data", None)


# -- param_tree_specs ------------------------------------------------------

def tiny_cfg():
    from repro.data.synthetic import VOCAB_SIZE
    return ModelConfig(name="shard-tiny", n_layers=2, d_model=64,
                       n_heads=2, n_kv_heads=2, head_dim=32, d_ff=128,
                       vocab_size=VOCAB_SIZE, dtype="float32")


def test_param_tree_specs_stacked_blocks():
    from repro.models import transformer as T
    params = T.init_params(jax.random.PRNGKey(0), tiny_cfg())
    specs = sh.param_tree_specs(params)
    flat = {sh.path_str(p): (leaf, spec) for (p, leaf), (_, spec) in zip(
        jax.tree_util.tree_flatten_with_path(params)[0],
        jax.tree_util.tree_flatten_with_path(specs)[0])}
    saw_stacked = False
    for path, (leaf, spec) in flat.items():
        assert len(spec) <= leaf.ndim, (path, spec, leaf.shape)
        if path.startswith("blocks/"):
            saw_stacked = True
            # stacked scan-over-blocks leaves: leading repeat dim is
            # never sharded
            assert len(spec) == 0 or spec[0] is None, (path, spec)
    assert saw_stacked
    # a known TP rule applies under the stacked prefix
    wq = next(v for k, v in flat.items() if k.endswith("wq"))
    assert tuple(wq[1]) == (None, "data", "model")


def test_param_specs_jit_roundtrip_on_mesh():
    """Sanitized specs are accepted by device_put + jit in_shardings."""
    if jax.device_count() < 2:
        pytest.skip("needs >= 2 devices")
    from jax.sharding import Mesh
    from repro.models import transformer as T
    params = T.init_params(jax.random.PRNGKey(0), tiny_cfg())
    n = 2
    mesh = Mesh(np.array(jax.devices()[:n]).reshape(1, n),
                ("data", "model"))
    shardings = sh.named_shardings(mesh, sh.param_tree_specs(params),
                                   params)
    committed = jax.device_put(params, shardings)
    out = jax.jit(lambda p: jax.tree_util.tree_map(jnp.sum, p),
                  in_shardings=(shardings,))(committed)
    ref = jax.tree_util.tree_map(jnp.sum, params)
    for a, b in zip(jax.tree_util.tree_leaves(out),
                    jax.tree_util.tree_leaves(ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5)


_MOE_ON_MESH = """
import jax, jax.numpy as jnp, numpy as np
from dataclasses import replace
from jax.sharding import Mesh
from repro.configs import archs
from repro.models import transformer as T
from repro.parallel import sharding as sh
for name in ("granite-moe-3b-a800m", "lfm2-8b-a1b"):
    cfg = replace(archs.get(name, smoke=True), dtype="float32")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    tok = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                             cfg.vocab_size)
    def loss(p):
        out = T.forward(p, cfg, {"tokens": tok}, remat=False)
        return jnp.mean(jnp.square(out["logits"]))
    want, gw = jax.jit(jax.value_and_grad(loss))(params)
    mesh = Mesh(np.array(jax.devices()).reshape(2, 2), ("data", "model"))
    placed = jax.device_put(params, sh.named_shardings(
        mesh, sh.param_tree_specs(params), params))
    with mesh, sh.use_hints(sh.default_activation_rules()):
        got, gg = jax.jit(jax.value_and_grad(loss))(placed)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(gg),
                    jax.tree_util.tree_leaves(gw)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=1e-6)
print("OK")
"""


def test_grouped_moe_on_a_sharded_mesh_matches_one_device():
    """The dropless expert layer (sorted assignments, ``ragged_dot``) has
    no activation hints of its own: on a 2x2 data/model mesh its
    placement comes from the parameter rules (experts over data, their
    width over model) and the compiler's propagation.  Loss and
    gradients there equal one device's (float32; rtol 2e-4 covers the
    sharded reductions' order).  Four forced host devices need their
    own interpreter."""
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(repo, "src"))
    r = subprocess.run([sys.executable, "-c", _MOE_ON_MESH],
                       capture_output=True, text=True, timeout=600,
                       env=env, cwd=repo)
    assert r.returncode == 0 and r.stdout.strip().endswith("OK"), r.stderr


# -- hint ------------------------------------------------------------------

def test_hint_identity_outside_rules():
    x = jnp.arange(8.0).reshape(2, 4)
    y = sh.hint(x, "residual")
    assert y is x


def test_hint_identity_for_unknown_name():
    x = jnp.arange(4.0)
    with sh.use_hints({"residual": P("data")}):
        assert sh.hint(x, "not-a-rule") is x


def test_use_hints_restores_previous_rules():
    with sh.use_hints({"a": P()}):
        with sh.use_hints({"b": P()}):
            pass
        x = jnp.ones(3)
        assert sh.hint(x, "b") is x  # inner rules popped


# -- fold_plan / fold_devices ---------------------------------------------

def gen_rest_plan(n_devices=8):
    cfg = tiny_cfg()
    topo = topology.build_testbed("single_region",
                                  counts={"A100": n_devices // 2,
                                          "L4": n_devices // 2})
    spec = workflow.LLMSpec.from_model_config(cfg)
    wf = workflow.make_workflow("grpo", spec, synchronous=True,
                                n_rollouts=4, seq_in=8, seq_out=4,
                                global_batch=1)
    grouping = next(g for g in enum_mod.priority_groupings(wf)
                    if len(g) == 2 and any(
                        wf.task(t).kind == workflow.TaskKind.GEN
                        for t in min(g, key=len)))
    sizes = enum_mod.proportional_sizes(wf, grouping, topo.n)
    plan = enum_mod.build_plan(topo, wf, grouping, sizes,
                               list(range(topo.n)))
    return wf, plan


def test_fold_plan_injective_when_plan_fits():
    wf, plan = gen_rest_plan()
    local = [f"dev{i}" for i in range(8)]
    f = placement_mod.fold_plan(plan, local)
    assert not f.oversubscribed
    assert f.n_collisions == 0
    # injective: distinct plan ids -> distinct local indices
    assert len(set(f.mapping.values())) == len(f.mapping)
    # plan over ids 0..7 on 8 local devices folds to the identity
    assert f.mapping == {i: i for i in range(8)}


def test_fold_plan_disjoint_groups_stay_disjoint():
    wf, plan = gen_rest_plan()
    local = [f"dev{i}" for i in range(8)]
    f = placement_mod.fold_plan(plan, local)
    locals_of = [
        {f.mapping[int(d)] for d in g.devices} for g in plan.groups]
    assert locals_of[0].isdisjoint(locals_of[1])


def test_fold_plan_rank_maps_sparse_ids():
    """Non-contiguous plan ids still fold injectively when they fit."""
    wf, plan = gen_rest_plan()
    # remap plan device ids 0..7 -> sparse ids (x*3 + 1)
    import dataclasses as dc
    remap = {i: i * 3 + 1 for i in range(8)}
    groups = tuple(
        dc.replace(g, devices=tuple(remap[int(d)] for d in g.devices))
        for g in plan.groups)
    assignment = {t: np.vectorize(remap.get)(a)
                  for t, a in plan.assignment.items()}
    sparse = dc.replace(plan, groups=groups, assignment=assignment)
    local = [f"dev{i}" for i in range(8)]
    f = placement_mod.fold_plan(sparse, local)
    assert not f.oversubscribed and f.n_collisions == 0
    # rank order: i-th smallest id -> local index i
    assert f.mapping == {i * 3 + 1: i for i in range(8)}


def test_fold_plan_oversubscribed_reports_collisions():
    wf, plan = gen_rest_plan()
    local = ["devA", "devB", "devC"]   # 8 plan ids on 3 real devices
    f = placement_mod.fold_plan(plan, local)
    assert f.oversubscribed
    # d % 3 folds ids from both groups onto shared devices
    assert f.n_collisions > 0
    for li, gis in f.collisions:
        assert 0 <= li < 3
        assert len(gis) >= 2
    assert f.colliding_groups  # some group flagged
    # deterministic
    f2 = placement_mod.fold_plan(plan, local)
    assert f2.mapping == f.mapping and f2.collisions == f.collisions


def test_fold_devices_legacy_vs_mapping():
    local = ["devA", "devB", "devC"]
    # legacy (no mapping): d % L
    assert placement_mod.fold_devices([5, 2], local) == ["devC"]
    # group-aware mapping overrides
    mapping = {5: 0, 2: 1}
    assert placement_mod.fold_devices([5, 2], local, mapping) \
        == ["devA", "devB"]


def test_build_placement_collision_flag():
    wf, plan = gen_rest_plan()
    local = ["devA", "devB", "devC"]
    folding = placement_mod.fold_plan(plan, local)
    pls = {t: placement_mod.build_placement(plan, t, local, folding)
           for t in range(wf.n_tasks)}
    # oversubscribed 3-device host: at least one task's group collides
    assert any(pl.collision for pl in pls.values())
    # and the mesh never exceeds the distinct folded devices
    for pl in pls.values():
        assert int(np.prod(pl.mesh_shape)) == len(pl.local_devices)
        assert pl.tp_eff == pl.mesh_shape[1]


def test_build_placement_full_host_no_collision():
    wf, plan = gen_rest_plan()
    local = [f"dev{i}" for i in range(8)]
    folding = placement_mod.fold_plan(plan, local)
    pls = {t: placement_mod.build_placement(plan, t, local, folding)
           for t in range(wf.n_tasks)}
    assert all(not pl.collision for pl in pls.values())
    gen_t = next(t for t in range(wf.n_tasks)
                 if wf.task(t).kind == workflow.TaskKind.GEN)
    other = [t for t in range(wf.n_tasks) if t != gen_t]
    gen_set = set(pls[gen_t].local_devices)
    for t in other:
        if plan.group_of(t) is not plan.group_of(gen_t):
            assert gen_set.isdisjoint(pls[t].local_devices)
