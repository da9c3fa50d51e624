"""Compile the main path for a described (not attached) TPU v5e chip.

The TPU compiler refuses what interpret mode accepts: kernel blocks off
the (8, 128) tiling, and programs that do not fit the chip's memory.
These tests compile the attention kernels at real widths and the
full-width qwen3-0.6b actor step from shapes alone; nothing runs.  The
topology is described inside a fixture (never at import) so that only
the worker running this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import archs
from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.flash_attention.ops import flash_attention
from repro.models import transformer as T
from repro.optim import adam
from repro.rl.trainer import RLConfig, make_actor_step

HBM_BYTES = 16 * 2 ** 30      # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    prev_log = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip can be written to the persistent
    # cache but never read back here: keep it out of the cache
    prev_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", prev_cache)
        if prev_log is None:
            os.environ.pop("TPU_LOG_DIR", None)


def _shape(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_flash_attention_compiles(one_chip):
    q = _shape(one_chip, (8, 2048, 16, 128))
    kv = _shape(one_chip, (8, 2048, 8, 128))
    compiled = _compile(
        lambda q, k, v: flash_attention(q, k, v, causal=True,
                                        interpret=False), q, kv, kv)
    assert "tpu_custom_call" in compiled.as_text()


def test_decode_attention_compiles(one_chip):
    q = _shape(one_chip, (32, 16, 128))
    kv = _shape(one_chip, (32, 2048, 8, 128))
    bias = _shape(one_chip, (32, 2048), jnp.float32)
    compiled = _compile(
        lambda q, k, v, b: decode_attention(q, k, v, b, interpret=False),
        q, kv, kv, bias)
    assert "tpu_custom_call" in compiled.as_text()


def test_qwen3_actor_step_fits_one_chip(one_chip):
    """The GRPO actor step at the published width, over the batch the
    one-chip bring-up trains on (16 prompts x 4 rollouts; 5 prompt and 3
    generated tokens), with the reference copy it lives beside."""
    cfg = archs.get("qwen3-0.6b")
    assert cfg.dtype == "bfloat16" and cfg.vocab_size == 151936
    on_chip = lambda tree: jax.tree_util.tree_map(      # noqa: E731
        lambda x: _shape(one_chip, x.shape, x.dtype), tree)
    params = jax.eval_shape(lambda k: T.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    opt = jax.eval_shape(
        lambda p: adam.init_adam_state(p, adam.AdamConfig()), params)
    B, P, G = 64, 5, 3
    batch = {"sequences": _shape(one_chip, (B, P + G), jnp.int32),
             "logp_old": _shape(one_chip, (B, G), jnp.float32),
             "advantages": _shape(one_chip, (B, G), jnp.float32),
             "mask": _shape(one_chip, (B, G), jnp.float32)}
    step = make_actor_step(cfg, RLConfig())
    compiled = jax.jit(step, static_argnums=(3,)).lower(
        on_chip(params), on_chip(opt), batch, P).compile()
    mem = compiled.memory_analysis()
    ref_bytes = sum(x.size * x.dtype.itemsize
                    for x in jax.tree_util.tree_leaves(params))
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes + ref_bytes)
    assert total < HBM_BYTES, (
        f"args {mem.argument_size_in_bytes / 1e9:.2f} GB + out "
        f"{mem.output_size_in_bytes / 1e9:.2f} GB + temp "
        f"{mem.temp_size_in_bytes / 1e9:.2f} GB + ref "
        f"{ref_bytes / 1e9:.2f} GB >= {HBM_BYTES / 1e9:.2f} GB")
