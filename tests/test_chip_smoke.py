"""CPU rehearsal of chip_smoke.py: its phases at the reduced (smoke)
config with the kernels in interpret mode, its refusal of a non-TPU
platform, the four-chip phase on four virtual CPU devices, and the
compile-cache path rule."""
import json
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke                                       # noqa: E402
from repro.launch import compile_cache                  # noqa: E402


@pytest.mark.parametrize("phase", ["rl", "serve", "kernels"])
def test_phase_at_smoke_size(phase):
    if phase == "rl":
        out = chip_smoke.phase_rl(smoke=True)
        assert out["rollouts"] == chip_smoke.RL_BATCH * chip_smoke.N_ROLLOUTS
        assert out["gen_wave"] < out["rollouts"]     # through genserve
        assert len(out["loss"]) == chip_smoke.RL_STEPS
    elif phase == "serve":
        out = chip_smoke.phase_serve(smoke=True)
        assert out["requests"] == chip_smoke.SERVE["batch"]
        assert out["tokens"] == (chip_smoke.SERVE["batch"]
                                 * chip_smoke.SERVE["new_tokens"])
        assert out["engine"] == "genserve" and out["prefix_hit_rate"] > 0
    else:
        out = chip_smoke.phase_kernels(smoke=True, interpret=True)
        assert out["mode"] == "interpret"
        for name in ("flash", "decode"):
            assert out[name]["max_abs_err"] <= chip_smoke.KERNEL_TOL["atol"]


def test_main_refuses_a_non_tpu_platform(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main([]) != 0
    captured = capsys.readouterr()
    assert '"ok"' not in captured.out
    assert "no TPU found" in captured.err


def test_four_chip_phase_on_virtual_devices():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    code = ("import json, chip_smoke; "
            "print(json.dumps(chip_smoke.phase_four_chips(smoke=True)))")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env, cwd=REPO)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["collisions"] == 0
    assert out["gen_chips"] == [0, 1] and out["train_chips"] == [2, 3]
    assert out["meshes"] == {"gen": [1, 2], "train": [1, 2]}
    assert out["overlap_active"] is True
    assert out["loss_abs_diff"] <= chip_smoke.LOSS_ATOL
    assert out["greedy_identical_share"] >= chip_smoke.MIN_GREEDY_SHARE


@pytest.mark.parametrize("environ,expected", [
    ({"JAX_COMPILATION_CACHE_DIR": "/somewhere/cache"}, "/somewhere/cache"),
    ({}, os.path.join(REPO, ".jax_cache")),
], ids=["env-set", "env-unset"])
def test_compile_cache_dir_rule(environ, expected):
    assert compile_cache.cache_dir(environ) == expected


def test_compile_cache_leaves_a_set_directory_to_jax(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == "/somewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before
