"""End-to-end sharded execution smoke: run examples/sharded_exec.py (GRPO
and PPO) in a subprocess with 8 forced host devices (the parent's jax is
already initialized with this process's device count, so the
multi-device run needs its own interpreter) and check its
machine-readable summary."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLE = os.path.join(REPO, "examples", "sharded_exec.py")


@pytest.mark.parametrize("algorithm", ["grpo", "ppo"])
def test_sharded_exec_example(algorithm):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, EXAMPLE, "--iters", "3", "--algorithm", algorithm,
         "--json"],
        capture_output=True, text=True, timeout=900, env=env, cwd=REPO)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    summary = json.loads(r.stdout.strip().splitlines()[-1])
    assert summary["devices"] >= 8
    assert summary["folding_collisions"] == 0
    assert summary["overlap_active"] is True
    assert summary["tokens_identical"] is True
    assert summary["train_mesh"] == [2, 2]
    assert set(summary["gen_devices"]).isdisjoint(summary["train_devices"])
    assert summary["overlap_honest"] == 1.0
    assert summary["algorithm"] == algorithm
    # every model program is lowered on the meshes under its own name
    assert set(summary["programs"]) <= set(summary["lowered_modules"])
    assert len(summary["programs"]) == (5 if algorithm == "ppo" else 3)
