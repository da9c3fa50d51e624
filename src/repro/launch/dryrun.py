import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax import: jax locks device count at first init.

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) and
extract memory / FLOP / collective statistics for the roofline analysis.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch phi3-medium-14b \
        --shape train_4k [--multi-pod] [--out results/dryrun]

Writes one JSON per combination; repro.launch.roofline consumes them.
"""
import argparse
import json
import re
import sys
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs import archs
from repro.configs.shapes import SHAPES, InputShape, input_specs, skip_reason
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as T
from repro.optim import adam
from repro.parallel.sharding import use_hints

_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "pred": 1,
                "s8": 1, "u8": 1, "f64": 8, "s64": 8, "u64": 8, "s16": 2,
                "u16": 2, "f8e4m3fn": 1, "f8e5m2": 1}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_bytes(shape_str: str) -> int:
    """'bf16[128,1024]' -> bytes."""
    m = re.match(r"([a-z0-9]+)\[([0-9,]*)\]", shape_str)
    if not m:
        return 0
    dt, dims = m.groups()
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dt, 4)


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Sum operand bytes of every collective op in the (S)HLO text.

    Operand shapes are resolved from each named op's definition."""
    def_shape: Dict[str, str] = {}
    defn = re.compile(r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*"
                      r"(\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^ ]*)")
    for line in hlo_text.splitlines():
        m = defn.match(line)
        if m:
            def_shape[m.group(1)] = m.group(2)
    out: Dict[str, float] = {c: 0.0 for c in _COLLECTIVES}
    op_re = re.compile(r"=\s*(\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^ ]*)\s*"
                       r"([a-z\-]+)(?:-start|-done)?\(([^)]*)\)")
    for line in hlo_text.splitlines():
        m = op_re.search(line)
        if not m:
            continue
        _, op, operands = m.groups()
        base = op
        for c in _COLLECTIVES:
            if base.startswith(c):
                base = c
                break
        else:
            continue
        if "-done" in line.split("(")[0]:
            continue  # count the -start, not the -done
        total = 0
        for operand in operands.split(","):
            name = operand.strip().lstrip("%")
            name = name.split(" ")[-1].lstrip("%")
            shape = def_shape.get(name)
            if shape:
                if shape.startswith("("):
                    for part in re.findall(r"[a-z0-9]+\[[0-9,]*\]", shape):
                        total += _shape_bytes(part)
                else:
                    total += _shape_bytes(shape)
        out[base] += total
    out["total"] = sum(out[c] for c in _COLLECTIVES)
    return out


def dryrun_one(arch: str, shape_name: str, multi_pod: bool,
               verbose: bool = True, save_hlo: Optional[str] = None) -> Dict:
    cfg = archs.get(arch)
    shape = SHAPES[shape_name]
    reason = skip_reason(cfg, shape)
    if reason:
        return {"arch": arch, "shape": shape_name,
                "multi_pod": multi_pod, "skipped": reason}

    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    params_shape = jax.eval_shape(
        lambda: T.init_params(jax.random.PRNGKey(0), cfg))
    p_shardings = steps_mod.param_shardings(mesh, params_shape)
    batch_shape = input_specs(cfg, shape)
    b_shardings = steps_mod.batch_shardings(mesh, batch_shape, shape)
    rules = steps_mod.activation_rules(mesh, shape)

    if shape.phase == "train":
        opt_cfg = adam.AdamConfig(
            moment_dtype="bfloat16" if cfg.param_count() > 1e11
            else "float32")
        opt_shape = jax.eval_shape(
            lambda: adam.init_adam_state(params_shape, opt_cfg))
        o_shardings = steps_mod.opt_shardings(mesh, params_shape)
        step = steps_mod.make_train_step(cfg, opt_cfg)
        in_sh = (p_shardings, o_shardings, b_shardings)
        out_sh = (p_shardings, o_shardings, None)
        args = (params_shape, opt_shape, batch_shape)
    elif shape.phase == "prefill":
        step = steps_mod.make_prefill_step(cfg, long_mode=shape.long_mode,
                                           max_cache_len=shape.seq_len)
        in_sh = (p_shardings, b_shardings)
        out_sh = None
        args = (params_shape, batch_shape)
    else:  # decode
        step = steps_mod.make_serve_step(cfg, long_mode=shape.long_mode)
        cache_sh = b_shardings["cache"]
        in_sh = (p_shardings, b_shardings)
        out_sh = (None, cache_sh)
        args = (params_shape, batch_shape)

    donate = (1,) if shape.phase == "decode" else ()
    with mesh, use_hints(rules):
        jitted = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=donate)
        lowered = jitted.lower(*args)
        compiled = lowered.compile()

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    n_dev = mesh.devices.size
    if save_hlo:
        import zstandard
        os.makedirs(save_hlo, exist_ok=True)
        tag = f"{arch}__{shape_name}__{'multi' if multi_pod else 'single'}"
        with open(os.path.join(save_hlo, tag + ".hlo.zst"), "wb") as f:
            f.write(zstandard.ZstdCompressor(level=9)
                    .compress(hlo.encode()))

    # loop-aware per-device analysis (XLA cost_analysis counts while
    # bodies once; see hlo_analysis module docstring)
    from repro.launch import hlo_analysis
    la = hlo_analysis.analyze(hlo)

    result = {
        "arch": arch, "shape": shape_name, "multi_pod": multi_pod,
        "n_devices": int(n_dev),
        "compile_s": round(time.time() - t0, 1),
        "flops_xla_bodies_once": float(cost.get("flops", 0.0)),
        "flops_per_device": float(la.flops),
        "bytes_per_device": float(la.bytes),
        "collective_bytes_per_device": {k: float(v)
                                        for k, v in la.collectives.items()},
        "collective_bytes_bodies_once": {k: float(v) for k, v in coll.items()},
        "params": int(cfg.param_count()),
        "active_params": int(cfg.active_param_count()),
        "seq_len": shape.seq_len, "global_batch": shape.global_batch,
        "phase": shape.phase,
    }
    if mem is not None:
        for attr in ("temp_size_in_bytes", "argument_size_in_bytes",
                     "output_size_in_bytes", "generated_code_size_in_bytes",
                     "alias_size_in_bytes"):
            v = getattr(mem, attr, None)
            if v is not None:
                result[attr] = int(v)
    if verbose:
        print(json.dumps(result, indent=2))
        print(f"memory_analysis: {mem}")
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True, choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--no-save-hlo", action="store_true")
    args = ap.parse_args()

    result = dryrun_one(args.arch, args.shape, args.multi_pod,
                        save_hlo=None if args.no_save_hlo else args.out)
    os.makedirs(args.out, exist_ok=True)
    tag = f"{args.arch}__{args.shape}__{'multi' if args.multi_pod else 'single'}"
    with open(os.path.join(args.out, tag + ".json"), "w") as f:
        json.dump(result, f, indent=2)
    print(f"wrote {args.out}/{tag}.json")
    if "skipped" in result:
        print(f"SKIP: {result['skipped']}")


if __name__ == "__main__":
    main()
