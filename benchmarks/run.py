"""Run every benchmark (one per paper figure) + the roofline table.

    PYTHONPATH=src python -m benchmarks.run            # quick budgets
    BENCH_QUICK=0 PYTHONPATH=src python -m benchmarks.run   # full sweep
    PYTHONPATH=src python -m benchmarks.run --only genserve_throughput

Every run (filtered or not) rewrites ``results/summary.json``: per-bench
status/timing for the benchmarks that ran, merged over the previous
summary, plus a copy of every per-bench ``results/*.json`` payload — one
file from which CI and local runs can diff the whole perf trajectory.
"""
from __future__ import annotations

import argparse
import datetime
import json
import os
import subprocess
import sys
import time
import traceback


def run_metadata() -> dict:
    """Provenance stamped onto every summary entry: git SHA, UTC
    timestamp, JAX backend and device kind.  Each probe degrades to a
    placeholder rather than failing the run (results must be writable
    from a detached checkout or a backend-less box)."""
    meta = {"git_sha": "unknown", "timestamp":
            datetime.datetime.now(datetime.timezone.utc)
            .isoformat(timespec="seconds")}
    try:
        meta["git_sha"] = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        ).stdout.strip() or "unknown"
    except (OSError, subprocess.SubprocessError):
        pass
    try:
        import jax
        meta["backend"] = jax.default_backend()
        devs = jax.devices()
        meta["device_kind"] = devs[0].device_kind if devs else "none"
        meta["device_count"] = len(devs)
    except Exception:
        meta["backend"] = "unavailable"
    return meta


def write_summary(statuses: dict) -> str:
    """Merge `statuses` into results/summary.json together with every
    per-bench results/*.json payload."""
    os.makedirs("results", exist_ok=True)
    path = os.path.join("results", "summary.json")
    summary = {"benchmarks": {}, "results": {}}
    if os.path.exists(path):
        try:
            with open(path) as f:
                summary = json.load(f)
        except (json.JSONDecodeError, OSError):
            pass
    summary.setdefault("benchmarks", {}).update(statuses)
    results = {}
    for fname in sorted(os.listdir("results")):
        if fname == "summary.json" or not fname.endswith(".json"):
            continue
        try:
            with open(os.path.join("results", fname)) as f:
                results[fname[:-len(".json")]] = json.load(f)
        except (json.JSONDecodeError, OSError):
            continue
    summary["results"] = results
    with open(path, "w") as f:
        json.dump(summary, f, indent=2)
    return path


def main() -> None:
    sys.path.insert(0, "src")
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, metavar="NAME",
                    help="run only benchmarks whose name contains NAME "
                         "(e.g. genserve_throughput, fig3)")
    ap.add_argument("--seed", type=int, default=0,
                    help="base RNG seed benchmarks fold into their "
                         "generators (exported as BENCH_SEED; stamped "
                         "into every summary entry)")
    args = ap.parse_args()
    # must land before the benchmark modules import benchmarks.common
    os.environ["BENCH_SEED"] = str(args.seed)

    from benchmarks import (elastic_redeploy, engine_throughput,
                            fault_recovery, fig3_e2e,
                            fig4_loadbalance, fig5_search_efficiency,
                            fig6_small_scale_ilp, fig7_costmodel_validation,
                            fig8_training_quality, fig10_heterogeneity,
                            genserve_throughput, obs_overhead)
    benches = [
        ("engine_throughput", "plan-driven engine, measured vs predicted",
         engine_throughput.run),
        ("elastic_redeploy", "§6 throughput recovery vs degraded incumbent",
         elastic_redeploy.run),
        ("fault_recovery",
         "injected faults: detection latency, recovery time, goodput",
         fault_recovery.run),
        ("obs_overhead", "span-tracing overhead + cost-model calibration",
         obs_overhead.run),
        ("genserve_throughput",
         "continuous batching vs single-wave decode; chunked admission; "
         "paged KV + prefix reuse; speculative decoding",
         genserve_throughput.run),
        ("fig3_e2e", "Figure 3: end-to-end throughput", fig3_e2e.run),
        ("fig4_loadbalance", "Figure 4: LB ablation", fig4_loadbalance.run),
        ("fig5_search_efficiency", "Figure 5", fig5_search_efficiency.run),
        ("fig6_small_scale_ilp", "Figure 6", fig6_small_scale_ilp.run),
        ("fig7_costmodel_validation", "Figure 7",
         fig7_costmodel_validation.run),
        ("fig8_training_quality", "Figures 8/9: sync vs async quality",
         fig8_training_quality.run),
        ("fig10_heterogeneity", "Figure 10", fig10_heterogeneity.run),
    ]
    if args.only:
        benches = [b for b in benches if args.only in b[0]]
        if not benches:
            raise SystemExit(f"--only {args.only!r} matches no benchmark")

    meta = run_metadata()
    meta["seed"] = args.seed
    failures = []
    statuses = {}
    for name, desc, fn in benches:
        print(f"\n==== {name} ({desc}) ====", flush=True)
        t0 = time.monotonic()
        try:
            fn()
            statuses[name] = {"ok": True}
        except Exception:
            traceback.print_exc()
            failures.append(name)
            statuses[name] = {"ok": False}
        statuses[name]["seconds"] = round(time.monotonic() - t0, 2)
        statuses[name].update(meta)
        print(f"({statuses[name]['seconds']:.0f}s)", flush=True)

    if not args.only:
        # roofline table from whatever dry-run results exist so far
        print("\n==== roofline (from results/dryrun) ====", flush=True)
        try:
            from repro.launch.roofline import table
            if os.path.isdir("results/dryrun"):
                print(table("results/dryrun"))
            else:
                print("no dry-run results yet; run repro.launch.dryrun_all")
        except Exception:
            traceback.print_exc()
            failures.append("roofline")

    path = write_summary(statuses)
    print(f"\nwrote {path}")
    if failures:
        print(f"FAILED: {failures}")
        raise SystemExit(1)
    print("all benchmarks complete")


if __name__ == "__main__":
    main()
