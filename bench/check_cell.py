"""Readings of sound runs, of the control and of planted faults, for
setting a cell's limits, in one process.

    python3 bench/check_cell.py --workload <cell> \
        --runs 11@20,12@20,13:bias_ignored@1,14:trace@20

Each run is ``seed[:what][@seconds]`` (``--seconds`` where no ``@``).
``what`` is nothing (a sound run, with the control: the reference
computed in float8 in the program's place), ``trace`` (a traced run that
reads the cell's per-layer metrics) or the name of a fault of
``bench/faults.py`` or ``bench/faults_lfm2.py``, planted for that run
alone.  One JSON line per run; the last line gives, per number, the
largest reading of the sound runs and the smallest of the control.
Needs the chips the cell asks for.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(
    __file__)))]

from bench import run as R  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--runs", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args()
    sys.path.insert(0, os.path.join(R.ROOT, "src"))
    cell, spec, traffic = R.cell_spec(args.workload)
    jax = R.setup_jax()
    if jax.default_backend() != "tpu" or len(jax.devices()) < cell["chips"]:
        print("needs a TPU with the cell's chips", file=sys.stderr)
        return 3
    from bench import faults, faults_lfm2
    planted = {**faults.FAULTS, **faults_lfm2.FAULTS}
    runs, ctl = {}, {}
    for run in args.runs.split(","):
        run, _, secs = run.partition("@")
        seed, _, what = run.partition(":")
        undo = []
        if what in planted:
            planted[what](lambda o, n, v: (undo.append((o, n, getattr(o, n))),
                                           setattr(o, n, v)))
        try:
            res = R.run_cell(cell, spec, traffic, int(seed),
                             float(secs or args.seconds), what == "trace",
                             jax.devices()[:cell["chips"]],
                             control=not what)
        finally:
            for o, n, v in undo:
                setattr(o, n, v)
        rec = res["_record"]
        print(json.dumps({"seed": int(seed), "what": what or "sound",
                          "correct": res["correct"],
                          "readings": rec["readings"],
                          "control": rec.get("control"),
                          "memory_peak_bytes": rec["memory_peak_bytes"],
                          "metrics": res["metrics"],
                          "device": res["device"],
                          "breakdown": res.get("breakdown")}), flush=True)
        if not what:
            for k, v in rec["readings"].items():
                runs[k] = max(runs.get(k, 0.0), v)
            for k, v in (rec.get("control") or {}).items():
                ctl[k] = min(ctl.get(k, float("inf")), v)
    print(json.dumps({"largest_reading": runs, "smallest_control": ctl}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
