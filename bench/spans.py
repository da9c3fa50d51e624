"""Where an RL iteration's time goes, by the program's own spans.

    python3 bench/spans.py --workload <cell> --seed <n> --seconds <s>

runs an RL cell as ``bench/run.py --trace 1`` does, with the program's
span tracer (``repro.obs.trace``) on for the whole run, and prints one
JSON line about the traced part of the window: each span's time per
iteration and its self time, what lies between iterations, the device's
idle time put down to the innermost program span over each gap, and the
share of the window in which the device is idle and no ``task.*`` span
is open.  Enabled spans are profiler annotations, so the profiler's
``.xplane.pb`` holds them on the device trace's clock.  Without a TPU it
exits 3 and prints nothing.

The functions below read that trace: spans are ``(name, start_ns,
end_ns)`` host events (``trace_reduce.load``'s host list), and the
program's spans are the host events whose names the tracer recorded.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

Span = Tuple[str, float, float]
Interval = Tuple[float, float]

WINDOW = "bench.window"
ITERATION = "bench.rl_iteration"       # the harness's span per iteration
BETWEEN = "(between iterations)"


def _union(iv: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _length(iv: Iterable[Interval]) -> float:
    return sum(b - a for a, b in iv)


def _clip(iv: Iterable[Interval], w0: float, w1: float) -> List[Interval]:
    return [(max(a, w0), min(b, w1)) for a, b in iv if b > w0 and a < w1]


def _minus(iv: Sequence[Interval], cover: Iterable[Interval]
           ) -> List[Interval]:
    """The parts of the disjoint, sorted intervals ``iv`` that no
    interval of ``cover`` covers."""
    out: List[Interval] = []
    cov = _union(cover)
    j = 0
    for a, b in iv:
        while j < len(cov) and cov[j][1] <= a:
            j += 1
        k = j
        while a < b and k < len(cov) and cov[k][0] < b:
            if cov[k][0] > a:
                out.append((a, cov[k][0]))
            a = max(a, cov[k][1])
            k += 1
        if a < b:
            out.append((a, b))
    return out


def window(host: Sequence[Span]) -> Interval:
    wins = [(a, b) for n, a, b in host if n == WINDOW]
    if not wins:
        raise ValueError(f"no {WINDOW} span in the trace")
    return min(a for a, _ in wins), max(b for _, b in wins)


def idle_intervals(devices: Dict[int, dict], w0: float, w1: float
                   ) -> Dict[int, List[Interval]]:
    """Per device, the parts of the window in which no operation runs."""
    out = {}
    for dev, rec in devices.items():
        busy = _union(_clip([(a, b) for _, a, b in rec["ops"]]
                            or [(a, b) for _, a, b in rec["modules"]],
                            w0, w1))
        out[dev] = _minus([(w0, w1)], busy)
    return out


def idle_between(devices: Dict[int, dict], host: Sequence[Span],
                 prefix: str = "task.") -> float:
    """Share of the window in which the device is idle and no span whose
    name starts with ``prefix`` is open on any thread, mean over the
    devices."""
    w0, w1 = window(host)
    tasks = [(a, b) for n, a, b in host if n.startswith(prefix)]
    idle = idle_intervals(devices, w0, w1)
    return sum(_length(_minus(iv, tasks)) for iv in idle.values()) \
        / len(idle) / (w1 - w0)


def _owners(host: Sequence[Span], names: Iterable[str], w0: float,
            w1: float) -> List[Tuple[float, float, str]]:
    """The window cut at every boundary of a span among ``names``, each
    piece as (start, end, owner): the innermost such span over it, the
    harness's iteration span where no program span is open, ``BETWEEN``
    outside every iteration."""
    names = set(names) | {ITERATION}
    spans = sorted((b - a, a, b, n) for n, a, b in host
                   if n in names and b > w0 and a < w1)
    pts = sorted({w0, w1} | {x for _, a, b, _ in spans for x in (a, b)
                             if w0 < x < w1})
    out = []
    for p, q in zip(pts, pts[1:]):
        mid = (p + q) / 2
        out.append((p, q, next((n for _, a, b, n in spans
                                if a <= mid <= b), BETWEEN)))
    return out


def gap_owners(devices: Dict[int, dict], host: Sequence[Span],
               names: Iterable[str]
               ) -> Tuple[Dict[str, float], List[Tuple[str, float]]]:
    """The first device's idle time in the window put down to its owners
    (``_owners``): seconds per owner, and each idle gap as (owner of its
    middle, seconds)."""
    w0, w1 = window(host)
    segs = _owners(host, names, w0, w1)
    by_owner: Dict[str, float] = defaultdict(float)
    gaps = []
    j = 0
    for a, b in idle_intervals(devices, w0, w1)[min(devices)]:
        while segs[j][1] <= a:
            j += 1
        k, mid = j, (a + b) / 2
        while k < len(segs) and segs[k][0] < b:
            p, q, owner = segs[k]
            by_owner[owner] += (min(b, q) - max(a, p)) / 1e9
            if p <= mid <= q:
                gaps.append((owner, (b - a) / 1e9))
            k += 1
    return dict(by_owner), gaps


def in_window(host: Sequence[Span], names: Iterable[str]) -> List[Span]:
    """The spans among ``names`` (and the harness's iterations) that
    start and end inside the window."""
    names = set(names) | {ITERATION}
    w0, w1 = window(host)
    return [(n, a, b) for n, a, b in host
            if n in names and a >= w0 and b <= w1]


def self_seconds(spans: Sequence[Span], span: Span) -> float:
    """A span's duration less the part of it that the other spans inside
    it cover, on any thread (nested children count once, overlapping
    ones by their union)."""
    _, a, b = span
    inside = [(sa, sb) for n, sa, sb in spans
              if (n, sa, sb) != span and sa >= a and sb <= b]
    return (b - a - _length(_union(inside))) / 1e9


def span_ms(spans: Sequence[Span], name: str, n_iter: int,
            own: bool = False) -> Optional[float]:
    """Mean milliseconds per iteration of the spans named ``name``
    (their self time with ``own``), over ``n_iter`` iterations; None
    unless the spans hold exactly ``n_iter`` ``engine.iteration``s."""
    if not n_iter or sum(n == "engine.iteration" for n, _, _ in spans) \
            != n_iter:
        return None
    mine = [s for s in spans if s[0] == name]
    secs = sum(self_seconds(spans, s) if own else (s[2] - s[1]) / 1e9
               for s in mine)
    return 1e3 * secs / n_iter


def inside(host: Sequence[Span], name: str, top: int = 8
           ) -> List[Tuple[str, float]]:
    """Seconds of every host event (the runtime's too) that lies inside a
    span named ``name``, by event name, the largest ``top``."""
    outer = [(a, b) for n, a, b in host if n == name]
    got: Dict[str, float] = defaultdict(float)
    for n, a, b in host:
        if n != name and any(oa <= a and b <= ob for oa, ob in outer):
            got[n] += (b - a) / 1e9
    return sorted(got.items(), key=lambda x: -x[1])[:top]


def report(devices: Dict[int, dict], host: Sequence[Span],
           names: Iterable[str]) -> Dict[str, object]:
    """The traced iterations as a sum of spans, and the idle time by
    owner."""
    w0, w1 = window(host)
    spans = in_window(host, names)
    n = sum(s[0] == ITERATION for s in spans)
    per_span = {}
    for name in sorted({s[0] for s in spans}):
        durs = [b - a for s, a, b in spans if s == name]
        per_span[name] = {"count": len(durs),
                          "mean_ms": sum(durs) / len(durs) / 1e6,
                          "ms": span_ms(spans, name, n),
                          "self_ms": span_ms(spans, name, n, own=True)}
    iters = [(a, b) for s, a, b in spans if s == ITERATION]
    by_owner, gaps = gap_owners(devices, host, names)
    idle = idle_intervals(devices, w0, w1)
    return {
        "window_s": (w1 - w0) / 1e9,
        "iterations": n,
        "between_iterations_ms": ((w1 - w0) - _length(iters))
        / 1e6 / max(n, 1),
        "spans": per_span,
        "advantage_ms": span_ms(spans, "rl.advantages", n),
        "engine_self_ms": span_ms(spans, "engine.iteration", n, own=True),
        "device_idle_pct": 100.0 * sum(_length(v) for v in idle.values())
        / len(idle) / (w1 - w0),
        "idle_between_tasks_pct": 100.0 * idle_between(devices, host),
        "idle_ms_by_owner": sorted(
            ([o, 1e3 * s / max(n, 1)] for o, s in by_owner.items()),
            key=lambda x: -x[1]),
        "longest_gaps_ms": [[o, 1e3 * s] for o, s in
                            sorted(gaps, key=lambda g: -g[1])[:12]],
        # what the host runs inside the spans that own most idle time
        "inside_ms": {o: [[e, 1e3 * s / max(n, 1)]
                          for e, s in inside(host, o)]
                      for o, _ in sorted(by_owner.items(),
                                         key=lambda x: -x[1])[:3]
                      if o != BETWEEN},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import run as R, trace_reduce
    from repro.obs import trace as obs_trace
    cell, spec, traffic = R.cell_spec(args.workload)
    jax = R.setup_jax()
    if jax.default_backend() != "tpu":
        print(f"no TPU: JAX found backend {jax.default_backend()!r}",
              file=sys.stderr)
        return 3
    devices = jax.devices()[:cell["chips"]]
    got = {}
    load = trace_reduce.load

    def load_and_read(path):
        # the harness reads the trace once and then removes it
        devs, host = load(path)
        names = {e["name"] for e in obs_trace.TRACER.chrome_events()}
        got.update(report(devs, host, names))
        return devs, host

    trace_reduce.load = load_and_read
    obs_trace.enable()
    try:
        result = R.run_cell(cell, spec, traffic, args.seed, args.seconds,
                            True, devices)
    finally:
        obs_trace.disable()
        trace_reduce.load = load
    walls = [it["wall_s"] for it in result["_record"]["traced_iters"]]
    got.update(correct=result["correct"],
               device=result["device"]["kind"],
               traced_iters=len(walls),
               wall_ms_mean=1e3 * sum(walls) / len(walls),
               harness_metrics=result["metrics"])
    print(json.dumps(got), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
