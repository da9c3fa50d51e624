"""The program's spans read from a profiler trace (``bench.spans``).

The recorded CPU trace under data/ holds three iterations inside a
``bench.window`` span, each a ``bench.rl_iteration`` around the
program's own spans (``repro.obs.trace``, enabled, so profiler
annotations): ``engine.iteration`` > ``engine.stage`` >
``task.actor_generation`` (a small jitted program, a 10 ms host wait,
the program again), ``rl.advantages`` (a 5 ms wait between tasks),
``engine.stage`` > ``task.actor_training`` (the program), then 4 ms in
``engine.iteration`` itself; 3 ms pass between iterations."""
import os

import pytest

from bench import spans as S
from bench import trace_reduce as tr

TRACE = os.path.join(os.path.dirname(__file__), "data",
                     "program_spans.xplane.pb")
NAMES = {"engine.iteration", "engine.stage", "task.actor_generation",
         "task.actor_training", "rl.advantages"}
MS = 1e6


@pytest.fixture(scope="module")
def trace():
    return tr.load(TRACE)


@pytest.fixture(scope="module")
def rep(trace):
    return S.report(*trace, NAMES)


def test_program_spans_are_host_events_in_the_window(trace):
    devices, host = trace
    w0, w1 = S.window(host)
    inside = S.in_window(host, NAMES)
    assert {n for n, _, _ in inside} == NAMES | {S.ITERATION}
    assert sum(n == "engine.iteration" for n, _, _ in inside) == 3
    assert all(w0 <= a < b <= w1 for _, a, b in inside)
    # the runtime's own host events sit inside the task that ran the
    # program; the advantage span only waited
    assert any(n.startswith("PjitFunction")
               for n, _ in S.inside(host, "task.actor_generation"))
    assert S.inside(host, "rl.advantages") == []


def test_iteration_as_a_sum_of_spans(rep):
    assert rep["iterations"] == 3
    sp = rep["spans"]
    assert 5.0 <= rep["advantage_ms"] < 7.0
    assert rep["advantage_ms"] == sp["rl.advantages"]["ms"]
    # the engine's own 4 ms, less nothing of its children
    assert 4.0 <= rep["engine_self_ms"] < 6.0
    assert 3.0 <= rep["between_iterations_ms"] < 5.0
    # the self times of every span and the gaps between iterations make
    # up the window
    total = sum(v["self_ms"] for v in sp.values()) * 3 \
        + rep["between_iterations_ms"] * 3
    assert total == pytest.approx(1e3 * rep["window_s"], rel=1e-9)


def test_idle_between_tasks(trace, rep):
    devices, host = trace
    by_owner, gaps = S.gap_owners(devices, host, NAMES)
    w0, w1 = S.window(host)
    idle = sum(b - a for a, b in S.idle_intervals(devices, w0, w1)[0]) / 1e9
    assert sum(by_owner.values()) == pytest.approx(idle, rel=1e-9)
    assert sum(s for _, s in gaps) == pytest.approx(idle, rel=1e-9)
    # idle with no task open is exactly the idle time owned outside tasks
    outside = sum(s for o, s in by_owner.items()
                  if not o.startswith("task."))
    assert rep["idle_between_tasks_pct"] == pytest.approx(
        100 * outside / rep["window_s"], rel=1e-9)
    assert 0 < rep["idle_between_tasks_pct"] < rep["device_idle_pct"]
    # the waits between tasks: 5 + 4 + 3 ms of each iteration's window
    assert 12 * 3 / 1e3 <= outside < 0.6 * rep["window_s"]
    # the longest gaps are the waits, each put down to its span
    owners = {o for o, s in gaps if s > 2e-3}
    assert owners == {"task.actor_generation", "rl.advantages",
                      "engine.iteration", S.BETWEEN}
    assert by_owner["task.actor_generation"] >= 30e-3


def test_self_time_with_nested_and_overlapping_children():
    spans = [("engine.iteration", 0, 100 * MS),
             ("engine.stage", 10 * MS, 40 * MS),
             ("task.a", 15 * MS, 35 * MS),          # nested in the stage
             ("engine.stage", 30 * MS, 60 * MS),    # overlaps the first
             ("rl.advantages", 70 * MS, 80 * MS),
             ("engine.iteration", 200 * MS, 210 * MS),
             ("task.b", 150 * MS, 160 * MS)]        # in neither iteration
    it = spans[0]
    # covered: 10-60 and 70-80 ms
    assert S.self_seconds(spans, it) == pytest.approx(40e-3)
    assert S.self_seconds(spans, spans[1]) == pytest.approx(10e-3)
    assert S.span_ms(spans, "engine.iteration", 2, own=True) == \
        pytest.approx((40 + 10) / 2)
    assert S.span_ms(spans, "rl.advantages", 2) == pytest.approx(5.0)


def test_span_readers_need_one_engine_iteration_per_iteration():
    spans = [("engine.iteration", 0, 10 * MS),
             ("rl.advantages", 2 * MS, 3 * MS)]
    assert S.span_ms(spans, "rl.advantages", 1) == pytest.approx(1.0)
    assert S.span_ms(spans, "rl.advantages", 2) is None
    assert S.span_ms(spans, "engine.iteration", 2, own=True) is None
    assert S.span_ms(spans, "rl.advantages", 0) is None
    assert S.span_ms([], "rl.advantages", 1) is None


def test_minus_leaves_the_uncovered_parts():
    iv = [(0, 10), (20, 30), (40, 50)]
    cover = [(5, 22), (25, 26), (45, 60), (1, 2)]
    assert S._minus(iv, cover) == [(0, 1), (2, 5), (22, 25), (26, 30),
                                   (40, 45)]
    assert S._minus(iv, []) == iv
