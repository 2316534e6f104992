"""The deadline wrapper stops a paired hill climb and a DFS exploration at
the deadline, keeps the best, and loses no neighbour to an AttributeError;
the rate is completed candidates over the time to the last completion, and
a candidate that stalls until the deadline is charged."""

import json
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmarks.harness.cell import load_module, toy_shapes
from benchmarks.harness.stack import (
    Deadline,
    DeadlineBenchmarker,
    Spans,
    build_stack,
)

HERE = Path(__file__).parent.parent


def toy_cell(config_name, mix_name):
    c = toy_shapes(json.loads(
        (HERE / "configs" / f"{config_name}.json").read_text()))
    mix = json.loads((HERE / "mixes" / f"{mix_name}.json").read_text())
    return c, mix


def drive(config_name, mix_name, seconds, seed=3, bench_opts=None):
    import jax

    config, mix = toy_cell(config_name, mix_name)
    if bench_opts:  # a CPU under load needs a lower floor than the cell's
        mix = {**mix, "params": {**mix["params"], "bench_opts": bench_opts}}
    ref = load_module("references", config["reference"])
    built = load_module("builders", config["builder"]).build(
        config, seed, jax.devices()[:1], ref)
    spans = Spans()
    bench, verifier, prefetcher, _ = build_stack(built.executor, built.graph,
                                                 spans)
    ctx = SimpleNamespace(graph=built.graph, bench=bench, verifier=verifier,
                          prefetcher=prefetcher, hints=built.hints, seed=seed)
    bench.open(seconds)
    t0 = time.perf_counter()
    try:
        with pytest.raises(Deadline):
            load_module("solvers", mix["solver"]).run(ctx, mix["params"])
    finally:
        bench.close()
        prefetcher.close()
    return bench, spans, time.perf_counter() - t0


def test_deadline_stops_paired_hill_climb_and_keeps_the_best():
    bench, spans, wall = drive(
        "halo512", "climb", seconds=8.0,
        bench_opts={"n_iters": 3, "target_secs": 0.005, "max_retries": 1})
    done = bench.in_window()
    assert len(done) >= 2, "incumbent and at least one neighbour"
    assert done[0]["kind"] == "single"            # the climb's start point
    assert any(c["kind"] == "batch" for c in done)  # paired steps arrived
    errors = [c["error"] for c in bench.candidates if "error" in c]
    assert not [e for e in errors if "AttributeError" in e], errors
    assert all(c["t1"] <= bench.deadline for c in done)
    assert wall < 8.0 + 60.0                      # stopped, not run out
    finalists = bench.finalists(2)
    assert 2 <= len(finalists) <= 3   # the two best, and the incumbent
    assert finalists[0][1] <= finalists[1][1]
    most = max(bench.readings.values(), key=lambda ov: len(ov[1]))
    assert any(o is most[0] for o, _ in finalists)
    assert finalists[0][1] == min(
        __import__("statistics").median(v) for _, v in
        bench.readings.values())
    # the incumbent is measured again in every paired step
    assert max(len(v) for _, v in bench.readings.values()) >= 2


def test_deadline_stops_dfs_explore():
    bench, spans, wall = drive("spmv16k", "dfs", seconds=4.0)
    done = bench.in_window()
    assert len(done) >= 3
    assert all(c["kind"] == "single" for c in done)
    assert not [c for c in done if "error" in c]
    assert wall < 4.0 + 30.0
    names = {s[0] for s in spans.items}
    assert {"solver", "measure", "verify", "first_call"} <= names


def test_deadline_stops_mcts_and_every_rollout_is_a_candidate():
    bench, spans, wall = drive(
        "halo512", "mcts", seconds=6.0,
        bench_opts={"n_iters": 2, "target_secs": 0.002, "max_retries": 1})
    done = bench.in_window()
    assert len(done) >= 3
    assert all(c["kind"] == "single" for c in done)
    assert not [c for c in done if "error" in c]
    assert len({c["key"] for c in done}) == len(done)  # no schedule twice
    assert wall < 6.0 + 30.0


class FakeInner:
    """A cache-like layer over a layer with a batch method."""

    def __init__(self, clock):
        self.hits = 0
        self.inner = self
        self.clock = clock

    def benchmark(self, order, opts=None):
        if order == "cached":
            self.hits += 1
            return SimpleNamespace(pct50=1.0)
        if order == "bad":
            raise ValueError("does not compile")
        self.clock.t += 2.0
        return SimpleNamespace(pct50={"a": 3.0, "b": 2.0}.get(order, 5.0))

    def benchmark_batch_times(self, orders, opts=None, seed=0):
        self.clock.t += 3.0
        return [[4.0, 4.2, 4.1], [1.0, 1.2, 1.1]]


class FakeClock:
    t = 100.0

    def __call__(self):
        return self.t


def test_rate_is_completed_over_time_of_last_completion():
    clock = FakeClock()
    b = DeadlineBenchmarker(FakeInner(clock), Spans(clock), key=lambda o: o)
    b.open(10.0)
    b.benchmark("a")                       # done at 2
    b.benchmark("cached")                  # no candidate, no time
    with pytest.raises(ValueError):
        b.benchmark("bad")                 # failed at 2
    clock.t += 1.0                         # solver time
    b.benchmark_batch_times(["a", "n1"])   # done at 6: one candidate, n1
    b.benchmark("b")                       # done at 8
    with pytest.raises(Deadline):
        b.benchmark("late")                # would end at 10 + 0: not late
        b.benchmark("later")               # gate: deadline reached
    b.close()
    done = b.in_window()
    ok = [c for c in done if "error" not in c]
    assert [c["key"] for c in ok] == ["a", "n1", "b", "late"]
    t_last = max(c["t1"] for c in done) - b.t_open
    assert t_last == 10.0
    assert len(ok) / t_last == pytest.approx(0.4)
    # best: n1's batch reading 1.1, then b at 2.0; a (3.0 and 4.1) is the
    # one measured more than once, the incumbent, and is timed as well
    assert [o for o, _ in b.finalists(2)] == ["n1", "b", "a"]


@pytest.mark.parametrize("by_deadline, t_end, expected, why", [
    (False, 51.0, 40.0, "the solver returned: the last completion"),
    (True, 44.5, 40.0, "cut short after 4.5 s, less than the longest took"),
    (True, 51.0, 45.0, "stalled 11 s: all but the longest's 6 s counts"),
])
def test_rate_span_charges_a_stall_but_not_a_cut(by_deadline, t_end,
                                                 expected, why):
    from benchmarks.harness.cell import rate_seconds

    ok = [{"t0": 0.0, "t1": 3.0}, {"t0": 3.0, "t1": 9.0},
          {"t0": 37.0, "t1": 40.0}]
    assert rate_seconds(ok, 40.0, by_deadline, t_end) == expected, why


def test_candidate_in_flight_at_the_deadline_is_dropped():
    clock = FakeClock()
    b = DeadlineBenchmarker(FakeInner(clock), Spans(clock), key=lambda o: o)
    b.open(3.0)
    b.benchmark("a")                       # done at 2
    with pytest.raises(Deadline):
        b.benchmark("b")                   # ends at 4 > 3
    assert [c["key"] for c in b.in_window()] == ["a"]
    assert [o for o, _ in b.finalists(2)] == ["a"]
