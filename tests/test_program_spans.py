"""The program's own spans (ISSUE 25): the tracer follows a profiler session
and mirrors its spans into it; a first call is split into lower / XLA compile
/ first run; a dispatch into enqueue / fence wait; the foreground's wait on a
prefetch worker has a name; the benchmark's seven readers on hand-made spans;
and the program's reduction of a profile (obs/attrib/xplane.py) on a
hand-made trace and on one recorded on a v5e."""

import json
import subprocess
import sys
import threading
import time
from pathlib import Path

import pytest

from tenzing_tpu.obs import tracer as tracer_mod
from tenzing_tpu.obs.attrib import xplane
from tenzing_tpu.obs.metrics import MetricsRegistry, set_metrics
from tenzing_tpu.obs.tracer import Span, Tracer, set_tracer

ROOT = Path(__file__).resolve().parent.parent
DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture
def tracer():
    tr = Tracer(enabled=True)
    prev = set_tracer(tr)
    try:
        yield tr
    finally:
        set_tracer(prev)


@pytest.fixture
def registry():
    reg = MetricsRegistry()
    prev = set_metrics(reg)
    try:
        yield reg
    finally:
        set_metrics(prev)


def _toy():
    """``(executor, schedule)`` of a one-kernel graph on one lane."""
    import jax.numpy as jnp

    from tenzing_tpu.core.graph import Graph
    from tenzing_tpu.core.operation import DeviceOp
    from tenzing_tpu.core.platform import Platform
    from tenzing_tpu.core.state import State
    from tenzing_tpu.runtime.executor import TraceExecutor

    class Mul(DeviceOp):
        def reads(self):
            return ["x"]

        def writes(self):
            return ["y"]

        def apply(self, bufs, ctx):
            return {"y": bufs["x"] * 2.0}

    g = Graph()
    m = Mul("m")
    g.start_then(m)
    g.then_finish(m)
    plat = Platform.make_n_lanes(1)
    ex = TraceExecutor(plat, {"x": jnp.ones((8, 8)), "y": jnp.zeros((8, 8))})
    st = State(g)
    while not st.is_terminal():
        st = st.apply(st.get_decisions(plat)[0])
    return ex, st.sequence


def _by_name(tr, name):
    return [s for s in tr.spans() if s.name == name]


def _kids(tr, parent):
    return [s for s in tr.spans() if s.parent_id == parent.span_id]


# -- the tracer follows the profiler ------------------------------------------

def test_tracer_records_and_mirrors_only_while_a_session_is_active(tmp_path):
    import jax

    tr = Tracer(enabled=False)
    assert tr.span("before") is tr.span("before2")  # the shared no-op
    assert not tr.recording
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert tr.recording
        with tr.span("outer", k=1) as sp:
            sp.set("seen", True)
            with tr.span("inner"):
                time.sleep(0.02)
            tr.event("tick", n=3)

        def worker():
            with tr.span("on_worker"):
                time.sleep(0.01)

        th = threading.Thread(target=worker)
        th.start()
        th.join()
    finally:
        jax.profiler.stop_trace()
    assert tr.span("after") is tr.span("after2")
    assert not tr.recording and tr.enabled is False
    ring = {s.name: s for s in tr.spans()}
    assert set(ring) == {"outer", "inner", "on_worker"}
    assert ring["outer"].attrs == {"k": 1, "seen": True}
    assert ring["inner"].parent_id == ring["outer"].span_id
    assert [e.name for e in tr.events()] == ["tick"]
    # perf_counter stamps agree with the unix ones
    for s in ring.values():
        assert s.t1 >= s.t0 and abs((s.t1 - s.t0) * 1e6 - s.dur_us) < 1.0
    # the same spans are in the xplane, under tz:, with the ring's durations
    # to 1 ms, each thread's on a line of its own
    threads = xplane.program_threads(xplane.load_xplane(tmp_path))
    found = {}
    for line, evs in threads.items():
        for name, a, b in evs:
            found[name] = (line, (b - a) / 1e9)
    assert set(found) == set(ring)
    for name, (_, secs) in found.items():
        assert abs(secs - (ring[name].t1 - ring[name].t0)) < 1e-3, name
    assert found["outer"][0] == found["inner"][0] != found["on_worker"][0]


@pytest.mark.parametrize("edge", ["opened_before", "closed_after"])
def test_a_span_across_an_edge_of_the_session_does_not_raise(tmp_path, edge):
    import jax

    tr = Tracer(enabled=False)
    if edge == "opened_before":
        ctx = tr.span("early")
        ctx.__enter__()
        jax.profiler.start_trace(str(tmp_path))
        ctx.__exit__(None, None, None)
        jax.profiler.stop_trace()
        assert tr.spans() == []  # not recorded: it opened as the no-op
    else:
        jax.profiler.start_trace(str(tmp_path))
        ctx = tr.span("late")
        ctx.__enter__()
        jax.profiler.stop_trace()
        with tr.span("inside_late"):  # no session any more: the no-op
            pass
        ctx.__exit__(None, None, None)
        assert [s.name for s in tr.spans()] == ["late"]
        assert tr.spans()[0].t1 is not None


def test_obs_imports_and_records_without_jax():
    code = (
        "import sys\n"
        "import tenzing_tpu.obs, tenzing_tpu.obs.tracer as t\n"
        "tr = t.Tracer(enabled=False)\n"
        "assert tr.span('a') is tr.span('b') and not tr.recording\n"
        "tr.enabled = True\n"
        "with tr.span('s'): pass\n"
        "assert len(tr.spans()) == 1 and tr.spans()[0].t1 is not None\n"
        "assert not [m for m in sys.modules if m.split('.')[0] == 'jax']\n")
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr


def test_span_site_cost_off_and_on():
    """Nanoseconds a span site costs (ISSUE 25 acceptance; printed with
    ``-s``).  Off: the shared no-op plus the look for a session."""
    import jax  # noqa: F401  (the look then calls the real is_enabled)

    def per_site(tr, n):
        t0 = time.perf_counter()
        for _ in range(n):
            with tr.span("hot", n=1):
                pass
        return (time.perf_counter() - t0) / n * 1e9

    off = min(per_site(Tracer(enabled=False), 20_000) for _ in range(3))
    on = min(per_site(Tracer(enabled=True), 5_000) for _ in range(3))
    print(f"span site: {off:.0f} ns off, {on:.0f} ns on (no session)")
    assert tracer_mod._session_active is not tracer_mod._no_session
    assert off < 5_000 and on < 100_000


# -- a first call, in its parts -------------------------------------------------

@pytest.mark.parametrize("path", ["compile", "prepare_n", "precompile"])
def test_first_call_is_one_span_whose_parts_add_to_it(tracer, path):
    ex, seq = _toy()
    count0 = ex.compile_count
    if path == "compile":
        ex.run(seq)
        ex.run(seq)
    elif path == "prepare_n":
        ex.prepare_n(seq)(2)
        ex.prepare_n(seq)(2)
    else:
        assert ex.precompile(seq) is True
        assert ex.precompile(seq) is False
    assert ex.compile_count == count0 + 1
    (fc,) = _by_name(tracer, "executor.first_call")
    parts = _kids(tracer, fc)
    want = ["executor.lower", "executor.xla_compile"]
    if path == "precompile":
        assert fc.attrs["aot"] is True
    else:
        want.append("executor.first_run")
        assert "aot" not in fc.attrs
    assert [p.name for p in parts] == want
    total = sum(p.t1 - p.t0 for p in parts)
    assert abs(total - (fc.t1 - fc.t0)) <= 0.05 * (fc.t1 - fc.t0)
    assert all(p.t1 - p.t0 > 0 for p in parts)
    if path != "precompile":
        assert parts[-1].attrs["n"] == (1 if path == "compile" else 2)
    assert fc.attrs["schedule"]


def test_a_precompiled_program_has_its_first_run_in_the_foreground(tracer):
    ex, seq = _toy()
    ex.precompile(seq)
    count = ex.compile_count
    run_n = ex.prepare_n(seq)
    run_n(3)
    run_n(3)
    ex.prepare_n(seq)(3)
    assert ex.compile_count == count  # a first run is no first call
    runs = _by_name(tracer, "executor.first_run")
    assert len(runs) == 1 and runs[0].attrs == {"n": 3}
    assert runs[0].parent_id is None
    assert len(_by_name(tracer, "executor.enqueue")) == 2


@pytest.mark.parametrize("path", ["prepare_n", "precompile"])
def test_the_timed_object_is_still_callable_as_bufs_n(path):
    import jax
    import jax.numpy as jnp

    from tenzing_tpu.core.serdes import sequence_to_json_str

    ex, seq = _toy()
    if path == "precompile":
        ex.precompile(seq)
    ex.prepare_n(seq)(1)
    f = ex._cache["n:" + sequence_to_json_str(seq)]
    fence, host_outs = f(ex.init_bufs, jnp.int32(2))
    assert float(jax.device_get(fence)) == 64 + 128 and host_outs == {}


# -- a dispatch, in its parts ---------------------------------------------------

def test_measure_gives_one_dispatch_span_per_run_n(tracer, registry):
    from tenzing_tpu.bench.benchmarker import BenchOpts, EmpiricalBenchmarker

    ex, seq = _toy()
    bench = EmpiricalBenchmarker(ex)
    run_n = ex.prepare_n(seq)
    run_n(1)  # the first call, outside what is counted below
    tracer.clear()
    calls = []

    def counted(n):
        calls.append(n)
        run_n(n)

    bench._measure(counted, 1, BenchOpts(target_secs=2e-3))
    disp = _by_name(tracer, "bench.dispatch")
    assert [d.attrs["n"] for d in disp] == calls and len(calls) >= 2
    for d in disp:
        assert [k.name for k in _kids(tracer, d)] == [
            "executor.enqueue", "executor.fence_wait"]
    assert registry.counter("bench.dispatches").value == len(calls)


def test_benchmark_nests_warm_first_call_and_dispatches(tracer, registry):
    from tenzing_tpu.bench.benchmarker import BenchOpts, EmpiricalBenchmarker

    ex, seq = _toy()
    EmpiricalBenchmarker(ex).benchmark(
        seq, BenchOpts(n_iters=2, max_retries=1, target_secs=1e-4))
    (call,) = _by_name(tracer, "bench.benchmark")
    (warm,) = _by_name(tracer, "bench.warm")
    (fc,) = _by_name(tracer, "executor.first_call")
    by_id = {s.span_id: s for s in tracer.spans()}
    assert by_id[fc.parent_id].name == "bench.dispatch"
    assert by_id[fc.parent_id].parent_id == warm.span_id
    assert warm.parent_id == call.span_id and call.attrs["schedule"]
    disp = _by_name(tracer, "bench.dispatch")
    assert registry.counter("bench.dispatches").value == len(disp) >= 3


# -- the wait on the prefetcher ---------------------------------------------------

class _SlowExecutor:
    def __init__(self, secs):
        self.secs = secs
        self.started = threading.Event()

    def precompile(self, order):
        self.started.set()
        time.sleep(self.secs)
        return True


class _NoBench:
    def benchmark(self, order, opts=None):
        return "measured"


@pytest.mark.parametrize("joined", ["running", "finished", "never_hinted"])
def test_pipeline_wait_only_when_a_running_precompile_is_joined(
        tracer, registry, joined):
    from tenzing_tpu.bench.pipeline import PrefetchingBenchmarker

    _, seq = _toy()
    slow = _SlowExecutor(0.15)
    with PrefetchingBenchmarker(_NoBench(), executor=slow, workers=1) as pf:
        if joined != "never_hinted":
            assert pf.prefetch([seq]) == 1
            assert slow.started.wait(5)
        if joined == "finished":
            time.sleep(0.4)
        assert pf.benchmark(seq) == "measured"
    waits = _by_name(tracer, "pipeline.wait")
    if joined == "running":
        assert len(waits) == 1 and waits[0].attrs["schedule"]
        assert 0.02 < waits[0].t1 - waits[0].t0 < 0.3
    else:
        assert waits == []
    if joined != "never_hinted":
        assert registry.counter("pipeline.prefetch.hits").value == 1


def test_hill_climb_iterations_are_spans(tracer):
    from tests.test_local import PHASES, RiggedBenchmarker, mk

    from tenzing_tpu.bench.benchmarker import BenchOpts, CachingBenchmarker
    from tenzing_tpu.solve.local import LocalOpts, hill_climb

    g, plat, _ = mk()
    hill_climb(g, plat, CachingBenchmarker(RiggedBenchmarker()), PHASES,
               opts=LocalOpts(budget=6, bench_opts=BenchOpts(n_iters=1),
                              seed=3))
    its = _by_name(tracer, "climb.iter")
    assert len(its) >= 3 and all({"it", "pos"} <= set(s.attrs) for s in its)


# -- the benchmark's readers, on hand-made spans -----------------------------------

def _reader(name):
    from benchmarks.harness.cell import load_module

    return load_module("layer_metrics", name).read


def _put(tr, name, t0, t1, tid=0, parent=None, **attrs):
    sp = Span(name, tr._to_us(t0), 0, tid, len(tr._spans) + 1000,
              parent.span_id if parent else None, attrs, t0)
    sp.t1 = t1
    sp.dur_us = (t1 - t0) * 1e6
    tr._spans.append(sp)
    return sp


# a window from t=100 to t=110 on perf_counter's scale
RECORD = {"window": {"span_s": 10.0, "candidates": [
    {"t0": 100.0, "t1": 104.0}, {"t0": 104.0, "t1": 110.0},
    {"t0": 110.0, "t1": 113.0, "late": True}]}}


def _hand_made(tr):
    """A foreground thread (tid 0) that measures two candidates, a prefetch
    worker (tid 1) that compiles ahead."""
    # the worker: one whole AOT first call, one cut off by the slice's end
    # (no xla_compile recorded), one whose call opened before the slice
    pre = _put(tr, "pipeline.precompile", 100.5, 103.6, tid=1)
    fc = _put(tr, "executor.first_call", 100.5, 103.5, tid=1, parent=pre,
              aot=True)
    _put(tr, "executor.lower", 100.5, 100.7, tid=1, parent=fc)
    _put(tr, "executor.xla_compile", 100.7, 103.5, tid=1, parent=fc)
    cut = _put(tr, "executor.first_call", 108.0, 111.0, tid=1, aot=True)
    _put(tr, "executor.lower", 108.0, 108.3, tid=1, parent=cut)
    _put(tr, "executor.xla_compile", 100.1, 100.4, tid=1)  # an orphan
    # the foreground: candidate 1 waits for the worker, runs its program
    # for the first time, then dispatches twice
    _put(tr, "pipeline.wait", 101.0, 103.6)
    b1 = _put(tr, "bench.benchmark", 103.6, 104.0)
    d = _put(tr, "bench.dispatch", 103.6, 103.7, parent=b1, n=1)
    _put(tr, "executor.first_run", 103.6, 103.68, parent=d, n=1)
    for a in (103.7, 103.8):
        d = _put(tr, "bench.dispatch", a, a + 0.1, parent=b1, n=4)
        _put(tr, "executor.enqueue", a, a + 0.03, parent=d)
        _put(tr, "executor.fence_wait", a + 0.03, a + 0.1, parent=d)
    # candidate 2 compiles in the foreground (a whole lazy first call)
    b2 = _put(tr, "bench.batch", 104.5, 109.0)
    d = _put(tr, "bench.dispatch", 104.5, 108.5, parent=b2, n=1)
    fc2 = _put(tr, "executor.first_call", 104.5, 108.5, parent=d)
    _put(tr, "executor.lower", 104.5, 104.9, parent=fc2)
    _put(tr, "executor.xla_compile", 104.9, 108.3, parent=fc2)
    _put(tr, "executor.first_run", 108.3, 108.5, parent=fc2, n=1)
    d = _put(tr, "bench.dispatch", 108.5, 108.6, parent=b2, n=2)
    _put(tr, "executor.enqueue", 108.5, 108.51, parent=d)
    _put(tr, "executor.fence_wait", 108.51, 108.6, parent=d)
    # after the window: the epilogue's traced finalist
    d = _put(tr, "bench.dispatch", 120.0, 121.0, n=13)
    _put(tr, "executor.enqueue", 120.0, 120.9, parent=d)


HAND_MADE = {
    # two whole first calls: (0.2 + 0.4) / 2, (2.8 + 3.4) / 2
    "lower_s_per_program": 0.3,
    "xla_compile_s_per_program": 3.1,
    # two first runs: (0.08 + 0.2) / 2
    "first_run_s_per_program": 0.14,
    # foreground: wait 2.6 + first call 4.0, over two measurement calls
    "first_call_wait_s_per_eval": 3.3,
    "prefetch_hit_share": 50.0,
    # median of 30, 30, 10 ms
    "dispatch_enqueue_ms": 30.0,
    # five dispatches inside the window, two calls
    "dispatches_per_eval": 2.5,
}


@pytest.mark.parametrize("name", sorted(HAND_MADE))
def test_reader_on_hand_made_spans(tracer, registry, name):
    _hand_made(tracer)
    registry.counter("pipeline.prefetch.issued").inc(4)
    registry.counter("pipeline.prefetch.hits").inc(2)
    assert _reader(name)(RECORD) == pytest.approx(HAND_MADE[name], rel=1e-6)


@pytest.mark.parametrize("name", sorted(HAND_MADE))
def test_reader_finds_nothing_on_an_empty_tracer(tracer, registry, name):
    assert _reader(name)(RECORD) is None
    # spans outside the window (the epilogue's) are not the window's
    d = _put(tracer, "bench.dispatch", 120.0, 121.0, n=13)
    _put(tracer, "executor.enqueue", 120.0, 120.9, parent=d)
    _put(tracer, "executor.first_run", 121.0, 121.5, n=1)
    assert _reader(name)(RECORD) is None


# -- the program's reduction of a profile -----------------------------------------

def test_merge_intervals_coalesces_and_counts_once():
    merged = xplane.merge_intervals(
        [(0, 10), (5, 15), (20, 30), (30, 40), (50, 60), (70, 70)])
    assert merged == [[0, 15], [20, 40], [50, 60]]
    assert sum(b - a for a, b in merged) == 45


def test_innermost_gives_a_parent_only_its_self_time():
    pieces = xplane.innermost([["p", 0, 100], ["c1", 10, 30], ["g", 15, 20],
                               ["c2", 30, 50], ["q", 120, 130]])
    assert pieces == [(0, 10, "p"), (10, 15, "c1"), (15, 20, "g"),
                      (20, 30, "c1"), (30, 50, "c2"), (50, 100, "p"),
                      (120, 130, "q")]


def _hand_made_trace():
    s = 1_000_000_000  # one second, in ns
    fg = [["tz:bench.benchmark", 0, 10 * s], ["tz:pipeline.wait", 0, 4 * s],
          ["tz:bench.dispatch", 4 * s, 6 * s],
          ["tz:executor.enqueue", 4 * s, 5 * s],
          ["tz:executor.fence_wait", 5 * s, 6 * s],
          ["tzb:measure", 0, 10 * s], ["unrelated", 0, 10 * s]]
    worker = [["tz:pipeline.precompile", 1 * s, 9 * s],
              ["tz:executor.first_call", 1 * s, 9 * s],
              ["tz:executor.lower", 1 * s, 2 * s],
              ["tz:executor.xla_compile", 2 * s, 9 * s]]
    ops = [["%while.3 = (f32[]) while(...)", 5 * s, 6 * s],
           ["%fusion.12 = f32[8] fusion(...)", 5 * s, int(5.5 * s)],
           ["%copy.7 = f32[8] copy(...)", 8 * s, 9 * s],
           ["%copy.9 = f32[8] copy(...)", 20 * s, 21 * s]]  # outside
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [["jit_f", 5 * s, 6 * s]]},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": fg},
            {"name": "python", "events": worker},
            {"name": "tf_pjrt", "events": [["Compile", 2 * s, 9 * s]]}]}]}


def test_reduction_gives_idle_gaps_to_the_foregrounds_innermost_span():
    red = xplane.reduce_trace(_hand_made_trace())
    assert red["slice_s"] == 10.0 and red["n_threads"] == 2
    assert red["busy_s"] == 2.0 and red["idle_s"] == 8.0
    assert red["n_devices"] == 1
    assert dict(red["device_ops"]) == {"copy": 1.0, "fusion": 0.5,
                                       "while": 0.5}
    idle = {r["span"]: r for r in red["idle_by_span"]}
    assert {k: v["idle_s"] for k, v in idle.items()} == {
        "pipeline.wait": 4.0, "bench.benchmark": 3.0,
        "executor.enqueue": 1.0}
    assert dict(idle["pipeline.wait"]["meanwhile"]) == {
        "executor.xla_compile": 2.0, "executor.lower": 1.0}
    assert dict(idle["bench.benchmark"]["meanwhile"]) == {
        "executor.xla_compile": 2.0}
    assert dict(idle["executor.enqueue"]["meanwhile"]) == {
        "executor.xla_compile": 1.0}
    text = xplane.render(red)
    assert "idle 8.000 s (80.0%)" in text and "pipeline.wait" in text


def test_reduction_of_a_trace_without_program_spans_is_empty():
    trace = _hand_made_trace()
    for line in trace["planes"][1]["lines"]:
        line["events"] = [e for e in line["events"]
                          if not e[0].startswith("tz:")]
    assert xplane.reduce_trace(trace) == {}
    assert "no tz: span" in xplane.render({})


@pytest.mark.parametrize("cell", ["spmv16k_dfs", "halo512_climb"])
def test_reduction_of_a_trace_recorded_on_the_chip(cell):
    """A cut of a profile of the cell's search on one TPU v5 lite
    (``benchmarks/tests/program_spans_on_chip.py --trim``)."""
    trace = json.loads((DATA / f"{cell}_v5e.json").read_text())
    red = xplane.reduce_trace(trace)
    assert red["n_devices"] == 1 and red["n_threads"] >= 1
    assert 0 < red["busy_s"] < red["slice_s"]
    idle = {r["span"]: r["idle_s"] for r in red["idle_by_span"]}
    assert sum(idle.values()) == pytest.approx(red["idle_s"], rel=1e-9)
    # the idle time has the program's names on it
    bare = sum(idle.get(k, 0.0) for k in (
        "unattributed", "bench.benchmark", "bench.batch"))
    assert bare < 0.1 * red["idle_s"]
    assert {"executor.fence_wait", "executor.enqueue"} & set(idle)
    if cell == "spmv16k_dfs":
        # the foreground waits while both workers compile
        waits = red["idle_by_span"][0]
        assert waits["span"] == "pipeline.wait" and red["n_threads"] == 3
        assert waits["meanwhile"][0][0] == "executor.xla_compile"
        assert waits["meanwhile"][0][1] > 1.5 * waits["idle_s"]
    else:
        # the flagship's dispatch: nearly all of it is the wait for the fence
        assert red["idle_by_span"][0]["span"] == "executor.fence_wait"
        assert red["idle_by_span"][0]["idle_s"] > 0.5 * red["idle_s"]
    assert red["device_ops"] and all(s > 0 for _, s in red["device_ops"])


def test_the_command_prints_the_reduction_of_a_trace_directory(tmp_path):
    import jax
    import jax.numpy as jnp

    tr = Tracer(enabled=False)
    with jax.profiler.trace(str(tmp_path)):
        with tr.span("bench.dispatch"):
            jax.block_until_ready(jnp.ones(8) + 1)
    p = subprocess.run(
        [sys.executable, "-m", "tenzing_tpu.obs.attrib.xplane",
         str(tmp_path)], cwd=ROOT, capture_output=True, text=True,
        timeout=300, env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert p.returncode == 0, p.stderr
    assert "bench.dispatch" in p.stdout and "slice" in p.stdout
    assert "RuntimeWarning" not in p.stderr
    assert xplane.main([]) == 2
