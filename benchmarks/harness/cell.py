"""One run of one cell: set-up, window, epilogue, result.

Driven by data: the cell names a configuration and a traffic mix; the
configuration's file names a builder and a plain reference; the mix's file
names a solver adapter and its parameters; each per-layer metric is a reader
of its own.  This module holds the name of none of them.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import time
from pathlib import Path
from types import SimpleNamespace

from benchmarks.harness import clock as clock_mod
from benchmarks.harness import trace as trace_mod
from benchmarks.harness.peaks import peaks_for
from benchmarks.harness.stack import Deadline, Spans, build_stack

HERE = Path(__file__).resolve().parent.parent  # the benchmark's directory
ROOT = HERE.parent                             # the checkout


class Refused(RuntimeError):
    """The run cannot be made here (no chip, too few, unknown kind): exit
    non-zero, print no result."""


def say(msg: str) -> None:
    print(msg, flush=True)


# -- data -------------------------------------------------------------------

def load_module(kind: str, name: str):
    """``benchmarks/<kind>/<name>.py`` as a module of its own."""
    path = HERE / kind / f"{name}.py"
    if not path.is_file():
        raise Refused(f"no {kind[:-1]} {name!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.{kind}.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str) -> SimpleNamespace:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next((w for w in bench["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        raise Refused(f"no cell {workload!r} in BENCHMARK.json")
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == cell["config"])
    config = json.loads((ROOT / cfg_entry["file"]).read_text())
    mix = json.loads((HERE / "mixes" / f"{cell['traffic']}.json").read_text())

    def applies(metric):
        return workload in metric.get("workloads", [workload])

    end_to_end = [m for m in bench["end_to_end"] if applies(m)]
    reported = {m["name"] for m in end_to_end}
    # a per-layer metric is read where the end-to-end metric it moves is
    # reported, in the cells it lists or, where it lists none, in all
    return SimpleNamespace(
        name=workload, chips=int(cell["chips"]), config=config, mix=mix,
        end_to_end=end_to_end,
        per_layer=[m for m in bench["per_layer"]
                   if applies(m) and m["moves"] in reported])


def toy_shapes(config: dict) -> dict:
    """``config`` at its ``rehearse`` shapes (CPU rehearsal and tests)."""
    return {**config, "shapes": {**config["shapes"],
                                 **config.get("rehearse", {})}}


# -- the device ---------------------------------------------------------------

def find_devices(chips: int, rehearse: bool):
    import jax

    devs = jax.devices()
    platform = devs[0].platform
    if rehearse:
        if platform != "cpu":
            raise Refused("--rehearse-cpu is for JAX_PLATFORMS=cpu")
    elif platform != "tpu":
        raise Refused(f"needs a TPU, JAX found platform {platform!r}")
    if len(devs) < chips:
        raise Refused(f"cell needs {chips} chip(s), JAX found {len(devs)}")
    if not rehearse:
        peaks_for(devs[0].device_kind)  # unknown kind: UnknownDeviceError
    return devs[:chips]


def memory_peak(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def persistent_cache(on: bool) -> None:
    """Set-up's programs are read from and written to the persistent cache;
    the window's are not (a user's search meets candidates it has never
    compiled, and a second run on one seed must not find the first's)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    jax.config.update("jax_enable_compilation_cache", bool(on))
    cc.reset_cache()


# -- tracing ------------------------------------------------------------------

class SliceTracer:
    """Profiles a slice in mid-window.  Started and stopped between two
    candidates (the wrapper's ``on_boundary``), so the slice holds whole
    candidates; tracing the whole window would bring back too much."""

    def __init__(self, out_dir: Path, spans: Spans, start_after: float,
                 length: float):
        self.out_dir = out_dir
        self.spans = spans
        self.start_after = start_after
        self.length = length
        self.t_open = None
        self.t_started = None
        self.done = False

    def open(self, now: float) -> None:
        self.t_open = now

    def on_boundary(self, now: float) -> None:
        if self.done or self.t_open is None:
            return
        if self.t_started is None:
            if now - self.t_open >= self.start_after:
                start_trace(self.out_dir)
                self.spans.annotate = True
                self.t_started = now
        elif now - self.t_started >= self.length:
            self.stop()

    def stop(self) -> None:
        if self.t_started is not None and not self.done:
            import jax

            self.spans.annotate = False
            jax.profiler.stop_trace()
        self.done = True


def start_trace(out_dir: Path) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0   # no Python call stacks: they swamp it
    opts.host_tracer_level = 2     # the benchmark's own annotations
    jax.profiler.start_trace(str(out_dir), profiler_options=opts)


# -- one run --------------------------------------------------------------------

def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_process: float, rehearse: bool = False, devices=None,
             cell=None, builder=None):
    """Returns the result object (the caller prints it).  ``devices``,
    ``cell`` and ``builder`` may be handed in by a test; a run from the
    command line finds them by name."""
    wall = time.perf_counter
    cell = cell if cell is not None else load_cell(workload)
    config, mix = cell.config, cell.mix
    if devices is None:
        devices = find_devices(cell.chips, rehearse)
    import jax

    if rehearse:
        config = toy_shapes(config)

    # ---- set-up ---------------------------------------------------------
    from tenzing_tpu.bench.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache(0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    persistent_cache(True)
    builder = builder or load_module("builders", config["builder"])
    reference = (load_module("references", config["reference"])
                 if config.get("reference") else None)
    solver = load_module("solvers", mix["solver"])
    t_import = wall()
    built = builder.build(config, seed, devices, reference)
    built.executor.init_bufs = committed(built.executor.init_bufs)
    t_built = wall()
    spans = Spans(wall)
    out_dir = HERE / "out" / f"{workload}.seed{seed}"
    tracer = None
    if trace:
        tracer = SliceTracer(out_dir / "window", spans,
                             start_after=0.25 * seconds,
                             length=min(10.0, 0.25 * seconds))
    bench, verifier, prefetcher, resilient = build_stack(
        built.executor, built.graph, spans,
        on_boundary=tracer.on_boundary if tracer else None)
    try:
        from tenzing_tpu.bench.benchmarker import BenchOpts

        # naive through the whole stack once (verifier, first call, fetch
        # calibration), and its one-shot program for the epilogue's check
        resilient.benchmark(
            built.naive, BenchOpts(n_iters=1, max_retries=1,
                                   target_secs=1e-4))
        t_naive = wall()
        naive_out = built.executor.run(built.naive)
        jax.block_until_ready(naive_out)
        built.precompile_check(naive_out)
        del naive_out
        setup_s = wall() - t_process
        say(f"set-up {setup_s:.3f} s: start-up and imports "
            f"{t_import - t_process:.3f}, data and graph "
            f"{t_built - t_import:.3f}, naive through the stack "
            f"{t_naive - t_built:.3f}, naive once and the reference's "
            f"programs {wall() - t_naive:.3f} (compile cache: {cache_dir})")

        # ---- window -------------------------------------------------------
        persistent_cache(False)
        ex = built.executor
        count0, secs0 = ex.compile_count, ex.compile_secs
        ctx = SimpleNamespace(graph=built.graph, bench=bench,
                              verifier=verifier, prefetcher=prefetcher,
                              hints=built.hints, seed=seed)
        bench.open(seconds)
        if tracer:
            tracer.open(bench.t_open)
        ended = "solver returned"
        try:
            solver.run(ctx, mix["params"])
        except Deadline:
            ended = "deadline"
        finally:
            bench.close()
            t_closed = wall()
            if tracer:
                tracer.stop()
    finally:
        prefetcher.close()
    count1, secs1 = ex.compile_count, ex.compile_secs
    done = bench.in_window()
    ok = [c for c in done if "error" not in c]
    raised = {c["key"] for c in done if "error" in c}
    refused = set(verifier.refused)
    n_failed = len(raised | refused)
    n_attempted = len(ok) + n_failed
    if not ok:
        raise RuntimeError("no candidate completed inside the window: "
                           "nothing to report")
    t_last = max(c["t1"] for c in done)
    span_s = t_last - bench.t_open
    rate_s = rate_seconds(ok, t_last, ended == "deadline",
                          min(t_closed, bench.deadline)) - bench.t_open
    measure_s = sum(min(b, t_last) - a for name, a, b, _ in spans.items
                    if name == "measure" and bench.t_open <= a < t_last)
    say(f"window {seconds:g} s ended by {ended} after "
        f"{t_closed - bench.t_open:.3f} s: {len(ok)} completed, "
        f"{n_failed} failed, last completion at {span_s:.3f} s, rate over "
        f"{rate_s:.3f} s")

    # ---- epilogue: the benchmark's own clock ------------------------------
    t_epilogue = wall()
    finalists = [(f"finalist{i}", order)
                 for i, (order, _) in enumerate(bench.finalists())]
    timed = time_schedules(ex, finalists + [("naive", built.naive)], wall)
    t_timed = wall()
    naive_clock = timed[-1]
    best_i = min(range(len(finalists)), key=lambda i: timed[i]["iter_s"])
    best_clock, best_order = timed[best_i], finalists[best_i][1]
    traced = None
    if trace:
        traced = trace_epilogue(out_dir, ex.prepare_n(best_order),
                                best_clock["n"], best_clock["n4"],
                                keep=rehearse)
    peak_bytes = memory_peak(devices)
    t_traced = wall()

    # ---- correct ------------------------------------------------------------
    compared = compare(ex, built.check, verifier.inner,
                       [("naive", built.naive)] + finalists,
                       {c["label"]: c["n"] for c in timed}, seed)
    correct = all(c["value"] <= c["limit"] for c in compared)
    say(f"epilogue {wall() - t_epilogue:.3f} s: clocks "
        f"{t_timed - t_epilogue:.3f}, traced finalist "
        f"{t_traced - t_timed:.3f}, compared {wall() - t_traced:.3f}")

    # ---- result -------------------------------------------------------------
    record = {
        "cell": cell.name, "seed": seed, "config": config, "mix": mix,
        "window": {"seconds": seconds, "ended_by": ended, "span_s": span_s,
                   "rate_s": rate_s,
                   "measure_s": measure_s, "n_completed": len(ok),
                   "n_failed": n_failed, "n_attempted": n_attempted,
                   "candidates": [{k: v for k, v in c.items() if k != "key"}
                                  for c in bench.candidates]},
        "executor": {"first_calls": count1 - count0,
                     "first_call_secs": secs1 - secs0},
        "epilogue": {"best": best_clock, "naive": naive_clock,
                     "clocks": timed},
        "cost": built.cost,
        "peaks": None if rehearse else peaks_for(devices[0].device_kind),
        "trace": traced,
        "setup_s": setup_s,
    }
    end_to_end = {
        "evals_per_s": len(ok) / rate_s,
        "best_iter_ms": best_clock["iter_s"] * 1e3,
        "setup_s": setup_s,
    }
    metrics = {}
    if trace:
        for m in cell.per_layer:
            reader = load_module("layer_metrics", m["name"])
            value = reader.read(record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": end_to_end[m["name"]],
                                  "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak_bytes}
    result = {"correct": bool(correct), "attempted": n_attempted,
              "failed": n_failed, "metrics": metrics, "device": device}
    if trace and traced and traced.get("window"):
        w = traced["window"]
        device["busy_s"], device["window_s"] = w["busy_s"], w["window_s"]
        result["breakdown"] = {"device_ops": w["device_ops"],
                               "idle_gaps": w["idle_gaps"]}
    # last in the line: every number compared, beside its limit
    result["compared"] = {f"{c['schedule']}.{c['name']}":
                          [c["value"], c["limit"]] for c in compared}
    out_dir.mkdir(parents=True, exist_ok=True)
    (out_dir / f"record.trace{int(trace)}.json").write_text(
        json.dumps({"record": record, "compared": compared,
                    "result": result}, indent=1, default=str))
    return result


def rate_seconds(ok: list, t_last: float, by_deadline: bool,
                 t_end: float) -> float:
    """The time at which the rate's span ends.  A solver that returned on its
    own: the last completion.  A window the deadline ended: the candidate in
    flight is cut short, and is left out with the time it had, but only as
    much of it as the longest completed candidate took; whatever it had
    beyond that counts, so a candidate that stalls until the deadline makes
    the rate worse (a count over the whole window instead would step by a
    tenth where a window holds ten candidates)."""
    if not by_deadline:
        return t_last
    longest = max(c["t1"] - c["t0"] for c in ok)
    return max(t_last, t_end - longest)


def time_schedules(ex, labelled, wall) -> list:
    """The two-point clock's reading of each ``(label, schedule)``."""
    timed = []
    for label, order in labelled:
        c = clock_mod.two_point(ex.prepare_n(order), clock=wall)
        c["label"] = label
        timed.append(c)
        say(f"clock {label}: iter {c['iter_s'] * 1e3:.4f} ms, fixed "
            f"{c['fixed_s'] * 1e3:.3f} ms per dispatch (n={c['n']},"
            f" {c['n4']})")
    return timed


def timed_program(ex, order):
    """The object the window's measurements and the clock dispatch for
    ``order``: the executor's repeat-n program ``(bufs, n) -> (fence,
    host_outs)``, as ``prepare_n`` keeps it.  The runner ``prepare_n`` hands
    out fetches the fence and drops it, so the benchmark takes the program
    from the executor's table (PERF.md, for the ``tracing`` issue: a public
    way to it and to its final buffers)."""
    from tenzing_tpu.core.serdes import sequence_to_json_str

    table = getattr(ex, "_cache", {})
    key = "n:" + sequence_to_json_str(order)
    if key not in table:
        raise RuntimeError(
            "correct needs the executor's repeat-n program of a schedule, a "
            "callable (bufs, n) -> (fence, host_outs) under "
            "TraceExecutor._cache['n:' + schedule JSON] (PERF.md, section 7)")
    return table[key]


def committed(bufs: dict) -> dict:
    """Each buffer committed to where it lies.  Whatever is derived from a
    committed array is committed, the probe and a one-shot program's outputs
    too, and ``jax.jit`` compiles anew for arguments that differ in that: so
    the window's own buffers are committed from the start, and the probe
    finds the executable the window timed."""
    import jax

    return {k: jax.device_put(v, v.sharding) for k, v in bufs.items()}


@functools.lru_cache(maxsize=None)
def probe_fill(shape, dtype, low: int, sharding):
    """The compiled program that draws one probe buffer: whole numbers
    ``low`` to ``low + 4`` in ``dtype``, each device drawing its own shard of
    ``sharding`` (the generator's values do not depend on the sharding), so
    no device ever holds a sharded buffer's global shape."""
    import jax

    def fill(key):
        return jax.random.randint(key, shape, low, low + 5).astype(dtype)

    key = jax.eval_shape(lambda: jax.random.key(0))
    return jax.jit(fill, out_shardings=sharding).lower(key).compile()


def probe_fills(bufs: dict) -> dict:
    """``{name: (position, program)}`` for the floating buffers of ``bufs``:
    a buffer of up to 2**20 elements takes 0 to 4, a larger one -2 to 2,
    drawn in device memory under the buffer's own sharding."""
    import jax.numpy as jnp

    fills = {}
    for i, name in enumerate(sorted(bufs)):
        v = bufs[name]
        if jnp.issubdtype(v.dtype, jnp.floating):
            device = next(iter(v.sharding.device_set)).default_memory().kind
            fills[name] = (i, probe_fill(
                v.shape, v.dtype, 0 if v.size <= 2 ** 20 else -2,
                v.sharding.with_memory_kind(device)))
    return fills


def probe_buffers(bufs: dict, seed: int) -> dict:
    """Buffers shaped, typed and placed as ``bufs``, the floating ones filled
    with small whole numbers drawn from the seed (index buffers stay as they
    are).  Sums and products of such numbers are exact in float32 in whatever
    order they are taken, so two programs that should leave the same state
    give the same fence to the last bit.  A buffer of up to 2**20 elements
    takes 0 to 4, a larger one -2 to 2: its sum is then as large as stays
    exact, so that a fault moves the fence by more than the rounding of what
    else the fence adds (an index buffer's sum can reach 2**31)."""
    import jax

    key = jax.random.key(seed & 0xFFFFFFFF)
    out = dict(bufs)
    for name, (i, fill) in probe_fills(bufs).items():
        # placed as the buffer is (a pinned-host buffer moves there)
        out[name] = jax.device_put(fill(jax.random.fold_in(key, i)),
                                   bufs[name].sharding)
    return out


def timed_fence_gap(ex, order, n: int, probe: dict) -> float:
    """The timed program against the one-shot program of the same schedule,
    on the probe: the fence the timed program returns after ``n`` repeats,
    less the fence the same program returns for the one-shot program's
    outputs after no repeat.  Both iterations are idempotent, so the two
    states are the same and the gap is 0; a repeat-n program that skips an
    operation, loses its carry or never iterates leaves another state.  The
    fence is a sum, so it does not see a value that lands in the wrong cell:
    that the one-shot comparison sees, on the same operations.  ``probe`` is
    taken over and emptied once the one-shot program has run on it: the
    zero-repeat call then finds two sets of buffers on a device beside the
    program's own temporaries, not three (PERF.md, PR 27)."""
    import jax
    import jax.numpy as jnp

    f = timed_program(ex, order)
    variants = getattr(f, "_cache_size", lambda: None)
    before = variants()
    after_n = float(jax.device_get(f(probe, jnp.int32(n))[0]))
    once = ex.compile(order)(probe)
    once = {k: jax.device_put(v, probe[k].sharding) for k, v in once.items()}
    probe.clear()
    after_one = float(jax.device_get(f(once, jnp.int32(0))[0]))
    if variants() != before:
        raise RuntimeError("the probe made jax.jit compile the timed "
                           "program anew: it is not the timed object")
    return abs(after_n - after_one)


def compare(ex, check, verify, schedules, repeats: dict, seed: int) -> list:
    """Every number ``correct`` rests on, each printed beside its limit.  For
    naive and each finalist: one iteration of the one-shot program against
    the plain reference on the run's data; the timed program itself, at the
    repeat count the clock timed it at, against that one-shot program on
    the probe; and the verifier's verdict on each finalist."""
    # the probe's programs go through the persistent cache (off since the
    # window opened): only a checkout's first run compiles them, and none
    # of it is set-up
    persistent_cache(True)
    try:
        probe_fills(ex.init_bufs)
    finally:
        persistent_cache(False)
    compared = []
    for label, order in schedules:
        out = ex.run(order)
        compared += [{**c, "schedule": label} for c in check(out)]
        del out
        compared.append({"name": "timed_fence_gap", "schedule": label,
                         "value": timed_fence_gap(
                             ex, order, repeats[label],
                             probe_buffers(ex.init_bufs, seed)),
                         "limit": 0})
        if label != "naive":
            compared.append({"name": "verifier_rejections", "schedule": label,
                             "value": 0 if verify(order).ok else 1,
                             "limit": 0})
    for c in compared:
        good = c["value"] <= c["limit"]
        say(f"compared {c['schedule']}.{c['name']}: {c['value']!r} "
            f"(limit {c['limit']!r}) {'ok' if good else 'NOT CORRECT'}")
    return compared


def trace_epilogue(out_dir: Path, run_n, n: int, n4: int,
                   keep: bool) -> dict:
    """Reduce the window's slice, then profile the best finalist at the
    clock's two repeat counts: the device's own time for each dispatch.
    The traces (some 100 MB a run) are deleted once reduced, unless
    ``keep`` (a rehearsal keeps them for ``tests/trace_dump.py``)."""
    import shutil

    import jax

    window = {}
    try:
        window = trace_mod.reduce_window(
            trace_mod.load_xplane(out_dir / "window"))
    except FileNotFoundError as e:
        say(f"trace: {e} (window too short for a slice)")
    start_trace(out_dir / "finalist")
    try:
        run_n(n)
        run_n(n4)
    finally:
        jax.profiler.stop_trace()
    mods = trace_mod.module_seconds(
        trace_mod.load_xplane(out_dir / "finalist"), longest=2)
    if not keep:
        for d in ("window", "finalist"):
            shutil.rmtree(out_dir / d, ignore_errors=True)
    return {"window": window, "finalist_modules": mods,
            "finalist_n": [n, n4]}
