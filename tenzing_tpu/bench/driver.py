"""The library driver: the search→gate→JSON loop as a callable.

The schedule-serving subsystem (``tenzing_tpu/serve/``, docs/serving.md)
drains its queued work items through this loop, and ``bench.py`` is a thin
argparse shim over it:

* :class:`DriverRequest` — the typed request, field-for-field the CLI's
  argparse namespace (defaults asserted equal by tests/test_driver.py, so
  the two can never drift);
* :func:`run` — the whole loop; returns a :class:`DriverResult` whose
  ``verdict`` dict, serialized, is the JSON line ``bench.py`` prints.
  :func:`_run` calls one function per phase, in order: open (device check,
  build), learn, tile planting, :func:`assemble_stack`, naive, incumbents
  and recorded warm start, tree search, climbs, paired screen and final
  batch, integrity gate, the winner's provenance reports
  (:func:`winner_report`), dump and stamp;
* what a workload is (builders, ``graph_for``, ``workload_shape``, naive
  schedule, incumbents, climb policies) is one row of
  ``bench/workloads.py``; its names are re-exported here;
* :exc:`DriverConfigError` — an invalid request (the shim maps it to
  ``argparse.error``).

The search is anytime: greedy domain incumbents (for halo, an engine x
lane-count grid), the best recorded schedules from previous runs' databases
(``--seed-csv``, bench/recorded.py — cross-run search memory ranked by
in-file paired ratio), and a FastMin MCTS that explores at CHEAP measurement
cost — search-time numbers only steer the tree — followed by drift-immune
hill-climbs seeded from the best recorded schedule's menu choices and from
the strongest hand disciplines.  Candidate selection and the verdict are
both *paired decorrelated batches* (:func:`_screen_and_final`).
``vs_baseline`` is the best finalist's **paired speedup** (median of
naive[k]/cand[k] with a bootstrap CI, utils.numeric.paired_speedup) — drift
common to both schedules cancels instead of masquerading as, or drowning, a
schedule difference; a win additionally requires the CI to exclude 1.0.

Prints ONE JSON line:
  {"metric": ..., "value": <best pct50, us>, "unit": "us",
   "vs_baseline": <naive_pct50 / best_pct50>}

Every verdict carries ``device: {platform, kind, count}``, read from the
devices the run used.  A run that is not ``--smoke`` measures on a TPU or not
at all: on any other backend, and on backend-init failure (one attempt — the
chip is attached directly, so a failed init is final), the verdict is still
one parseable JSON line, with an ``error`` field, and the CLI exits non-zero.

``--smoke`` is the one named CPU mode: a tiny configuration for tests and
rehearsal, never a device metric.
"""

from __future__ import annotations

import dataclasses
import json
import os as _os_mod
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

# what a workload is lives one layer down; the names it had here stay
# importable from here (bench.py, the benchmark's builders, examples)
from tenzing_tpu.bench.workloads import (  # noqa: F401
    ALIAS_UNPACK, BUILDERS, WORKLOADS, DriverConfigError, Workload,
    alias_unpack_choice, build_attn, build_halo, build_moe, build_spmv,
    first_decision_schedule, generic_xla_prefer, graph_for,
    halo_alias_prefer, metric_for, moe_bf16_prefer, naive_schedule,
    nbytes_of, recorded_prefer, search_lanes, workload_cost, workload_shape,
)

# the CLI's relative default globs (--seed-csv) resolve against the repo
# root, where bench.py lives — anchored here so the extracted driver keeps
# resolving the same files the monolith did
REPO_ROOT = _os_mod.path.dirname(_os_mod.path.dirname(
    _os_mod.path.dirname(_os_mod.path.abspath(__file__))))


@dataclass
class DriverRequest:
    """The driver's typed request — field-for-field the ``bench.py``
    argparse namespace, with identical defaults (tests/test_driver.py
    asserts the parser and this dataclass agree, so CLI and API can never
    drift).  Construct with keyword overrides and hand to :func:`run`;
    the shim builds one via ``DriverRequest(**vars(args))``."""

    smoke: bool = False
    workload: str = "halo"
    moe_tokens: int = 8192
    m: Optional[int] = None
    spmv_bw: Optional[int] = None
    halo_n: int = 512
    lanes: Optional[int] = None
    mcts_iters: int = 56
    iters: int = 20
    search_iters: int = 6
    climb_budget: int = 44
    prefetch_compiles: int = 2
    dump_csv: Optional[str] = None
    trace_out: Optional[str] = None
    metrics_json: Optional[str] = None
    seed_csv: Optional[str] = None
    seed_topk: int = 3
    learn_train: Optional[List[str]] = None
    learn_trace: Optional[List[str]] = None
    learn_model: Optional[str] = None
    learn_screen: bool = False
    checkpoint: Optional[str] = None
    resume: bool = False
    measure_timeout: Optional[float] = None
    inject_faults: Optional[str] = None
    inject_hang_secs: float = 60.0
    profile_winner: bool = False
    profile_repeats: int = 7
    fuse_winner: bool = False
    fuse_search_tiles: bool = False
    chunk: bool = False
    synth_collectives: bool = False
    no_verify: bool = False
    verify_tol: float = 0.02
    search_workers: int = 0
    measure_batch: int = 0

    def to_json(self) -> Dict[str, Any]:
        """A JSON-ready dict (the serve work-queue payload —
        ``DriverRequest(**item)`` round-trips)."""
        return dataclasses.asdict(self)


@dataclass
class DriverResult:
    """What :func:`run` returns: the verdict dict whose ``json.dumps`` is
    the driver JSON line (key order preserved — the shim's print is
    byte-identical to the monolith's)."""

    verdict: Dict[str, Any] = field(default_factory=dict)

    def to_json_line(self) -> str:
        return json.dumps(self.verdict)


def device_stamp(devs) -> Dict[str, Any]:
    """``{platform, kind, count}`` of the devices a run used — the block
    every verdict carries so a number can never be read without the device
    it came from."""
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _written_buffers(seq) -> set:
    """Names of the buffers ``seq``'s ops (re)define."""
    return {b for op in seq.vector() for b in op.writes()}


def _mismatched_outputs(out_a, out_b, tol: float, names=None) -> List[str]:
    """THE numeric-agreement policy of the result-integrity gate: names
    (shared by both output dicts, restricted to ``names`` when given) whose
    arrays differ in shape or fail
    ``allclose(rtol=tol, atol=tol*1e-3, equal_nan=True)`` in float64.
    Used by the winner-vs-naive gate and the fused-vs-stepped gate — one
    copy, so a tolerance or NaN-policy change cannot split their
    semantics."""
    import jax as _jax
    import numpy as _np

    mismatched = []
    common = set(out_a) & set(out_b)
    if names is not None:
        common &= set(names)
    for name in sorted(common):
        a = _np.asarray(_jax.device_get(out_a[name]), dtype=_np.float64)
        b = _np.asarray(_jax.device_get(out_b[name]), dtype=_np.float64)
        if a.shape != b.shape or not _np.allclose(
                a, b, rtol=tol, atol=tol * 1e-3, equal_nan=True):
            mismatched.append(name)
    return mismatched


class _RunScope:
    """Per-call registration bookkeeping for :func:`run`.

    The monolith registered its crash-path handlers (telemetry flush,
    prefetcher shutdown, checkpoint cursor stamps) with ``atexit`` and
    the signal trap and simply leaked them — correct for a one-shot CLI
    process, wrong for the library API a work-queue drainer calls in a
    loop: item N's SIGINT must not fire item N-1's handlers (stamping
    ``interrupted`` into checkpoints of runs that completed cleanly),
    and each run's closures must not pin its executor and buffers until
    process exit.  The scope registers exactly like the monolith while
    the run is live, then on close runs each exit finalizer once (they
    are all idempotent — the same calls the success path already makes
    explicitly) and unregisters everything."""

    def __init__(self):
        self._finalizers: list = []
        self._traps: list = []

    def on_exit(self, fn) -> None:
        """Run ``fn`` at scope close AND (as a crash backstop while the
        scope is live) at interpreter exit."""
        import atexit

        atexit.register(fn)
        self._finalizers.append(fn)

    def on_trap(self, fn) -> None:
        """Run ``fn`` on SIGINT/SIGABRT while the scope is live."""
        from tenzing_tpu.utils import trap

        trap.register_handler(fn)
        self._traps.append(fn)

    def close(self) -> None:
        import atexit

        from tenzing_tpu.utils import trap

        # LIFO, like the atexit machinery these used to ride on: the
        # prefetcher's close() (registered after write_telemetry) must
        # finalize the pipeline counters BEFORE the telemetry flush
        # writes them out on a crash path
        for fn in reversed(self._finalizers):
            try:
                fn()
            except Exception as e:  # a failed finalizer must not mask
                sys.stderr.write(   # the run's own result/exception
                    f"driver: finalizer {getattr(fn, '__name__', fn)!r} "
                    f"failed ({type(e).__name__}: {str(e)[:120]})\n")
        for fn in self._finalizers:
            atexit.unregister(fn)
        for fn in self._traps:
            trap.unregister_handler(fn)
        self._finalizers.clear()
        self._traps.clear()


def run(req: DriverRequest) -> DriverResult:
    """Execute the whole search→gate→verdict loop for ``req``.

    Safe to call repeatedly in one process (the work-queue drain loop,
    docs/serving.md): every atexit/signal registration is scoped to the
    call and disposed on return, so runs cannot stamp each other's
    checkpoints or accumulate handlers.  One process-wide caveat: a
    ``smoke`` request pins ``jax_platforms`` to CPU for the remainder of
    the process (JAX backend selection is process-global and sticks
    after first initialization) — drain smoke and full-size items in
    separate processes."""
    # a shallow copy: run() resolves defaults in place (seed_csv globs,
    # smoke iteration caps) exactly like the monolith mutated its argparse
    # namespace, without surprising a caller who reuses the request
    args = dataclasses.replace(req)
    if args.workload not in WORKLOADS:
        # validate BEFORE the backend probe: argparse choices protect
        # the CLI, but a library caller (a drainer on a hand-edited work
        # item) must get the API's config error, not a KeyError after a
        # wasted init/retry cycle — or worse, a backend-failure verdict
        # mislabeled into metric_for's fall-through metric series
        raise DriverConfigError(f"unknown workload {args.workload!r}")
    if args.resume and not args.checkpoint:
        # silently ignoring resume would re-measure a multi-hour search
        # from scratch while the output JSON claims a resume happened
        raise DriverConfigError("--resume requires --checkpoint DIR")
    # adopt a parent process's trace context (obs/context.py): a drain
    # child spawned by the daemon — or a bare bench.py run under
    # TENZING_TRACE_CONTEXT — stamps every span/event with the
    # originating query's trace_id, so its bundle stitches into the
    # fleet trace.  Installed as the process default (worker threads —
    # the prefetch pool — inherit it) and restored on return: run() is
    # called in a loop by in-process drainers.
    from tenzing_tpu.obs import context as _obs_context

    env_ctx = None
    prev_ctx = None
    if _obs_context.current() is None:
        env_ctx = _obs_context.from_env()
        if env_ctx is not None:
            prev_ctx = _obs_context.set_process_default(env_ctx)
    scope = _RunScope()
    try:
        return _run(args, scope)
    finally:
        scope.close()
        if env_ctx is not None:
            _obs_context.set_process_default(prev_ctx)


@dataclass
class Stack:
    """The measurement stack's handles, inside-out (:func:`assemble_stack`)."""

    emp: Any
    injector: Any
    prefetcher: Any
    resilient: Any
    corrupt_injector: Any
    quarantine: Any
    checkpoint: Any
    bench: Any
    verifier: Any  # the soundness gate the resilient layer holds, or None


@dataclass
class _Run:
    """What lives through one :func:`run`: the request, what the opening
    phases built, and the measurement stack.  A phase takes it (and what the
    phase before returned) and returns what the next needs."""

    args: DriverRequest
    row: Workload
    compile_cache_dir: Optional[str]
    # _open: the device's stamp and peaks, the builder's return, its graph
    device: Optional[Dict[str, Any]] = None
    peaks: Any = None
    built: Any = None
    g: Any = None  # with the tile menu planted, once _plant_tiles has run
    surrogate: Any = None
    # _plant_tiles: search platform, executor, --fuse-search-tiles' menu
    plat: Any = None
    ex: Any = None
    tile_menu: Any = None
    tile_planted: bool = False
    stack: Optional[Stack] = None
    # _measure_naive: the verdict's and the search's floors, the baseline
    opts: Any = None
    search_opts: Any = None
    naive_seq: Any = None
    naive: Any = None
    labels: Dict[int, str] = field(default_factory=dict)  # id(sim) -> label
    # _tree_search, _climb: what the dump and the stamp read back
    mcts_screen: Any = None
    search_bench: Any = None
    distributed_stats: Any = None
    # --profile-winner's analysis of the reported schedule, for the reports
    # after it: re-stepping a multi-GB workload per op is minutes of waste
    profiled_attrib: Any = None
    # per-lane Gantt tracks from --profile-winner (chrome trace-event
    # dicts, obs/attrib/explain.py): filled late in the run, exported by
    # write_telemetry into the same Perfetto bundle as the PR-1 spans
    attrib_extra: list = field(default_factory=list)
    _telemetry_done: bool = False

    def write_telemetry(self):
        """Archive the telemetry bundle once.  Registered with atexit (for
        crashes: the interpreter still exits normally after an unhandled
        exception) AND with utils.trap (for SIGINT/SIGABRT: the trap handler
        re-raises via SIG_DFL, which kills the process without running
        atexit) so an interrupted search — the run where the trace matters
        most — still archives everything recorded so far.  The explicit call
        on the success path just makes the files land before the final JSON
        line.  Filenames are rank-qualified past rank 0 so multi-host runs
        writing to a shared directory do not clobber each other's bundles."""
        import os

        from tenzing_tpu import obs

        args = self.args
        if self._telemetry_done:
            return
        self._telemetry_done = True
        rank = obs.get_tracer().rank
        sfx = "" if rank == 0 else f".rank{rank}"
        if args.trace_out:
            os.makedirs(args.trace_out, exist_ok=True)
            obs.write_jsonl(obs.get_tracer(),
                            os.path.join(args.trace_out, f"trace{sfx}.jsonl"))
            obs.write_chrome_trace(
                obs.get_tracer(),
                os.path.join(args.trace_out, f"trace{sfx}.json"),
                extra_events=self.attrib_extra or None)
            sys.stderr.write(f"trace bundle: {args.trace_out}\n")
        if args.metrics_json:
            # block=False: this runs from the signal trap, where the
            # interrupted thread may hold an instrument lock — the
            # non-blocking read falls back to GIL-atomic copies instead of
            # deadlocking the Ctrl-C path (the exporters above are
            # non-blocking by construction, obs/export.py)
            with open(args.metrics_json + sfx, "w") as f:
                json.dump(obs.get_metrics().to_json(block=False), f,
                          indent=2, sort_keys=True)
            sys.stderr.write(f"metrics: {args.metrics_json}{sfx}\n")

    def error_verdict(self, msg: str, **extra) -> DriverResult:
        """No measurement was made: still one parseable line, carrying
        ``error`` (the CLI exits non-zero on it)."""
        self.write_telemetry()
        return DriverResult(verdict={
            "metric": self.row.metric(self.args),
            "value": -1.0,
            "unit": "us",
            "vs_baseline": 0.0,
            "error": msg,
            **extra,
        })

    def with_tile1(self, seq):
        """An out-of-graph sequence (naive_order/greedy helpers, recorded
        rows predating the tile node) completed with the ``fuse_tile.t1``
        directive the planted choice requires — without it the verifier
        would reject the schedule as an unresolved choice.  The directive
        goes AFTER the leading start sentinel: the planted choice is a
        successor of Start, so a directive at position 0 would violate
        the projected start->directive edge and fail verification."""
        if not self.tile_planted:
            return seq
        from tenzing_tpu.core.sequence import Sequence as _Seq
        from tenzing_tpu.runtime.fused import FuseTile, TILE_PREFIX

        ops_ = list(seq.vector())
        if any(op.name().startswith(TILE_PREFIX) for op in ops_):
            return seq
        at = 1 if ops_ and ops_[0].name() == "start" else 0
        return _Seq(ops_[:at] + [FuseTile(1)] + ops_[at:])

    def label_of(self, s) -> str:
        """'greedy-host-8l' for a labeled incumbent, 'climb/<engine>' for a
        hill-climb candidate, 'mcts/<engine>' for an MCTS rollout — the
        screen/final printouts must distinguish the entries they compare."""
        base = self.labels.get(id(s), "mcts")
        if base in ("mcts", "climb", "climb-tip"):
            names = [op.desc() for op in s.order.vector()]
            engine = "rdma" if any(".rdma" in n for n in names) else "host"
            return f"{base}/{engine}"
        return base


@dataclass
class _Pick:
    """The paired screen's and final batch's answer, then the integrity
    gate's: the number the verdict reports and the schedule it belongs to."""

    vs: float
    value_us: float
    screen_opts: Any
    fin_opts: Any
    finals: list = field(default_factory=list)
    top: list = field(default_factory=list)
    best_i: int = 0
    integrity: Optional[Dict[str, Any]] = None
    # gate outputs stashed for reuse: the fused phase compares against the
    # stepped program's outputs, which the gate just computed — re-running
    # a multi-GB workload's program for the same answer is pure waste
    gate_outs: Dict[int, Dict[str, Any]] = field(default_factory=dict)
    reported_seq: Any = None

    def winner(self):
        """The finalist the verdict reports, or None when it is naive's."""
        if self.top and self.finals and self.vs > 1.0:
            return self.top[self.best_i]
        return None


def _run(args: DriverRequest, scope: _RunScope) -> DriverResult:
    """The phases of a run, in order."""
    run = _open(args, scope)
    if isinstance(run, DriverResult):
        return run
    if args.learn_train:
        return _learn_train(run)
    run.surrogate = _load_surrogate(run)
    measure_ex = _plant_tiles(run)
    run.stack = assemble_stack(measure_ex, run.g, args, run.surrogate, scope)
    _measure_naive(run)
    # anytime search: heuristic incumbents first, then the directed search
    incumbents, seed_paths, rollout_policy = _measure_incumbents(run)
    recorded = _recorded_warm_start(run, incumbents)
    res = _tree_search(run, incumbents, seed_paths, rollout_policy)
    _climb(run, res, incumbents, recorded)
    pick = _screen_and_final(run, res, incumbents)
    _integrity_gate(run, pick)
    reports = _winner_reports(run, res, pick)
    if args.dump_csv:
        _dump_csv(run, res, pick)
    return _stamp(run, pick, reports, len(recorded))


def _open(args: DriverRequest, scope: _RunScope):
    """Smoke defaults, telemetry, the device check and the build: a
    :class:`_Run`, or the error verdict of a run that may not measure."""
    if args.smoke:
        import jax

        jax.config.update("jax_platforms", "cpu")

    from tenzing_tpu.bench.compile_cache import enable_compile_cache

    compile_cache_dir = enable_compile_cache()

    from tenzing_tpu import obs

    if args.trace_out:
        obs.configure(enabled=True)

    run = _Run(args=args, row=WORKLOADS[args.workload],
               compile_cache_dir=compile_cache_dir)
    if args.trace_out or args.metrics_json:
        scope.on_exit(run.write_telemetry)
        scope.on_trap(run.write_telemetry)

    try:
        # one attempt: the chip is attached to this host, so an init failure
        # is final and is reported, not retried
        import jax

        devs = jax.devices()
    except Exception as e:
        return run.error_verdict(f"backend init failed: {e}")
    sys.stderr.write(f"backend: {devs}\n")
    run.device = device = device_stamp(devs)
    if not args.smoke and device["platform"] != "tpu":
        # the numbers this run writes are device metrics: searching 512^3
        # on whatever jax.devices() happens to return would file CPU
        # timings under their name.  --smoke is the one named CPU mode.
        return run.error_verdict(
            f"device refused: a run without --smoke measures on a TPU, but "
            f"jax.devices()[0].platform is {device['platform']!r} "
            f"({device['kind']}); use --smoke for the CPU rehearsal",
            device=device)
    # fraction-of-peak denominators of the device this run measures on
    # (bench/roofline.py PEAKS).  Smoke states no fraction; a chip without
    # a row is an error, never the v5e's numbers under another name.
    if not args.smoke:
        from tenzing_tpu.bench import roofline

        try:
            run.peaks = roofline.peaks_for(device["kind"])
        except roofline.UnknownDeviceError as e:
            return run.error_verdict(str(e), device=device)

    run.built = run.row.build(args)
    run.g = run.built[0]
    return run


def _learn_nbytes(run: _Run) -> Dict[str, int]:
    """Buffer byte sizes feed the surrogate's comm-bytes + analytic-makespan
    features (learn/features.py) — the same map for train and screen, so
    the feature contract holds across the two phases."""
    return nbytes_of(run.built[1])


def _learn_train(run: _Run) -> DriverResult:
    """``--learn-train``: corpus -> features -> ridge ensemble -> model
    JSON, then exit: training is offline (no device measurement), it only
    needs the workload graph to deserialize the recorded schedules against."""
    import glob as _glob

    from tenzing_tpu import obs as _obs
    from tenzing_tpu.learn import train_from_corpus

    args = run.args
    log = lambda m: sys.stderr.write(m + "\n")
    paths = sorted(p for pat in args.learn_train for p in _glob.glob(pat))
    with _obs.get_tracer().span("learn.train", n_files=len(paths)):
        tpaths = (sorted(p for pat in args.learn_trace
                         for p in _glob.glob(pat))
                  if args.learn_trace else None)
        # THE shared training recipe (learn/train.py) — the serving
        # warm path trains through the same call
        model, info = train_from_corpus(
            paths, run.g, nbytes=_learn_nbytes(run), trace_paths=tpaths,
            log=log)
        out = {"metric": f"learn_train_{args.workload}",
               "device": run.device, **info}
        if model is not None and args.learn_model:
            model.save(args.learn_model)
            out["model"] = args.learn_model
            log(f"learn model: {args.learn_model} "
                f"({info['rows']} rows, train spearman "
                f"{out['train_spearman']})")
    run.write_telemetry()
    return DriverResult(verdict=out)


def _load_surrogate(run: _Run):
    """``--learn-screen``: the surrogate benchmarker, or None."""
    args = run.args
    if args.learn_screen and args.learn_model:
        from tenzing_tpu.learn import (
            FEATURE_NAMES,
            RidgeEnsemble,
            SurrogateBenchmarker,
        )

        model = RidgeEnsemble.load(args.learn_model,
                                   expect_features=list(FEATURE_NAMES))
        sys.stderr.write(
            f"learn screen: {args.learn_model} "
            f"({model.n_train} training rows)\n")
        return SurrogateBenchmarker(model, nbytes=_learn_nbytes(run))
    if args.learn_screen:
        sys.stderr.write("learn screen: no --learn-model given — "
                         "screening disabled\n")
    return None


def _plant_tiles(run: _Run):
    """The search platform and executor, and ``--fuse-search-tiles``' menu
    planted in the graph; returns the executor measurements lower through."""
    from tenzing_tpu.core.platform import Platform
    from tenzing_tpu.runtime.executor import TraceExecutor

    args = run.args
    # THE default rule lives in search_lanes() — the serving fingerprint's
    # mesh signature keys on the same call, so the two cannot drift
    run.plat = Platform.make_n_lanes(search_lanes(args))
    if args.smoke:
        # a small tree (the CPU path exists to be cheap)
        args.mcts_iters = min(args.mcts_iters, 12)
    run.ex = ex = TraceExecutor(run.plat, run.built[1])
    # --fuse-search-tiles (ISSUE 10 satellite of the PR-8 backend): plant
    # the megakernel tile menu as a decision node in the choice graph BEFORE
    # the verifier/search are built, so MCTS/DFS/hill-climb search tile
    # counts in-driver (the way tests/test_fused.py drives the library
    # workloads) instead of only sweeping the menu post-verdict.  Every
    # measurement then lowers through the schedule's ``fuse_tile.tN``
    # directive (FusedExecutor reads it back; tiles=None).
    if not args.fuse_search_tiles:
        return ex
    from tenzing_tpu.runtime.fused import FusedExecutor, with_tile_menu

    # the menu needs a complete schedule to partition: the cheap
    # first-decision serialization on one lane (host-side only)
    probe = first_decision_schedule(run.g, None, Platform.make_n_lanes(1))
    # smoke relaxes the traffic floor like tests/test_fused.py
    # (min_tile_bytes=0): toy buffers would prune every count and CI
    # could never exercise the searched tile nodes
    fuse_kw = {"min_tile_bytes": 0} if args.smoke else {}
    run.tile_menu = tile_menu = FusedExecutor(ex, **fuse_kw).plan(
        probe).tile_menu
    if len(tile_menu) > 1:
        run.g = with_tile_menu(run.g, tile_menu)
        run.tile_planted = True
        sys.stderr.write(
            f"fuse-search-tiles: menu {tile_menu} planted in the "
            "choice graph; measurements lower through the searched "
            "directive\n")
        return FusedExecutor(ex, **fuse_kw)
    sys.stderr.write(
        "fuse-search-tiles: tile menu is [1] (no fusible "
        "decomposition survived pruning) — nothing to search\n")
    return ex


def assemble_stack(executor, graph, req: DriverRequest, surrogate,
                   scope: _RunScope) -> Stack:
    """The fault-tolerance stack (docs/robustness.md) over ``executor``,
    inside-out — the one place that orders it:

      EmpiricalBenchmarker            device measurement
      [FaultInjectingBenchmarker]     --inject-faults seeded chaos
                                      (measurement-fault kinds)
      [PrefetchingBenchmarker]        --prefetch-compiles async compile
                                      pipeline: solver hints AOT-compile
                                      in the background, failures surface
                                      on the foreground call so the
                                      resilient layer above classifies /
                                      agrees / quarantines as usual
      ResilientBenchmarker            soundness gate / watchdog /
                                      classified retry / quarantine /
                                      degradation
      [FaultInjectingBenchmarker]     --inject-faults corrupt: schedule
                                      corruption — ABOVE the resilient
                                      layer so its verifier gate sees
                                      (and quarantines) the mutation
      [JournalingBenchmarker]         --checkpoint measurement journal
      CachingBenchmarker              equivalence-keyed cache (also the
                                      --resume restore target)
    """
    from tenzing_tpu.bench.benchmarker import (
        CachingBenchmarker,
        EmpiricalBenchmarker,
    )
    from tenzing_tpu.fault import (
        JournalingBenchmarker,
        Quarantine,
        ResilientBenchmarker,
        SearchCheckpoint,
    )
    from tenzing_tpu.verify import ScheduleVerifier

    emp = EmpiricalBenchmarker(executor)
    verifier = None if req.no_verify else ScheduleVerifier(graph)
    inner_specs, corrupt_specs = [], []
    if req.inject_faults:
        from tenzing_tpu.fault import parse_inject_specs

        specs = parse_inject_specs(req.inject_faults)
        inner_specs = [s for s in specs if s.kind != "corrupt"]
        corrupt_specs = [s for s in specs if s.kind == "corrupt"]
        if corrupt_specs and verifier is None:
            # corruption without the verifier would MEASURE broken
            # schedules — a chaos run that poisons its own archive
            raise DriverConfigError(
                "--inject-faults corrupt: requires the soundness "
                "verifier (drop --no-verify)")
        sys.stderr.write(f"chaos: injecting {req.inject_faults}\n")
    measured_stack = emp
    injector = None
    if inner_specs:
        from tenzing_tpu.fault import FaultInjectingBenchmarker

        injector = FaultInjectingBenchmarker(
            emp, inner_specs, hang_secs=req.inject_hang_secs)
        measured_stack = injector
    ckpt = SearchCheckpoint(req.checkpoint) if req.checkpoint else None
    prefetcher = None
    if req.resume:
        # a resumed run answers journaled measurements without touching the
        # executor (the PR 3 "0 compiles" provenance); background hints
        # would compile programs the journal already answers — keep the
        # resume contract and skip the pipeline.  What stays of it is its
        # width: the tree search draws ``workers`` rollouts ahead
        # (MctsOpts.prefetch), and only a search that draws what the
        # interrupted one drew finds its rollouts in the journal.  So the
        # solvers get a prefetcher of the recorded width, outside the
        # measured stack, with nothing to compile
        width = _recorded_lookahead(ckpt, req)
        sys.stderr.write("prefetch: disabled under --resume (journaled "
                         "answers never compile); the tree search keeps "
                         f"the checkpoint's lookahead of {width}\n")
        if width > 0:
            from tenzing_tpu.bench.pipeline import PrefetchingBenchmarker

            prefetcher = PrefetchingBenchmarker(
                measured_stack, executor=_NothingToCompile(), workers=width)
            scope.on_exit(prefetcher.close)
    elif req.prefetch_compiles > 0:
        from tenzing_tpu.bench.pipeline import PrefetchingBenchmarker

        # ABOVE injection (background compiles are not chaos targets — the
        # injector's per-attempt draws stay keyed to benchmark() calls
        # only) and BELOW the resilient layer (surfaced compile failures
        # ride the normal classify/agree/quarantine path)
        measured_stack = prefetcher = PrefetchingBenchmarker(
            measured_stack, executor=executor,
            workers=req.prefetch_compiles, rank=surrogate)
        # exception paths too (not only the happy-path close below): a
        # fatal mid-search error must not leave queued background compiles
        # draining at interpreter exit — the pool's own shutdown hook joins
        # only AFTER the queue empties (~3.4 s per pending compile), while
        # close() cancels pending first.  Idempotent; SIGINT has the trap.
        scope.on_exit(prefetcher.close)
    quar = Quarantine(ckpt.quarantine_path if ckpt else None,
                      log=lambda m: sys.stderr.write(m + "\n"))
    if len(quar):
        sys.stderr.write(
            f"quarantine: {len(quar)} schedule(s) carried from previous "
            "runs will not be re-measured\n")
    resilient = ResilientBenchmarker(
        measured_stack, timeout_secs=req.measure_timeout, quarantine=quar,
        fallback=surrogate, verifier=verifier)
    guarded = resilient
    corrupt_injector = None
    if corrupt_specs:
        from tenzing_tpu.fault import FaultInjectingBenchmarker

        corrupt_injector = FaultInjectingBenchmarker(
            resilient, corrupt_specs,
            unsound_check=lambda o: not verifier(o).ok)
        guarded = corrupt_injector
    bench = CachingBenchmarker(
        JournalingBenchmarker(guarded, ckpt) if ckpt else guarded)
    if ckpt is not None:
        _open_checkpoint(ckpt, bench, graph, req, scope,
                         lookahead=getattr(prefetcher, "workers", 0))
    return Stack(emp=emp, injector=injector, prefetcher=prefetcher,
                 resilient=resilient, corrupt_injector=corrupt_injector,
                 quarantine=quar, checkpoint=ckpt, bench=bench,
                 verifier=verifier)


class _NothingToCompile:
    """The executor of a resumed run's prefetcher: every program counts as
    compiled, so no hint is ever issued."""

    @staticmethod
    def is_compiled(order) -> bool:
        return True

    @staticmethod
    def precompile(order) -> bool:
        return False


def _recorded_lookahead(ckpt, req: DriverRequest) -> int:
    """How far ahead the interrupted run's tree search drew: the width its
    checkpoint recorded, 0 for a checkpoint from before widths were
    recorded (those searches drew one rollout at a time), and this run's
    ``--prefetch-compiles`` where there is no snapshot to resume from."""
    prior = None
    if ckpt is not None:
        try:
            prior = ckpt.load_state()
        except Exception:  # corrupt snapshot: _open_checkpoint reports it
            pass
    if prior is None:
        return max(0, req.prefetch_compiles)
    return int(prior.get("lookahead", 0))


def _open_checkpoint(ckpt, bench, graph, args: DriverRequest,
                     scope: _RunScope, lookahead: int = 0) -> None:
    """Check ``--checkpoint``'s recorded config against this run's, restore
    its journal under ``--resume``, and register its final snapshots.
    ``lookahead``: the width this run's tree search draws ahead with,
    recorded for the run that resumes it."""
    config = {"workload": args.workload,
              "metric": metric_for(args.workload, args),
              "smoke": bool(args.smoke), "seed_topk": args.seed_topk}
    prior = None
    try:
        prior = ckpt.load_state()
    except Exception as e:  # corrupt snapshot: resume from journal only
        sys.stderr.write(f"checkpoint: state unreadable ({e}); "
                         "journal + quarantine still apply\n")
    if prior is not None and prior.get("config") not in (None, config):
        sys.stderr.write(
            "checkpoint: recorded config differs from this run "
            f"({prior.get('config')} vs {config}); journal rows that "
            "do not resolve against this workload are skipped\n")
    want_inject = args.inject_faults or None
    if args.resume and prior is not None and \
            prior.get("inject") != want_inject:
        # a resumed chaos run whose injection spec disagrees with the
        # one the checkpoint was written under would replay journaled
        # answers from a DIFFERENT fault universe and silently diverge
        # from both the original run and a clean rerun — refuse loudly
        raise DriverConfigError(
            "--resume: this run's --inject-faults "
            f"({want_inject!r}) disagrees with the checkpoint's "
            f"recorded injection spec ({prior.get('inject')!r}); "
            "use the same spec (including seeds) or start a fresh "
            "checkpoint directory")
    if args.resume:
        restored = ckpt.restore_into(
            bench, graph, log=lambda m: sys.stderr.write(m + "\n"))
        sys.stderr.write(
            f"resume: {restored} recorded measurement(s) restored — "
            "already-measured schedules will not touch the device\n")
    ckpt.save_state(config=config, inject=want_inject, lookahead=lookahead)

    # final snapshots: the journal and quarantine are already on disk
    # (appended/rewritten as each measurement landed), so these only
    # stamp the cursor document.  The trap path marks the interrupt
    # (SIG_DFL then kills without running the exit finalizers); a
    # normal return (or crash) marks completion at scope close.
    scope.on_exit(lambda: ckpt.save_state(done=True))
    scope.on_trap(lambda: ckpt.save_state(interrupted=True))


def _measure_naive(run: _Run) -> None:
    """The measurement floors, and the naive incumbent: the
    fully-synchronous serialization on one lane (the reference's
    "sequential ordering on one stream" baseline, BASELINE.json)."""
    from tenzing_tpu.bench.benchmarker import BenchOpts

    args, stack = run.args, run.stack
    # max_retries=2 (library default 10): the runs-test retry loop re-measures
    # the whole series on rejection, and in a slow chip regime that blew
    # a single naive benchmark to 558 s of wall; the verdict comes from the
    # paired batches (which have no retry loop), so the search-phase numbers
    # only need to be cheap, not certified-stationary
    run.opts = BenchOpts(n_iters=max(5, args.iters), max_retries=2,
                         target_secs=0.002 if args.smoke else 0.02)
    # the search phase buys BREADTH with cheap measurements (VERDICT r2 weak
    # #2: 24 iters at full measurement cost explored a 109-node tree of a far
    # larger space); ranking candidates is the paired screening batch's job,
    # so search-time numbers only need to steer the tree
    run.search_opts = BenchOpts(
        n_iters=max(3, args.search_iters),
        max_retries=2,
        target_secs=0.002 if args.smoke else 0.01,
    )
    # a planted tile menu makes the directive part of every complete
    # schedule; the out-of-graph naive builders predate it
    run.naive_seq = naive_seq = run.with_tile1(
        naive_schedule(args.workload, run.g, run.built[3]))
    # the baseline is not a search candidate: exempt it from the
    # identity-keyed candidate-fault kinds (deterministic/corrupt), which
    # would otherwise deterministically kill the run under ~rate of the
    # seeds before the search starts.  Device-fault kinds still apply.
    for inj in (stack.injector, stack.corrupt_injector):
        if inj is not None:
            from tenzing_tpu.bench.benchmarker import schedule_id as _sid

            inj.exempt_ids.add(_sid(naive_seq))
    if stack.prefetcher is not None:
        # hint the baseline itself: its compile starts on a worker while
        # argument/driver setup finishes, the foreground join consumes it,
        # and every run deterministically exercises the AOT-program /
        # prepare_n cache-key agreement on the real executor (the CI smoke
        # asserts prefetch hits > 0 on exactly this)
        stack.prefetcher.prefetch([naive_seq])
    t0 = time.time()
    run.naive = stack.bench.benchmark(naive_seq, run.opts)
    sys.stderr.write(f"naive: pct50={run.naive.pct50*1e6:.1f}us (wall {time.time()-t0:.0f}s)\n")


def _measure_incumbents(run: _Run):
    """The row's hand incumbents, measured: ``(incumbents, seed_paths,
    rollout_policy)``."""
    from tenzing_tpu.solve.mcts.mcts import SimResult

    stack = run.stack
    hand = run.row.incumbents(run.args, run.g, run.built[3], run.plat)
    seqs = hand.seqs
    if seqs and not hand.tolerant:
        seqs = [(label, run.with_tile1(s)) for label, s in seqs]
        if stack.prefetcher is not None:
            # the incumbent grid is known up front: incumbent k+1 compiles
            # in the background while incumbent k measures
            stack.prefetcher.prefetch([s for _, s in seqs])
    incumbents = []
    for label, seq in seqs:
        t0 = time.time()
        # search-phase cost: incumbents are re-ranked by the paired
        # screen anyway, this number only seeds the tree
        try:
            meas = stack.bench.benchmark(seq, run.search_opts)
        except Exception as e:
            if not hand.tolerant:
                raise
            sys.stderr.write(
                f"{label} incumbent rejected ({type(e).__name__}: "
                f"{str(e)[:160]})\n")
            continue
        sys.stderr.write(
            f"{label} incumbent: pct50={meas.pct50*1e6:.1f}us "
            f"(wall {time.time()-t0:.0f}s)\n"
        )
        sim = SimResult(order=seq, result=meas)
        run.labels[id(sim)] = label
        incumbents.append(sim)
    return incumbents, hand.seed_paths, hand.rollout_policy


def _recorded_warm_start(run: _Run, incumbents: list) -> list:
    """Recorded-best warm start: the best distinct schedules from previous
    runs' search databases are first-class candidates (the search remembers
    its own discoveries across runs — CSV checkpoint/resume, the reference's
    mcts_csv workflow) and a hill-climb seed discipline.  r4l motivated
    this: r4k's climb discovered the batched-z-unpack combination at paired
    2.48, and the next run's climbs wandered to 1.42 local optima instead of
    starting from it.  Appends to ``incumbents``; returns the recorded
    sequences that measured, best first."""
    args, stack = run.args, run.stack
    if args.seed_csv is None:
        args.seed_csv = run.row.seed_csv
    if not (args.seed_csv and args.seed_topk > 0 and not args.smoke):
        return []
    import glob as _glob
    import os.path as _osp

    from tenzing_tpu.bench.recorded import rank_recorded
    from tenzing_tpu.solve.mcts.mcts import SimResult

    pat = args.seed_csv
    if not _osp.isabs(pat):
        pat = _osp.join(REPO_ROOT, pat)
    paths = sorted(_glob.glob(pat))
    if not paths:
        sys.stderr.write(f"recorded db: no files match {pat!r}\n")
    picked = rank_recorded(
        paths, run.g, args.seed_topk,
        log=lambda m: sys.stderr.write(m + "\n"),
    )
    # recorded rows predating a planted tile menu carry no directive
    picked = [(run.with_tile1(s), r) for s, r in picked]
    recorded_ok = []
    if stack.prefetcher is not None:
        stack.prefetcher.prefetch([s for s, _ in picked])
    from tenzing_tpu.fault.backoff import BackoffPolicy as _BP, retry_call

    for ri, (seq_r, ratio) in enumerate(picked):
        t0 = time.time()
        # transient-classified retry via the shared backoff helper (the
        # device runtime can have flaky spells); a deterministic failure — a recorded
        # schedule this chip genuinely cannot run — drops immediately
        try:
            meas = retry_call(
                lambda seq_r=seq_r: stack.bench.benchmark(
                    seq_r, run.search_opts),
                policy=_BP(retries=1, base_secs=2.0),
                where="recorded.warmstart",
            )
        except Exception as err:
            sys.stderr.write(
                f"recorded[{ri}] dropped "
                f"({type(err).__name__}: {str(err)[:200]})\n"
            )
            continue
        sys.stderr.write(
            f"recorded[{ri}] candidate: pct50={meas.pct50*1e6:.1f}us "
            f"(recorded ratio {ratio:.3f}, wall {time.time()-t0:.0f}s)\n"
        )
        sim = SimResult(order=seq_r, result=meas)
        run.labels[id(sim)] = f"recorded[{ri}]"
        incumbents.append(sim)
        recorded_ok.append((seq_r, meas.pct50))
    # best by RE-MEASURED time first for the climb seed (this run's
    # regime, same fidelity across the three)
    return [s for s, _ in sorted(recorded_ok, key=lambda e: e[1])]


def _tree_search(run: _Run, incumbents, seed_paths, rollout_policy):
    """Directed search over the order x lane x kernel x engine space, at the
    cheap search-phase measurement cost.  Multi-fidelity (VERDICT r4 item
    2): rollouts are measured at a ~1 ms screen floor — search-time numbers
    only steer the tree — and the top-k distinct schedules are re-measured
    at the climb floor before the dump, so MCTS's official candidates carry
    comparable-fidelity numbers into the paired screen."""
    from tenzing_tpu.bench.benchmarker import BenchOpts
    from tenzing_tpu.solve.mcts import MctsOpts, explore
    from tenzing_tpu.solve.mcts.strategies import FastMin

    args, stack, bench = run.args, run.stack, run.stack.bench
    t0 = time.time()
    run.mcts_screen = mcts_screen = BenchOpts(
        n_iters=2, max_retries=2,
        target_secs=0.0005 if args.smoke else 0.001,
    )
    mcts_confirm = BenchOpts(
        n_iters=max(5, args.iters), max_retries=2,
        target_secs=run.search_opts.target_secs * 10,
    )
    run.search_bench = search_bench = bench
    if run.surrogate is not None:
        # the learned screen slots into the existing screen/confirm split:
        # rollout queries (mcts_screen opts) may be answered by the model,
        # while the confirm pass and everything at any other fidelity
        # always reaches the device (screen_only_opts)
        from tenzing_tpu.learn import ScreeningBenchmarker

        run.search_bench = search_bench = ScreeningBenchmarker(
            run.surrogate, bench, escalate_topk=max(4, args.seed_topk + 1),
            screen_only_opts=mcts_screen,
        )
    res = explore(
        run.g,
        run.plat,
        search_bench,
        MctsOpts(n_iters=args.mcts_iters, bench_opts=mcts_confirm,
                 screen_opts=mcts_screen, confirm_topk=4, seed=0,
                 rollout_policy=rollout_policy,
                 checkpoint=stack.checkpoint, verify=stack.verifier,
                 prefetch=stack.prefetcher),
        strategy=FastMin,
        seeds=seed_paths,
    )
    if run.surrogate is not None:
        sys.stderr.write(
            f"learn screen: {search_bench.hits} surrogate answers / "
            f"{search_bench.escalations} escalations\n")
    confirmed = [s for s in res.sims if s.fidelity == "full"]
    best_seen = min(
        (s.result.pct50 for s in (confirmed or res.sims)),
        default=float("inf"),
    )
    sys.stderr.write(
        f"mcts wall {time.time()-t0:.0f}s, tree={res.tree_size}, "
        f"{len(res.sims)} rollouts ({len(seed_paths)} seeded, "
        f"{len(confirmed)} confirmed at {mcts_confirm.target_secs}s floor), "
        f"best-seen pct50={best_seen*1e6:.1f}us\n"
    )
    # where the search wall goes (VERDICT r3 weak #5): per-phase counters +
    # benchmark-cache economics in the driver tail
    if res.counters is not None:
        sys.stderr.write(res.counters.report() + "\n")
    sys.stderr.write(
        f"bench cache: {bench.hits} hits / {bench.misses} misses; "
        f"compiled programs: {run.ex.compile_count} "
        f"({run.ex.compile_secs:.1f}s compile wall)\n"
    )
    if stack.prefetcher is not None:
        pst = stack.prefetcher.stats()
        sys.stderr.write(
            "prefetch: %(issued)d issued / %(hits)d hits / %(wasted)d "
            "wasted / %(failed)d failed / %(dropped)d dropped\n" % pst)
    res.sims = incumbents + res.sims
    return res


def _climb(run: _Run, res, incumbents: list, recorded: list) -> None:
    """Neighborhood search from the best-known heuristic: hill-climb in
    decision space (solve/local.py) refines it with measured
    single-substitution moves — the local complement to MCTS's global
    exploration, at the same cheap search cost.  The climbs' candidates
    join ``res.sims``, their tips ``incumbents``.

    Distributed search fleet (docs/performance.md, "Distributed search"):
    --search-workers N / --measure-batch K route the SAME climb jobs
    through search/fleet.py — (1,1) is the serialized inline baseline
    (bit-identical to the legacy loop below), N>=2 spawns worker
    processes measuring through fused K-candidate rounds.  0/0 keeps the
    legacy loop byte-for-byte."""
    args, stack, bench = run.args, run.stack, run.stack.bench
    climb_cfg = run.row.climb_config(args, run.plat, recorded)
    fleet_n = max(0, int(args.search_workers or 0))
    fleet_k = max(0, int(args.measure_batch or 0))
    fleet_engaged = fleet_n > 0 or fleet_k > 0
    if fleet_engaged and not climb_cfg and args.climb_budget > 0:
        # --smoke builds no climb configs; synthesize a deterministic 2-job
        # split of the climb budget — the job list depends only on the
        # request (never on N or K), so the (1,1) serialized baseline and
        # the fused fleet spend the same candidate budget
        _half = max(1, args.climb_budget // 2)
        climb_cfg = [(run.plat, run.row.phases(), generic_xla_prefer, None,
                      _half, "generic_xla", None)] * 2
    if not (climb_cfg and args.climb_budget > 0):
        return
    from dataclasses import replace as _replace

    from tenzing_tpu.solve.local import LocalOpts, hill_climb

    # paired=True: accept moves only on a back-to-back paired comparison
    # with the incumbent — the r4a run showed unpaired first-improvement
    # climbing chases chip drift (climb "best" 96 ms that the paired
    # screen ranked below its own seed).  Accepts run at SCREEN fidelity
    # (r4c: accepts at the cheap 0.01s floor did not replicate under the
    # screen's 0.1s floor — measurement-regime-dependent overlap), which
    # costs ~1.6s of measurement per neighbor on top of the ~3s compile.
    climb_opts = _replace(run.search_opts, n_iters=8,
                          target_secs=10 * run.search_opts.target_secs)

    def adopt(sims, final):
        for s in sims:
            run.labels[id(s)] = "climb"
        res.sims = res.sims + sims
        if final is not None:
            # the accepted chain tip is the climb's official output: it
            # always advances to the paired screen, like the incumbents
            run.labels[id(final)] = "climb-tip"
            incumbents.append(final)
            res.sims = res.sims + [final]

    if fleet_engaged:
        from tenzing_tpu.search.fleet import (
            FleetJob,
            run_fleet,
            run_serialized,
        )

        jobs = [
            FleetJob(index=ci, budget=cbudget, seed=2 + ci,
                     lanes=len(cplat.lanes), phases=tuple(cphases),
                     prefer=pname, chosen=chosen)
            for ci, (cplat, cphases, _cpf, _cpri, cbudget, pname,
                     chosen) in enumerate(climb_cfg)
        ]
        n_w, k_fuse = max(1, fleet_n), max(1, fleet_k)
        t0 = time.time()
        if n_w == 1 and k_fuse == 1:
            fres = run_serialized(
                run.g, jobs, bench, climb_opts, surrogate=run.surrogate,
                ckpt=stack.checkpoint, verifier=stack.verifier,
                prefetcher=stack.prefetcher)
        else:
            fres = run_fleet(
                run.g, args.to_json(), jobs, bench, climb_opts, n_w, k_fuse,
                prefetcher=stack.prefetcher, verify=not args.no_verify)
        run.distributed_stats = st = fres.stats
        for jr in fres.jobs:
            if jr.failed:
                sys.stderr.write(
                    f"fleet job {jr.index}: FAILED ({jr.failed})\n")
                continue
            adopt(jr.sims, jr.final)
        sys.stderr.write(
            f"fleet: {st['workers']}w K={st['measure_batch']}: "
            f"{st['candidates']} candidates / {st['jobs']} jobs in "
            f"{st['wall_s']}s ({st['rounds']} fused rounds, occupancy "
            f"{st['batch_occupancy']}, {st['singles']} singles, "
            f"{st['reclaimed_subtrees']} reclaimed, scaling "
            f"{st['scaling_factor']}x, wall {time.time()-t0:.0f}s)\n")
        return
    for ci, (cplat, cphases, cprefer, cpriority, cbudget, _pname,
             _chosen) in enumerate(climb_cfg):
        t0 = time.time()
        lres = hill_climb(
            run.g, cplat, bench, cphases, prefer=cprefer,
            priority=cpriority,
            opts=LocalOpts(budget=cbudget, bench_opts=climb_opts,
                           seed=2 + ci, paired=True,
                           prescreen=run.surrogate,
                           checkpoint=stack.checkpoint,
                           verify=stack.verifier,
                           prefetch=stack.prefetcher),
        )
        lbest = lres.best()
        sys.stderr.write(
            f"hill-climb[{ci}] ({len(cplat.lanes)} lanes): "
            f"{len(lres.sims)} candidates, best "
            f"pct50={lbest.result.pct50*1e6:.1f}us "
            f"(wall {time.time()-t0:.0f}s)\n"
        )
        adopt(lres.sims, lres.final)


def _screen_and_final(run: _Run, res, incumbents: list) -> _Pick:
    """Candidate selection is DRIFT-IMMUNE (VERDICT r2 weak #1: raw search-
    phase pct50s picked final candidates while naive drifted 254ms -> 129ms
    within one run, and 2 of 4 finalists lost to naive).  Two paired
    decorrelated batches (reference batch benchmark, benchmarker.cpp:21-76):

      screen: naive + the distinct candidates (incumbent grid + top
              searched), moderate cost; paired
              per-iteration speedups rank them, dropping everything whose
              paired median is < 1.0 — search-time drift cancels because
              iteration k visits every schedule back-to-back;
      final:  naive + the top 3 screened, 3x iterations and a 20x adaptive
              measurement floor (the reference's >=10ms floor scaled up,
              benchmarker.cpp:83-119) so single-execution jitter cannot
              widen the bootstrap CI across 1.0 when the margin is real.

    All programs are already compiled (executor cache) — pure measurement."""
    from dataclasses import replace
    from itertools import chain, zip_longest

    from tenzing_tpu.bench.benchmarker import BenchResult
    from tenzing_tpu.core.sequence import canonical_key
    from tenzing_tpu.utils.numeric import paired_speedup

    args, naive, opts = run.args, run.naive, run.opts
    resilient, label_of = run.stack.resilient, run.label_of

    def batch_paired(seqs, bopts, seed):
        """(results, paired-vs-naive) for [naive] + candidates run as one
        decorrelated batch — through the resilient wrapper, so a transient
        flake mid-verdict retries the batch instead of killing the run."""
        times = resilient.benchmark_batch_times(
            [run.naive_seq] + list(seqs), bopts, seed=seed)
        results = [BenchResult.from_times(ts) for ts in times]
        paired = [paired_speedup(times[0], ts, seed=seed + 1) for ts in times[1:]]
        return results, paired

    # distinct candidates by canonical key; heuristic incumbents always
    # advance to screening (search-time noise must not knock them out).
    # The mcts pool is the confirm-pass sims (re-measured at the same 10x
    # floor the climbs use), but each pool is still sorted within itself and
    # the screen slots interleave the pools: measurements taken minutes
    # apart on a drifting chip are safer ranked per-pool than jointly.
    seen = set()
    cands = []
    inc_ids = {id(s) for s in incumbents}
    # screen-fidelity MCTS rollouts never advance directly: their ~1 ms-floor
    # pct50s are not comparable with any other pool, and the confirm pass
    # already re-measured the best of them at the climb floor
    others = [s for s in res.sims
              if id(s) not in inc_ids
              and getattr(s, "fidelity", "full") == "full"]
    pools = {
        label: sorted(
            (s for s in others if run.labels.get(id(s), "mcts") == label),
            key=lambda s: s.result.pct50,
        )
        for label in ("climb", "mcts")
    }
    interleaved = [
        s
        for pair in zip_longest(pools["climb"], pools["mcts"])
        for s in pair
        if s is not None
    ]
    for s in chain(incumbents, interleaved):
        key = canonical_key(s.order)
        if key not in seen:
            seen.add(key)
            cands.append(s)
    # the screen needs room for searched candidates BEYOND the incumbent
    # grid (7 labeled incumbents for halo) without shrinking the pool for
    # workloads with few incumbents
    cands = cands[: max(8, len(incumbents) + 4) if not args.smoke else 4]

    if resilient.degraded:
        # graceful degradation (docs/robustness.md): the device was lost
        # mid-search and the run finished against cache + surrogate.  The
        # paired screen/final need live hardware, and a verdict from
        # predicted numbers must never pass as a measurement — report the
        # pre-loss naive measurement with vs_baseline 1.0 and degraded
        # provenance instead of a fabricated win.
        sys.stderr.write(
            "degraded: device lost mid-search — skipping the paired "
            "screen/final; reporting no-win with degraded provenance\n")
        cands = []
    # constructed unconditionally: the regime metadata in the final JSON
    # reads the ACTUAL floors these carry, so tuning a multiplier at one
    # site cannot silently desynchronize the reported metadata
    pick = _Pick(
        vs=1.0, value_us=naive.pct50 * 1e6,
        screen_opts=replace(opts, target_secs=5 * opts.target_secs),
        fin_opts=replace(
            opts, n_iters=3 * opts.n_iters, target_secs=20 * opts.target_secs
        ))
    if cands:
        for attempt in range(2):
            t0 = time.time()
            _, screen = batch_paired(
                [s.order for s in cands], pick.screen_opts,
                seed=1 + 10 * attempt
            )
            sys.stderr.write(
                "screen (paired vs naive, wall %.0fs): %s\n"
                % (
                    time.time() - t0,
                    ", ".join(
                        "%s=%.4f" % (label_of(s), p[0])
                        for s, p in zip(cands, screen)
                    ),
                )
            )
            # DEGENERATE-SCREEN guard: the chip was seen in a slow regime in which
            # every measurement is latency-dominated and all paired ratios
            # collapse toward 1.0 (observed: a MoE screen ranking everything
            # 0.95-1.05 minutes before the final batch measured the same
            # candidates at 10.9-12.2x).  A screen is suspect only when it
            # separates nothing (max ratio < 1.1) while the search-time
            # medians PREDICTED real separation (naive vs best candidate
            # >= 1.5x) — honest no-win workloads (SpMV ~1.0 everywhere)
            # never trip it.  One re-run, then the measurement stands.
            predicted = naive.pct50 / min(s.result.pct50 for s in cands)
            best_screen = max(p[0] for p in screen)
            # second clause added after r4w: a degraded chip regime flattened
            # the whole screen to 1.02-1.18 while the search predicted 3.4x
            # (the high-floor final then measured the survivors at 2.39x —
            # but the RANKING had already been made under the flattened
            # regime, advancing a 1.30 incumbent over stronger climbs)
            degenerate = (best_screen < 1.1 and predicted > 1.5) or (
                best_screen < 1.25 and predicted > 1.8
            )
            if not degenerate or attempt == 1:
                break
            sys.stderr.write(
                f"screen degenerate (best ratio {best_screen:.2f}, search "
                f"predicted {predicted:.2f}x) — re-running once\n"
            )
        ranked = sorted(
            zip(cands, screen), key=lambda sp: sp[1][0], reverse=True
        )
        # only candidates that beat naive under the paired screen advance —
        # the final batch reports no sub-1.0 losers
        pick.top = top = [s for s, p in ranked if p[0] > 1.0][:3]
    if pick.top:
        t0 = time.time()
        pick.finals, paired = batch_paired(
            [s.order for s in top], pick.fin_opts, seed=3)
        fin_naive, fin_cands = pick.finals[0], pick.finals[1:]
        sys.stderr.write(
            "final batch (wall %.0fs): naive=%.1fus candidates=[%s]us\n"
            % (
                time.time() - t0,
                fin_naive.pct50 * 1e6,
                ", ".join("%.1f" % (r.pct50 * 1e6) for r in fin_cands),
            )
        )
        pick.best_i = max(range(len(paired)), key=lambda i: paired[i][0])
        m, lo, hi = paired[pick.best_i]
        sys.stderr.write(
            "paired speedup vs naive: best=%.4f [%.4f, %.4f] 95%% CI "
            "(all: %s)\n"
            % (
                m, lo, hi,
                ", ".join(
                    "%s=%.4f [%.4f, %.4f]" % (label_of(s), p[0], p[1], p[2])
                    for s, p in zip(top, paired)
                ),
            )
        )
        # a win requires the bootstrap CI to exclude 1.0, not just the bare
        # median — otherwise sampling noise reports a spurious speedup on
        # roughly half of no-difference runs
        if m > 1.0 and lo > 1.0:
            pick.value_us = fin_cands[pick.best_i].pct50 * 1e6
            pick.vs = m
        else:
            pick.value_us = fin_naive.pct50 * 1e6
            pick.vs = 1.0
    return pick


def _integrity_gate(run: _Run, pick: _Pick) -> None:
    """Result-integrity gate (docs/robustness.md, "Schedule soundness"): the
    schedule whose number the JSON is about to report re-executes on the
    device next to naive, and their outputs must numerically agree — plus
    the independent verifier must pass it.  A fast-but-WRONG schedule
    (an under-synchronized winner whose race made it fast) can therefore
    never be the answer: a failed gate demotes the run to no-win and
    stamps ``verified: false`` with the verdict into the fault meta.
    Sets ``pick.integrity``, ``pick.gate_outs`` and ``pick.reported_seq``."""
    ex, naive_seq, verifier = run.ex, run.naive_seq, run.stack.verifier
    if verifier is not None and not run.stack.resilient.degraded:
        win = pick.winner()
        winner_seq = win.order if win is not None else naive_seq
        verdict = verifier(winner_seq)
        num_ok = False
        gate_err = None
        try:
            from tenzing_tpu.fault.backoff import (
                BackoffPolicy as _GP,
                retry_call as _gate_retry,
            )

            t0 = time.time()
            # transient-classified retry (default retry_on), like every
            # other device interaction: one transient flake must not demote a
            # multi-hour search's legitimate winner to verified: false
            rerun = lambda seq: _gate_retry(
                lambda: ex.run(seq), policy=_GP(retries=2, base_secs=2.0),
                where="verify.gate")
            out_w = rerun(winner_seq)
            out_n = out_w if winner_seq is naive_seq else rerun(naive_seq)
            pick.gate_outs[id(winner_seq)] = out_w
            pick.gate_outs[id(naive_seq)] = out_n
            # compared: every buffer BOTH schedules define.  A staging
            # buffer only one of them writes (naive's host_* under an
            # all-rdma winner, moe's bf16 set) is that menu choice's
            # scratch, not a result — the other side still holds its
            # initial zeros, so comparing it fails every winner that
            # picked another engine than naive's
            mismatched = _mismatched_outputs(
                out_n, out_w, run.args.verify_tol,
                names=_written_buffers(naive_seq)
                & _written_buffers(winner_seq))
            num_ok = not mismatched
            if mismatched:
                gate_err = f"outputs diverge on {mismatched[:4]}"
            sys.stderr.write(
                "integrity gate: winner-vs-naive outputs "
                f"{'agree' if num_ok else 'DIVERGE'}, verifier "
                f"{'ok' if verdict.ok else 'UNSOUND'} "
                f"(wall {time.time()-t0:.0f}s)\n")
        except Exception as e:
            gate_err = f"{type(e).__name__}: {str(e)[:200]}"
            sys.stderr.write(
                f"integrity gate: winner re-execution failed ({gate_err})\n")
        pick.integrity = integrity = {"verified": bool(verdict.ok and num_ok)}
        if not verdict.ok:
            integrity["verdict"] = verdict.witness()
        if gate_err is not None:
            integrity["error"] = gate_err
        if not integrity["verified"] and pick.vs > 1.0:
            sys.stderr.write(
                "integrity gate FAILED — demoting the winner to no-win\n")
            pick.value_us = (pick.finals[0].pct50 if pick.finals
                             else run.naive.pct50) * 1e6
            pick.vs = 1.0
    elif verifier is not None:
        # degraded: no device to re-execute on — the answer is explicitly
        # NOT verified (and already demoted to the pre-loss naive number)
        pick.integrity = {"verified": False, "error": "degraded: no device"}

    # the schedule whose number the JSON reports, AFTER any gate demotion —
    # the one object the profiling and fusion phases both operate on
    win = pick.winner()
    pick.reported_seq = win.order if win is not None else naive_seq


def winner_report(name: str, enabled, needs_device: Optional[str], body,
                  degraded: bool = False, skipped=None):
    """The frame of a post-search provenance report on the reported
    schedule: its block, or None when it is not ``enabled``.  A report that
    ``needs_device`` (says what for) is skipped on a degraded run and leaves
    ``skipped``; ``body(t0)`` fills the block.  Provenance is observability,
    never a verdict gate: a body that raises (a stepped program that cannot
    compile, a mesh platform) degrades to an error-carrying block instead of
    killing a finished search."""
    if not enabled:
        return None
    if needs_device and degraded:
        sys.stderr.write(f"{name}: skipped (device lost — no hardware to "
                         f"{needs_device})\n")
        return skipped
    try:
        return body(time.time())
    except Exception as e:
        sys.stderr.write(
            f"{name} failed ({type(e).__name__}: {str(e)[:200]})\n")
        return {"error": f"{type(e).__name__}: {str(e)[:200]}"}


def stepped_analysis(run: _Run, seq, measured_us, cost=None):
    """obs/attrib's analysis of ``seq`` on the run's executor; ``cost``
    joins the roofline's fractions of peak.  On a TPU the timeline is one
    profiled dispatch of the program that was timed, cut by vertex
    (``traced_timeline``); elsewhere a profile has no ``XLA Ops`` line
    and the ops are stepped one by one."""
    import jax

    from tenzing_tpu.obs import attrib as _attrib

    if jax.default_backend() == "tpu":
        tl = _attrib.traced_timeline(run.ex, seq)
    else:
        tl = _attrib.stepped_timeline(run.ex, seq,
                                      repeats=run.args.profile_repeats)
    return _attrib.analyze(seq.vector(), tl, measured_us=measured_us,
                           cost=cost, peaks=run.peaks)


def _reported_analysis(run: _Run, pick: _Pick, cost=None):
    """The reported schedule's analysis: ``--profile-winner``'s when it ran
    (this exact sequence, repeats and measured_us), else stepped now."""
    if run.profiled_attrib is not None:
        return run.profiled_attrib
    return stepped_analysis(run, pick.reported_seq, pick.value_us, cost)


def _winner_reports(run: _Run, res, pick: _Pick) -> Dict[str, Any]:
    """The four post-search provenance reports, each through
    :func:`winner_report`: the verdict's blocks by where they are stamped."""
    args, degraded = run.args, run.stack.resilient.degraded
    return {
        "attrib": winner_report(
            "profile-winner", args.profile_winner, "step ops on",
            lambda t0: _profile_winner(run, pick, t0), degraded),
        "fused": winner_report(
            "fuse-winner", args.fuse_winner, "run fused programs on",
            lambda t0: _fuse_winner(run, pick, t0), degraded,
            skipped={"error": "degraded: no device"}),
        "chunked": winner_report(
            "chunked provenance", args.chunk, None,
            lambda t0: _chunk_report(run, res, pick, t0)),
        "synth": winner_report(
            "synth provenance", args.synth_collectives, None,
            lambda t0: _synth_report(run, res, pick, t0)),
    }


def _profile_winner(run: _Run, pick: _Pick, t0) -> Dict[str, Any]:
    """Attribution profiling (docs/observability.md, "Attribution"): per-op
    stepped timing of the schedule whose number the JSON reports, plus
    naive for the decision diff — the attrib block is the measurement
    substrate the mega-kernel and chunking work will be judged with
    (dispatch overhead removed, which ops fail to overlap)."""
    import os as _os

    from tenzing_tpu import obs
    from tenzing_tpu.obs import attrib as _attrib

    args, naive_seq, finals = run.args, run.naive_seq, pick.finals
    winner_seq_p = pick.reported_seq
    cost = workload_cost(args.workload, run.built)
    naive_meas_us = (finals[0].pct50 if finals else run.naive.pct50) * 1e6
    # stashed for the reports after this one (_reported_analysis)
    run.profiled_attrib = w_at = stepped_analysis(
        run, winner_seq_p, pick.value_us, cost)
    attrib_block = w_at.to_json()
    expl = None
    if winner_seq_p is not naive_seq:
        n_at = stepped_analysis(run, naive_seq, naive_meas_us, cost)
        expl = _attrib.explain(naive_seq.vector(),
                               winner_seq_p.vector(),
                               naive_attrib=n_at,
                               winner_attrib=w_at)
        attrib_block["explain"] = expl.get("timing", {})
    # the winner's raw measurement series rides along for the
    # report CLI's noise-aware regression check (obs/report.py)
    fin_res = (finals[1 + pick.best_i] if pick.winner() is not None
               else (finals[0] if finals else run.naive))
    if fin_res.times:
        attrib_block["measured_times"] = [round(t, 9) for t in fin_res.times]
    if args.trace_out:
        _os.makedirs(args.trace_out, exist_ok=True)
        doc = dict(expl) if expl is not None else {}
        doc["attrib"] = attrib_block
        _attrib.write_explain(
            _os.path.join(args.trace_out, "explain.json"), doc)
        rank = obs.get_tracer().rank
        # anchor the Gantt at the current unix-us instant so the
        # per-lane tracks render next to the span timeline (span
        # timestamps are unix-anchored, obs/tracer.py)
        t0_us = time.time() * 1e6
        run.attrib_extra.extend(_attrib.timeline_trace_events(
            w_at, pid=rank, t0_us=t0_us, label="attrib/winner"))
        if expl is not None:
            run.attrib_extra.extend(_attrib.timeline_trace_events(
                n_at, pid=rank, t0_us=t0_us, label="attrib/naive",
                tid_base=2000))
        sys.stderr.write(
            f"explain: {_os.path.join(args.trace_out, 'explain.json')}\n")
    eff = attrib_block.get("overlap_efficiency")
    sys.stderr.write(
        "profile-winner: %d ops stepped, sum-of-parts %.1fus, "
        "critical path %.1fus, dispatch overhead %.1fus, overlap "
        "efficiency %s (wall %.0fs)\n"
        % (attrib_block["n_timed"],
           attrib_block["sum_of_parts_us"],
           attrib_block["critical_path_us"],
           attrib_block["dispatch_overhead_us"],
           f"{eff:.3f}" if eff is not None else "n/a",
           time.time() - t0))
    return attrib_block


def _fuse_winner(run: _Run, pick: _Pick, t0) -> Dict[str, Any]:
    """Megakernel fusion (docs/performance.md, "Megakernel fusion"): lower
    the reported schedule into fused Pallas regions (runtime/fused.py),
    sweep the roofline-pruned tile menu, gate the best fused program
    through the result-integrity machinery (allclose vs the stepped
    program + re-verified), and stamp the ``perf.fused`` provenance
    block with the dispatch overhead before/after (obs/attrib) — the
    measured answer to "what did fusing the dispatches buy"."""
    from tenzing_tpu.bench.benchmarker import EmpiricalBenchmarker
    from tenzing_tpu.runtime.fused import FusedExecutor, fused_summary

    ex, verifier, value_us = run.ex, run.stack.verifier, pick.value_us
    winner_seq_f = pick.reported_seq
    cost = workload_cost(run.args.workload, run.built)
    # "before": the unfused program's dispatch overhead — per-op
    # stepped sum-of-parts minus the reported whole-program pct50
    at_b = _reported_analysis(run, pick, cost)
    # compile tallies snapshot AFTER the stepped timeline: the
    # per-op sub-program compiles above are attribution cost, not
    # fusion cost — the stamped delta covers plan + tile variants
    # + the gate's executions only
    compile0, csecs0 = ex.compile_count, ex.compile_secs
    plan0 = FusedExecutor(ex).plan(winner_seq_f)
    menu = plan0.tile_menu
    by_tiles: Dict[str, float] = {}
    best_t, best_us, best_fex = 1, None, None
    for t in menu:
        # fresh benchmarker per variant: the shared CachingBenchmarker
        # keys by canonical schedule, which would collide the fused
        # variants with the stepped measurement of the same order
        fex_t = FusedExecutor(ex, tiles=t)
        res_t = EmpiricalBenchmarker(fex_t).benchmark(winner_seq_f, run.opts)
        us = res_t.pct50 * 1e6
        by_tiles[str(t)] = round(us, 2)
        if best_us is None or us < best_us:
            best_t, best_us, best_fex = t, us, fex_t
    plan = best_fex.plan(winner_seq_f)
    # result-integrity gate on the fused outputs: allclose vs the
    # stepped program, and the schedule re-verified (PR 4 gate)
    out_f = best_fex.run(winner_seq_f)
    # the PR-4 gate already executed this exact sequence — reuse
    # its outputs instead of re-running a potentially multi-GB
    # program (gate skipped/failed -> fresh execution)
    out_s = pick.gate_outs.get(id(winner_seq_f))
    if out_s is None:
        out_s = ex.run(winner_seq_f)
    mismatched = _mismatched_outputs(out_s, out_f, run.args.verify_tol)
    num_ok = not mismatched
    re_verdict = verifier(winner_seq_f) if verifier is not None \
        else None
    fused_verified = bool(
        num_ok and (re_verdict.ok if re_verdict is not None
                    else True))
    # "after": the FUSED program's remaining dispatch overhead —
    # one stepped unit per region instead of per op
    at_a = stepped_analysis(run, best_fex.fused_order(winner_seq_f),
                            best_us, cost)
    fused_block = {
        "regions": len(plan.regions),
        "region_sizes": [r.n_ops for r in plan.regions],
        "fused_ops": plan.n_ops_fused,
        "n_ops_total": plan.n_ops_total,
        "tiles": {"chosen": best_t, "menu": menu,
                  "per_region": [r.tiles for r in plan.regions],
                  "by_tiles_us": by_tiles},
        "measured_us": {"stepped": round(value_us, 2),
                        "fused": round(best_us, 2)},
        "compile_secs": round(ex.compile_secs - csecs0, 3),
        "compiled_programs": ex.compile_count - compile0,
        "verified": fused_verified,
        "dispatch_overhead_us": {
            "before": round(at_b.dispatch_overhead_us, 3),
            "after": round(at_a.dispatch_overhead_us, 3)},
        "sum_of_parts_us": {
            "before": round(at_b.sum_of_parts_us, 3),
            "after": round(at_a.sum_of_parts_us, 3)},
    }
    if mismatched:
        fused_block["error"] = \
            f"fused outputs diverge on {mismatched[:4]}"
    if re_verdict is not None and not re_verdict.ok:
        fused_block["verdict"] = re_verdict.witness()
    sys.stderr.write(
        "fuse-winner: %s; tiles %s -> best t=%d %.1fus (stepped "
        "%.1fus); dispatch overhead %.1f -> %.1fus; %s (wall "
        "%.0fs)\n" % (
            fused_summary(plan), by_tiles, best_t, best_us,
            value_us,
            fused_block["dispatch_overhead_us"]["before"],
            fused_block["dispatch_overhead_us"]["after"],
            "verified" if fused_verified else "GATE FAILED",
            time.time() - t0))
    return fused_block


def _chunk_report(run: _Run, res, pick: _Pick, t0) -> Dict[str, Any]:
    """Op-chunking provenance (ISSUE 10, docs/performance.md "Chunked
    overlap"): the roofline-pruned chunk menus the models offered, what
    the search visited and chose, and the hidden comm the chunking bought
    — estimated (the roofline upper bound carried on the menu) vs
    measured (transfer-unit overlap with the chunk partials on the
    obs/attrib stepped timeline)."""
    from tenzing_tpu.core.chunking import chunk_menus, chunks_of

    reported_seq = pick.reported_seq
    menus = chunk_menus(run.g)
    chosen = chunks_of(reported_seq)
    searched_counts: set = set()
    n_cand_chunked = 0
    for s in res.sims:
        cm = chunks_of(s.order)
        if cm:
            n_cand_chunked += 1
            searched_counts.update(cm.values())
    est_total = 0.0
    for base, n in chosen.items():
        m = menus.get(base)
        if m:
            est_total += float(m.get("est_hidden_us", {}).get(n, 0.0))
    chunked_block = {
        "menus": {
            b: {"counts": list(m["counts"]),
                "est_hidden_us": {
                    str(k): round(float(v), 2)
                    for k, v in m.get("est_hidden_us", {}).items()}}
            for b, m in sorted(menus.items())},
        "searched_counts": sorted(int(c) for c in searched_counts),
        "n_candidates_chunked": n_cand_chunked,
        "chosen": {b: int(n) for b, n in sorted(chosen.items())},
        "hidden_comm_us": {"estimated": round(est_total, 2),
                           "measured": None},
    }
    if menus and all(
            not [c for c in m["counts"] if c > 1]
            for m in menus.values()):
        chunked_block["note"] = (
            "roofline pruned every chunking: no transfer whose "
            "hidden-comm bound beats the dispatch+combine cost on "
            "this workload/hardware (bench/roofline.py::"
            "prune_chunkings)")
    elif not menus:
        chunked_block["note"] = (
            "workload offers no chunkable-op menus (--chunk is a "
            "no-op for it)")
    if chosen and not run.stack.resilient.degraded:
        from tenzing_tpu.core.chunking import hidden_comm_measured_us

        measured = hidden_comm_measured_us(
            reported_seq.vector(), _reported_analysis(run, pick))
        chunked_block["hidden_comm_us"]["measured"] = round(measured, 2)
        sys.stderr.write(
            "chunked: winner uses %s; hidden comm est %.1fus / "
            "measured %.1fus (wall %.0fs)\n"
            % (chunked_block["chosen"], est_total, measured,
               time.time() - t0))
    else:
        sys.stderr.write(
            "chunked: %d menu(s), %d chunked candidate(s) "
            "searched, winner unchunked\n"
            % (len(menus), n_cand_chunked))
    return chunked_block


def _synth_report(run: _Run, res, pick: _Pick, t0) -> Dict[str, Any]:
    """Synthesized-collective provenance (ISSUE 17, docs/performance.md
    "Synthesized collectives"): the priced-and-pruned sketch menus each
    exchange site offered, what the search visited and chose, analytic
    est vs measured hidden comm of the chosen decomposition, and the
    result-integrity verdict on the reported projection."""
    from tenzing_tpu.collectives.synth import (
        synth_hidden_comm_measured_us,
        synth_menus,
        synths_of,
    )

    reported_seq, integrity = pick.reported_seq, pick.integrity
    smenus = synth_menus(run.g)
    schosen = synths_of(reported_seq)
    searched_sketches: set = set()
    n_cand_synth = 0
    for s in res.sims:
        sm = synths_of(s.order)
        if sm:
            n_cand_synth += 1
            searched_sketches.update(
                f"{v['sketch']}.c{v['chunks']}" for v in sm.values())
    sest_total = 0.0
    for base, v in schosen.items():
        m = smenus.get(base)
        if m:
            sest_total += float(m.get("est_us", {}).get(
                f"{v['sketch']}.c{v['chunks']}", 0.0))
    synth_block = {
        "menus": {
            b: {"menu": list(m["menu"]),
                "est_us": {k: round(float(v2), 3)
                           for k, v2 in m.get("est_us", {}).items()},
                "pruned": dict(m.get("pruned", {})),
                "note": m.get("note", "")}
            for b, m in sorted(smenus.items())},
        "searched_sketches": sorted(searched_sketches),
        "n_candidates_synth": n_cand_synth,
        "chosen": {b: f"{v['sketch']}.c{v['chunks']}"
                   for b, v in sorted(schosen.items())},
        "est_comm_us": round(sest_total, 3),
        "measured_hidden_us": None,
        "verified": bool(integrity and integrity.get("verified")),
    }
    if not smenus:
        synth_block["note"] = (
            "workload offers no synthesized-collective menus "
            "(--synth-collectives is a no-op for it)")
    elif all(len(m.get("menu", [])) <= 1 for m in smenus.values()):
        synth_block["note"] = (
            "roofline pruned every sketch instantiation: no "
            "decomposition whose alpha-beta estimate beats the "
            "fixed engine's one-post floor on this "
            "workload/hardware (bench/roofline.py::prune_sketches)")
    else:
        synth_block["note"] = "; ".join(
            f"{b}: {m.get('note', '')}"
            for b, m in sorted(smenus.items()))
    if schosen and not run.stack.resilient.degraded:
        smeasured = synth_hidden_comm_measured_us(
            reported_seq.vector(), _reported_analysis(run, pick))
        synth_block["measured_hidden_us"] = round(smeasured, 2)
        sys.stderr.write(
            "synth: winner uses %s; est comm %.1fus / hidden "
            "measured %.1fus (wall %.0fs)\n"
            % (synth_block["chosen"], sest_total, smeasured,
               time.time() - t0))
    else:
        sys.stderr.write(
            "synth: %d menu(s), %d synthesized candidate(s) "
            "searched, winner fixed-engine\n"
            % (len(smenus), n_cand_synth))
    return synth_block


def _dump_csv(run: _Run, res, pick: _Pick) -> None:
    """``--dump-csv``: one row per distinct schedule.  The decorrelated
    final-batch results *supersede* the search-time measurements for naive
    and the finalists (CsvBenchmarker returns the first equivalence match,
    so appending duplicate rows would leave the finals unreachable) — the
    headline verdict is replayable from the recorded database."""
    from tenzing_tpu.bench.benchmarker import result_row

    args, naive_seq, resilient = run.args, run.naive_seq, run.stack.resilient
    finals, top = pick.finals, pick.top
    results = [run.naive] + [s.result for s in res.sims]
    orders = [naive_seq] + [s.order for s in res.sims]
    # fidelity tags keep the DB honest: MCTS screen rows were measured at
    # a ~1 ms floor and must not be ranked against full-floor rows by the
    # warm-start loader (bench/recorded.py skips non-"full" rows)
    fids = ["full"] + [getattr(s, "fidelity", "full") for s in res.sims]
    if finals:
        results[0] = finals[0]
        for r, s in zip(finals[1:], top):
            # identity, not ==: sync ops compare kind-only, so two distinct
            # schedules can be ==-equal and .index() would mis-attribute
            idx = next(i for i, s2 in enumerate(res.sims) if s2 is s)
            results[1 + idx] = r
            fids[1 + idx] = "full"  # superseded by the final batch
    # rows the learned screen answered from the MODEL carry no device
    # measurement at all — tag them fid=model (inert to every reader,
    # like screen rows) so the archive never passes predictions off as
    # measurements
    if run.surrogate is not None:
        for i, s in enumerate(res.sims):
            if fids[1 + i] == "screen" and run.search_bench.was_predicted(
                    s.order):
                fids[1 + i] = "model"
    # rows answered after device loss carry degraded provenance — like
    # fid=model they are inert to every reader (CsvBenchmarker admits
    # only "full" rows, recorded.py skips non-"full"), so a degraded
    # run's archive can never pass predictions off as measurements
    if resilient.degraded:
        for i, s in enumerate(res.sims):
            if resilient.was_degraded(s.order):
                fids[1 + i] = "degraded"
    # screen rows cannot shadow full-fidelity twins on replay:
    # CsvBenchmarker only admits "full" rows into its equivalence cache
    rows = [
        result_row(i, r, o, fidelity=None if f == "full" else f)
        for i, (r, o, f) in enumerate(zip(results, orders, fids))
    ]
    # THE dump invariant every downstream reader trusts (recorded.py
    # naive_anchor_of, learn/dataset.py): row 0 is the naive schedule at
    # FINAL fidelity — checked at dump time (a real exception, not an
    # assert: it must hold under python -O too) so a future reshuffle of
    # the results list cannot silently poison every in-file ratio
    # computed against this file's anchor
    if orders[0] is not naive_seq or fids[0] != "full":
        raise RuntimeError(
            "dump-csv invariant violated: row 0 must be the naive "
            "schedule at full fidelity")
    with open(args.dump_csv, "w") as f:
        f.write("\n".join(rows) + "\n")
    sys.stderr.write(f"csv: {args.dump_csv} ({len(rows)} rows)\n")


def _stamp(run: _Run, pick: _Pick, reports: Dict[str, Any],
           n_recorded: int) -> DriverResult:
    """The verdict: the number, then the provenance blocks, each present
    iff its mechanism ran."""
    ex, stack, finals = run.ex, run.stack, pick.finals
    prefetcher, resilient = stack.prefetcher, stack.resilient
    # compile/perf provenance (ISSUE 5): "compiled programs: N" used to be
    # a stderr-only note, so a compile-wall regression was invisible to the
    # parsed BENCH_*.json series.  Close the prefetcher first (joins the
    # background workers — no leaked threads — and finalizes the wasted
    # tally), then stamp the pipeline economics into the JSON.
    if prefetcher is not None:
        prefetcher.close()
    perf = {
        "compiled_programs": ex.compile_count,
        "compile_secs": round(ex.compile_secs, 3),
        "compile_cache_dir": run.compile_cache_dir,
        "prefetch": (prefetcher.stats() if prefetcher is not None else
                     {"workers": 0, "issued": 0, "hits": 0, "wasted": 0,
                      "failed": 0, "surfaced": 0, "dropped": 0}),
    }
    tiles_block = None
    if run.tile_menu is not None:
        from tenzing_tpu.runtime.fused import tiles_of as _tiles_of

        tiles_block = {
            "menu": list(run.tile_menu),
            "planted": run.tile_planted,
            "chosen": _tiles_of(pick.reported_seq),
        }
    # in stamping order: megakernel fusion (ISSUE 8: regions, tiles chosen,
    # gate verdict, dispatch overhead before/after — iff --fuse-winner); the
    # in-driver tile search (iff --fuse-search-tiles); op chunking (ISSUE
    # 10, iff --chunk); synthesized collectives (ISSUE 17, iff
    # --synth-collectives); the distributed search (ISSUE 20, iff the fleet
    # ran: wall-clock, candidates/sec, fused-round batch occupancy and the
    # worker scaling factor, parsed by the CI distributed-search gate)
    for key, block in (("fused", reports["fused"]),
                       ("fuse_search_tiles", tiles_block),
                       ("chunked", reports["chunked"]),
                       ("synth", reports["synth"]),
                       ("distributed", run.distributed_stats)):
        if block is not None:
            perf[key] = block
    # regime metadata (VERDICT r4 item 6): cross-round vs_baseline
    # comparisons need the chip regime (naive_us), the measurement floors
    # that produced the verdict, and the warm-start provenance — without
    # them the parsed series quietly compares different machines
    win = pick.winner()
    meta = {
        "perf": perf,
        "naive_us": round(
            (finals[0].pct50 if finals else run.naive.pct50) * 1e6, 2),
        "search_floor_s": run.search_opts.target_secs,
        "screen_floor_s": pick.screen_opts.target_secs,
        "final_floor_s": pick.fin_opts.target_secs,
        "mcts_screen_floor_s": run.mcts_screen.target_secs,
        "winner_label": run.label_of(win) if win is not None else None,
        "recorded_seeds": n_recorded,
    }
    # attribution provenance (ISSUE 6): per-op timeline, critical path,
    # dispatch overhead and overlap efficiency of the reported schedule —
    # next to the fault/perf blocks, parsed by the report CLI
    if reports["attrib"] is not None:
        meta["attrib"] = reports["attrib"]
    # fault-layer provenance (ISSUE 3): a degraded verdict or a quarantine
    # -heavy run must be visible in the parsed metric series, not only in
    # stderr.  ``resumed`` distinguishes a continued run's numbers (its
    # search-phase measurements may predate the current chip regime).
    # ``verified`` (ISSUE 4) is the result-integrity gate's stamp: the
    # reported answer re-executed on device with outputs matching naive AND
    # passed the independent soundness verifier.
    injected: dict = {}
    for inj in (stack.injector, stack.corrupt_injector):
        if inj is not None:
            for k, v in inj.injected.items():
                if v:
                    injected[k] = injected.get(k, 0) + v
    integrity = pick.integrity
    if (resilient.degraded or len(stack.quarantine) or run.args.resume
            or injected or integrity is not None):
        meta["fault"] = {
            "degraded": resilient.degraded,
            "quarantined": len(stack.quarantine),
            "resumed": bool(run.args.resume),
            **({"injected": injected} if injected else {}),
            **(integrity if integrity is not None else {}),
        }
    run.write_telemetry()
    return DriverResult(verdict={
        "metric": run.built[2],
        "value": round(pick.value_us, 2),
        "unit": "us",
        "vs_baseline": round(pick.vs, 4),
        "device": run.device,
        **meta,
    })
