#!/usr/bin/env python
"""Driver benchmark CLI: searched schedule vs naive sequential ordering.

A thin argparse shim over the library driver
(``tenzing_tpu/bench/driver.py`` — ISSUE 7): flags parse into a typed
:class:`~tenzing_tpu.bench.driver.DriverRequest`, the whole search→gate→
JSON loop runs in :func:`~tenzing_tpu.bench.driver.run`, and this file
prints the returned verdict as ONE JSON line:

  {"metric": ..., "value": <best pct50, us>, "unit": "us",
   "vs_baseline": <naive_pct50 / best_pct50>}

Workloads, search structure, verdict semantics, fault/perf/attrib meta
blocks: see the driver module docstring (it carries the monolith's full
documentation).  The schedule-serving subsystem (``python -m
tenzing_tpu.serve``, docs/serving.md) calls the same driver API — a cold
request's queued work item is exactly a serialized DriverRequest, so a
queue drainer and this CLI produce identical driver JSON.

Exit code: 0 only for a verdict that was measured on the device it names.
A verdict that carries ``error`` (backend init failed, or a run without
``--smoke`` found no TPU) or whose ``fault.degraded`` is true (the device was
lost and the answer came from the cost model) still prints its parseable
line, and the process exits 1.

``--smoke`` runs a tiny CPU configuration (tests and rehearsal).
"""

import argparse
import json
import sys

# re-exports: the workload builders and menu recipes lived here for six
# rounds and are imported by example/experiment scripts by their old names
from tenzing_tpu.bench.driver import (  # noqa: F401
    ALIAS_UNPACK,
    BUILDERS,
    DriverConfigError,
    DriverRequest,
    DriverResult,
    alias_unpack_choice,
    build_attn,
    build_halo,
    build_moe,
    build_spmv,
    metric_for,
    workload_cost,
)
from tenzing_tpu.bench.driver import run as run_driver


def build_arg_parser() -> argparse.ArgumentParser:
    """The CLI surface.  Every ``dest`` and default must match a
    :class:`DriverRequest` field — tests/test_driver.py asserts the two
    agree, so a new flag cannot silently miss the library API."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="tiny CPU config")
    ap.add_argument("--workload", choices=("halo", "spmv", "attn", "mla_decode", "dsa_decode", "kda_decode", "moe"),
                    default="halo")
    ap.add_argument("--moe-tokens", type=int, default=8192,
                    help="total tokens (moe)")
    ap.add_argument("--m", type=int, default=None, help="matrix rows (spmv)")
    ap.add_argument("--spmv-bw", type=int, default=None,
                    help="band half-width (spmv); larger -> bigger remote exchange")
    ap.add_argument("--halo-n", type=int, default=512, help="cells per side (halo)")
    ap.add_argument("--lanes", type=int, default=None,
                    help="search-platform lanes (default: 8 for halo, else 2)")
    # raised 40 -> 56 in r5: informed playouts (rollout_policy) made MCTS a
    # producing solver (the r5c winner was a rollout), and the multi-fidelity
    # screen floor keeps the marginal iteration cheap (~2-3 s)
    ap.add_argument("--mcts-iters", type=int, default=56, help="MCTS iterations (compile budget)")
    ap.add_argument("--iters", type=int, default=20, help="measurements per schedule (screen/final)")
    ap.add_argument("--search-iters", type=int, default=6,
                    help="measurements per schedule during MCTS (cheap phase)")
    ap.add_argument("--climb-budget", type=int, default=44,
                    help="hill-climb benchmark budget after MCTS")
    ap.add_argument("--prefetch-compiles", type=int, default=2, metavar="N",
                    help="background compile workers for the async compile "
                         "pipeline (docs/performance.md): the solvers hint "
                         "upcoming candidates and their XLA compiles overlap "
                         "device measurement; 0 disables (serialized "
                         "compiles, bit-identical search behavior)")
    ap.add_argument("--dump-csv", default=None, help="write searched results as CSV rows")
    ap.add_argument("--trace-out", default=None,
                    help="directory for the telemetry bundle: trace.jsonl "
                         "(machine) + trace.json (Chrome trace-event, load "
                         "in Perfetto); enables span tracing")
    ap.add_argument("--metrics-json", default=None,
                    help="write the metrics registry (solver phase timings, "
                         "benchmark cache hit rate, measurement counts) as "
                         "JSON to this path")
    ap.add_argument("--seed-csv", default=None,
                    help="glob of recorded search CSVs; their best distinct "
                         "schedules are warm-start candidates and a climb "
                         "seed (default: this workload's round-4+ databases; "
                         "'' disables)")
    ap.add_argument("--seed-topk", type=int, default=3,
                    help="recorded schedules to carry as candidates")
    ap.add_argument("--learn-train", nargs="+", default=None,
                    metavar="CORPUS",
                    help="train the schedule-cost surrogate on these "
                         "recorded-search CSV globs (labels: in-file ratio "
                         "vs each file's naive anchor), save it to "
                         "--learn-model, print a summary JSON line and exit "
                         "(docs/learn.md)")
    ap.add_argument("--learn-trace", nargs="*", default=None,
                    metavar="TRACE",
                    help="telemetry-bundle JSONL globs joined onto the "
                         "training corpus by schedule digest (provenance "
                         "counts; used with --learn-train)")
    ap.add_argument("--learn-model", default=None,
                    help="surrogate model JSON: written by --learn-train, "
                         "read by --learn-screen")
    ap.add_argument("--learn-screen", action="store_true",
                    help="prescreen MCTS rollouts with the --learn-model "
                         "surrogate, escalating only plausible-top-k / "
                         "uncertain candidates to the device; also prunes "
                         "hill-climb neighbors the model can rule out")
    ap.add_argument("--checkpoint", default=None, metavar="DIR",
                    help="checkpoint directory (docs/robustness.md): the "
                         "measurement journal is appended as each "
                         "measurement lands, solver cursors snapshot "
                         "atomically, deterministic-failure quarantine "
                         "persists, and SIGINT writes a final snapshot")
    ap.add_argument("--resume", action="store_true",
                    help="restore the --checkpoint journal into the "
                         "benchmark cache before searching: already-"
                         "measured schedules never touch the device again "
                         "and the deterministic search reconstructs to the "
                         "kill point")
    ap.add_argument("--measure-timeout", type=float, default=None,
                    metavar="SECS",
                    help="watchdog wall-clock bound per measurement: a hung "
                         "compile/fetch surfaces as a transient timeout "
                         "(retried with backoff) instead of blocking the "
                         "search forever")
    ap.add_argument("--inject-faults", default=None,
                    metavar="KIND:RATE:SEED[,...]",
                    help="seeded chaos (fault/inject.py): deterministically "
                         "inject transient errors / hangs / deterministic "
                         "failures / device loss / schedule corruption "
                         "into every measurement (kinds: transient, hang, "
                         "deterministic, device_lost, corrupt)")
    ap.add_argument("--inject-hang-secs", type=float, default=60.0,
                    help="how long an injected hang stalls (pair with "
                         "--measure-timeout to exercise the watchdog)")
    ap.add_argument("--profile-winner", action="store_true",
                    help="attribution profiling of the final incumbent "
                         "(docs/observability.md, 'Attribution'): per-op "
                         "stepped timing of the winner (and naive, for the "
                         "decision diff), critical path / overlap "
                         "efficiency / dispatch overhead, stamped as an "
                         "``attrib`` block in the driver JSON; with "
                         "--trace-out also writes explain.json and "
                         "per-lane Gantt tracks into the Perfetto trace")
    ap.add_argument("--profile-repeats", type=int, default=7,
                    metavar="N",
                    help="timed repeats per op in --profile-winner "
                         "stepped profiling (median minus calibrated "
                         "fetch overhead)")
    ap.add_argument("--fuse-winner", action="store_true",
                    help="megakernel fusion of the reported schedule "
                         "(docs/performance.md, 'Megakernel fusion'): "
                         "partition it into fusible regions, lower each "
                         "into one Pallas kernel (runtime/fused.py), sweep "
                         "the roofline-pruned tile menu, gate the fused "
                         "outputs against the stepped program (allclose + "
                         "re-verified), and stamp the ``perf.fused`` block "
                         "(regions, tiles, dispatch overhead before/after)")
    ap.add_argument("--fuse-search-tiles", action="store_true",
                    help="run the megakernel tile-count decision nodes in "
                         "the driver's search path (docs/performance.md): "
                         "a FuseTileChoice planted in the choice graph is "
                         "searched by MCTS/DFS/hill-climb like any kernel "
                         "menu, every measurement lowers through the "
                         "schedule's fuse_tile.tN directive, and the "
                         "``perf.fuse_search_tiles`` block records the "
                         "menu and the chosen count")
    ap.add_argument("--chunk", action="store_true",
                    help="T3-style op chunking (docs/performance.md, "
                         "'Chunked overlap'): expand the workload's "
                         "expensive ops into searchable n-way chunked "
                         "variants (core/chunking.py) so a transfer "
                         "overlaps its own producer/consumer; chunk "
                         "counts are roofline-pruned menu entries the "
                         "solvers search like any kernel choice, and the "
                         "driver stamps the ``perf.chunked`` provenance "
                         "block (menus, searched/chosen counts, hidden "
                         "comm estimated vs measured)")
    ap.add_argument("--synth-collectives", action="store_true",
                    help="searchable synthesized collectives "
                         "(docs/performance.md, 'Synthesized collectives'): "
                         "decompose the workload's collective exchanges "
                         "into chunk-routed point-to-point sketches over "
                         "the mesh/host topology (collectives/synth.py) "
                         "and put each priced instantiation next to the "
                         "fixed engine in one ChooseOp; the solvers search "
                         "them like any kernel menu, the independent "
                         "verifier certifies every synthesized projection, "
                         "and the driver stamps the ``perf.synth`` "
                         "provenance block (menus, searched/chosen "
                         "sketches, est vs measured comm, verdict)")
    ap.add_argument("--no-verify", action="store_true",
                    help="disable the independent schedule-soundness "
                         "verifier (docs/robustness.md): the guard in the "
                         "measurement stack, the solver accept points, and "
                         "the final winner-vs-naive result-integrity gate")
    ap.add_argument("--verify-tol", type=float, default=0.02,
                    metavar="RTOL",
                    help="relative tolerance of the result-integrity "
                         "gate's winner-vs-naive output comparison (loose "
                         "enough for bf16-staging menu choices)")
    ap.add_argument("--search-workers", type=int, default=0, metavar="N",
                    help="distributed search fleet "
                         "(docs/performance.md, 'Distributed search'): run "
                         "the climb jobs across N solver worker processes "
                         "over the file control plane, with this process "
                         "as the single measurement owner; 1 (with "
                         "--measure-batch 1) is the serialized inline "
                         "path, bit-identical to the legacy climb loop; "
                         "0 disables the fleet entirely")
    ap.add_argument("--measure-batch", type=int, default=0, metavar="K",
                    help="fuse up to K candidate schedules from distinct "
                         "workers into one device measurement round "
                         "(grouped batch seeds keep each worker's paired "
                         "permutation stream intact), with prefetch hints "
                         "compiling round i+1 during round i; 0 disables "
                         "the fleet")
    return ap


def main() -> int:
    ap = build_arg_parser()
    args = ap.parse_args()
    try:
        res = run_driver(DriverRequest(**vars(args)))
    except DriverConfigError as e:
        ap.error(str(e))  # exits 2, same message/stream as the monolith
    print(json.dumps(res.verdict))
    return verdict_rc(res.verdict)


def verdict_rc(verdict) -> int:
    """The process exit code for a driver verdict: non-zero when nothing was
    measured (``error``) or the measurement degraded to the cost model."""
    if "error" in verdict:
        sys.stderr.write(f"bench: {verdict['error']}\n")
        return 1
    if verdict.get("fault", {}).get("degraded"):
        sys.stderr.write("bench: degraded verdict (device lost mid-run; "
                         "answers came from the cost model)\n")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
