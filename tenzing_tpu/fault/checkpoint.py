"""Checkpoint/resume for long searches.

A multi-hour empirical search must survive a kill — SIGINT, a SLURM wall
clock, a crashed runtime — without losing its corpus.  The checkpoint layout
(one directory, ``bench.py --checkpoint DIR``):

* ``measurements.jsonl`` — the **measurement journal**: one JSON line per
  completed device measurement (serialized ops, the BenchOpts fidelity key,
  the full BenchResult, provenance tag), appended and flushed *as each
  measurement lands* — crash-safe by construction; a torn tail line (killed
  mid-write) is detected and skipped on load.  Paired-batch results
  (``benchmark_batch_times`` — the hill-climb's accept primitive) journal
  into the same file as ``{"batch": ...}`` lines keyed by (batch-member
  schedule ids, decorrelation seed, fidelity key), so a resumed paired
  climb replays its accept batches device-free too.
* ``state.json`` — solver cursors + run config, written **atomically**
  (tmp + rename) as a versioned, sha256-digest-checked envelope
  (:func:`atomic_write_json`); a corrupt or version-mismatched file raises
  :class:`CheckpointError` instead of silently resuming from garbage.
* ``quarantine.json`` — fault/quarantine.py's persistent broken-candidate
  set (kept in the same directory so one ``--checkpoint DIR`` carries all
  cross-restart state).

**Resume model** (docs/robustness.md): the searches are deterministic given
their seeds and their measurement answers.  ``--resume`` therefore restores
the journal into the run's equivalence-keyed ``CachingBenchmarker`` and
re-executes the search from the top: every already-measured schedule is a
cache hit (zero device time, bit-identical BenchResult — floats round-trip
exactly through JSON), so the MCTS tree, the DFS frontier walk and the
hill-climb chain reconstruct *exactly* up to the kill point and continue
live from there.  No already-measured schedule touches the device again,
and the final best matches an uninterrupted run (tests/test_chaos_search.py
asserts both).  The solver cursors in ``state.json`` are consistency
metadata: resume sanity-checks the workload config digest against them.

Degraded-mode rows are journaled with their provenance but **not**
restored into the cache: on a healthy resumed device they should be
re-measured, not replayed as if they were measurements.  (Model-answered
queries never reach the journal at all — the learned screen wraps
*outside* the caching/journaling stack, bench.py — but the restore filter
skips any non-``measured`` provenance, so a journal written by a future
layer that does tag ``model`` rows degrades safely too.)
"""

from __future__ import annotations

import hashlib
import json
import os
from typing import Any, Dict, List, Optional, Tuple

from tenzing_tpu.bench.benchmarker import BenchOpts, BenchResult
from tenzing_tpu.obs.metrics import get_metrics
from tenzing_tpu.utils.atomic import atomic_dump_json  # noqa: F401 — re-export:
# the raw helper was born here and grew callers (fault/quarantine.py,
# historical imports); the one definition now lives in utils/atomic.py

CHECKPOINT_VERSION = 1

# the drain daemon wires its lease's fencing token to the checkpoint
# journal through the environment (`<lease-path>:<epoch>`): the drain —
# in-process or a --exec-item subprocess — then refuses to append
# journal lines once a rival claim supersedes the lease (serve/lease.py
# "Epoch fencing"), so a zombie holder cannot interleave stale rows into
# the successor's journal
FENCE_ENV = "TENZING_FENCE"


def _fence_from_env():
    """The env-wired fence check (see :data:`FENCE_ENV`); None when no
    fence is declared.  Parsed lazily per checkpoint object — the daemon
    sets the variable around each drained item."""
    spec = os.environ.get(FENCE_ENV)
    if not spec or ":" not in spec:
        return None
    path, _, epoch_s = spec.rpartition(":")
    try:
        epoch = int(epoch_s)
    except ValueError:
        return None

    def check() -> None:
        from tenzing_tpu.serve.lease import check_epoch

        check_epoch(path, epoch)

    return check

# journal provenance tags: only MEASURED rows restore into the cache
PROVENANCE_MEASURED = "measured"
PROVENANCE_DEGRADED = "degraded"
PROVENANCE_MODEL = "model"


class CheckpointError(RuntimeError):
    """A checkpoint file exists but cannot be trusted (bad digest/version)."""


def _digest(payload_text: str) -> str:
    return hashlib.sha256(payload_text.encode()).hexdigest()


def atomic_write_json(path: str, payload: Dict[str, Any]) -> None:
    """Write ``payload`` as a versioned digest-checked envelope via
    :func:`~tenzing_tpu.utils.atomic.atomic_dump_json`."""
    text = json.dumps(payload, sort_keys=True)
    atomic_dump_json(path, {"version": CHECKPOINT_VERSION,
                            "digest": _digest(text), "payload": payload},
                     prefix=".ckpt.")


def read_checked_json(path: str) -> Dict[str, Any]:
    """Read an :func:`atomic_write_json` envelope, verifying version and
    digest; raises :class:`CheckpointError` on any mismatch."""
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        raise CheckpointError(f"unreadable checkpoint {path}: {e}") from e
    if doc.get("version") != CHECKPOINT_VERSION:
        raise CheckpointError(
            f"checkpoint {path}: version {doc.get('version')!r} != "
            f"{CHECKPOINT_VERSION}")
    payload = doc.get("payload")
    text = json.dumps(payload, sort_keys=True)
    if _digest(text) != doc.get("digest"):
        raise CheckpointError(f"checkpoint {path}: digest mismatch "
                              "(truncated or corrupted)")
    return payload


def _opts_key(opts: Optional[BenchOpts]) -> Optional[List[float]]:
    if opts is None:
        return None
    return [opts.n_iters, opts.max_retries, opts.target_secs]


def _opts_from_key(key) -> Optional[BenchOpts]:
    if key is None:
        return None
    return BenchOpts(n_iters=int(key[0]), max_retries=int(key[1]),
                     target_secs=float(key[2]))


def _result_from_json(j: Dict[str, Any]) -> BenchResult:
    return BenchResult(
        pct01=j["pct01"], pct10=j["pct10"], pct50=j["pct50"],
        pct90=j["pct90"], pct99=j["pct99"], stddev=j["stddev"],
        times=j.get("times"), fetch_overhead=j.get("fetch_overhead"),
    )


class SearchCheckpoint:
    """One checkpoint directory (see module docstring).  ``fence`` is an
    optional zero-arg callable raising
    :class:`~tenzing_tpu.fault.errors.FencedWriteError` when this
    writer's lease has been superseded — checked before every journal
    append and state snapshot; defaults to the daemon's env-wired token
    (:data:`FENCE_ENV`), None when unfenced."""

    def __init__(self, directory: str, fence=None):
        self.dir = directory
        os.makedirs(directory, exist_ok=True)
        self._journal_f = None
        self._state: Dict[str, Any] = {}
        self._fence = fence if fence is not None else _fence_from_env()

    def _check_fence(self) -> None:
        if self._fence is not None:
            self._fence()

    # -- paths -------------------------------------------------------------
    @property
    def state_path(self) -> str:
        return os.path.join(self.dir, "state.json")

    @property
    def journal_path(self) -> str:
        return os.path.join(self.dir, "measurements.jsonl")

    @property
    def quarantine_path(self) -> str:
        return os.path.join(self.dir, "quarantine.json")

    # -- measurement journal ------------------------------------------------
    def record(self, order, opts: Optional[BenchOpts], res: BenchResult,
               provenance: str = PROVENANCE_MEASURED) -> None:
        """Append one measurement, flushed immediately (crash-safe)."""
        from tenzing_tpu.core.serdes import sequence_to_json

        line = json.dumps({
            "opts": _opts_key(opts),
            "prov": provenance,
            "result": res.to_json(),
            "ops": sequence_to_json(order),
        }, sort_keys=True)
        self._check_fence()
        if self._journal_f is None:
            self._journal_f = open(self.journal_path, "a")
        self._journal_f.write(line + "\n")
        self._journal_f.flush()
        os.fsync(self._journal_f.fileno())
        get_metrics().counter("fault.checkpoint.journaled").inc()

    def record_batch(self, ids: List[str], opts: Optional[BenchOpts],
                     seed: int, times: List[List[float]],
                     groups=None) -> None:
        """Append one ``benchmark_batch_times`` result, keyed by the batch
        members' schedule ids (the pair digest) + the decorrelation seed +
        the fidelity key — the paired hill-climb's accept batches replay
        from here on resume instead of re-running on device.  ``groups``
        (when the round was fused from per-group seeds) rides in the key:
        grouped and ungrouped rounds over the same ids are different
        measurements."""
        b = {"ids": list(ids), "seed": seed,
             "opts": _opts_key(opts), "times": times}
        if groups is not None:
            b["groups"] = [[int(n), int(s)] for n, s in groups]
        line = json.dumps({"batch": b}, sort_keys=True)
        self._check_fence()
        if self._journal_f is None:
            self._journal_f = open(self.journal_path, "a")
        self._journal_f.write(line + "\n")
        self._journal_f.flush()
        os.fsync(self._journal_f.fileno())
        get_metrics().counter("fault.checkpoint.journaled_batches").inc()

    def load_measurements(self, graph, log=None) -> List[
            Tuple[Any, Optional[BenchOpts], BenchResult, str]]:
        """Parse the journal against ``graph``; returns (sequence, opts,
        result, provenance) per complete line.  A torn tail line or a row
        whose ops no longer resolve is skipped with a note — a journal is
        an optimization, never a correctness gate."""
        from tenzing_tpu.core.sequence import Sequence
        from tenzing_tpu.core.serdes import op_from_json

        out = []
        if not os.path.exists(self.journal_path):
            return out
        with open(self.journal_path) as f:
            for i, line in enumerate(f):
                if not line.strip():
                    continue
                try:
                    j = json.loads(line)
                    if "batch" in j:
                        continue  # batch lines load via load_batches()
                    seq = Sequence(
                        [op_from_json(oj, graph) for oj in j["ops"]])
                    out.append((seq, _opts_from_key(j["opts"]),
                                _result_from_json(j["result"]),
                                j.get("prov", PROVENANCE_MEASURED)))
                except Exception as e:
                    if log is not None:
                        log(f"checkpoint: journal line {i} skipped "
                            f"({type(e).__name__}: {str(e)[:120]})")
        return out

    def load_batches(self, log=None) -> Dict[Tuple, List[List[float]]]:
        """The journaled batch results keyed by (ids tuple, seed, opts key)
        — no graph resolution needed: batch identity is pure digests.
        Later lines win (a re-run batch supersedes)."""
        out: Dict[Tuple, List[List[float]]] = {}
        if not os.path.exists(self.journal_path):
            return out
        with open(self.journal_path) as f:
            for i, line in enumerate(f):
                if not line.strip():
                    continue
                try:
                    j = json.loads(line)
                    b = j.get("batch")
                    if b is None:
                        continue
                    ok = b["opts"]
                    key = (tuple(b["ids"]), int(b["seed"]),
                           tuple(ok) if ok is not None else None)
                    if b.get("groups") is not None:
                        key = key + (tuple((int(n), int(s))
                                           for n, s in b["groups"]),)
                    out[key] = [list(ts) for ts in b["times"]]
                except Exception as e:
                    if log is not None:
                        log(f"checkpoint: batch journal line {i} skipped "
                            f"({type(e).__name__}: {str(e)[:120]})")
        return out

    def restore_into(self, caching, graph, log=None) -> int:
        """Pre-populate a ``CachingBenchmarker`` from the journal so every
        already-measured schedule is answered without touching the device.
        Only device measurements restore (see module docstring); later
        journal lines win (a re-measurement supersedes).  Journaled *batch*
        results restore into the first :class:`JournalingBenchmarker` found
        on the wrapper chain (``caching.inner...``), so a resumed paired
        hill-climb replays its accept batches too.  Returns the number of
        per-schedule cache entries installed."""
        n = 0
        for seq, opts, res, prov in self.load_measurements(graph, log=log):
            if prov != PROVENANCE_MEASURED:
                continue
            caching._cache[caching._key(seq, opts)] = res
            n += 1
        get_metrics().counter("fault.checkpoint.restored").inc(n)
        layer = caching
        while layer is not None:
            if isinstance(layer, JournalingBenchmarker):
                batches = self.load_batches(log=log)
                layer._batch_cache.update(batches)
                get_metrics().counter(
                    "fault.checkpoint.restored_batches").inc(len(batches))
                break
            layer = getattr(layer, "inner", None)
        return n

    # -- solver-state snapshot ----------------------------------------------
    def save_state(self, state: Optional[Dict[str, Any]] = None,
                   **merge: Any) -> None:
        """Atomically snapshot solver cursors/config.  ``state`` replaces
        the whole document; keyword arguments merge into the current one —
        each solver updates only its own cursor key."""
        if state is not None:
            self._state = dict(state)
        self._state.update(merge)
        self._check_fence()
        # transient EIO retries in-process through THE shared backoff
        # (same rule as store writes): a failed cursor snapshot would
        # otherwise fail the whole drain attempt, and a restarted
        # member replays the identical injected-fault schedule — the
        # item would poison on a bounded burst instead of outliving it
        from tenzing_tpu.fault.backoff import BackoffPolicy, retry_call
        from tenzing_tpu.fault.errors import is_transient_io

        retry_call(
            lambda: atomic_write_json(self.state_path, self._state),
            policy=BackoffPolicy(retries=4, base_secs=0.05, factor=2.0,
                                 max_secs=0.5),
            retry_on=is_transient_io, where="fault.checkpoint.state")

    def load_state(self) -> Optional[Dict[str, Any]]:
        """The last snapshot, digest-verified; None when absent."""
        if not os.path.exists(self.state_path):
            return None
        self._state = read_checked_json(self.state_path)
        return dict(self._state)

    def close(self) -> None:
        if self._journal_f is not None:
            self._journal_f.close()
            self._journal_f = None


class JournalingBenchmarker:
    """Records every successful measurement of the wrapped benchmarker into
    a :class:`SearchCheckpoint` journal.  Sits *inside* the run's
    ``CachingBenchmarker`` (cache hits are already journaled) and *outside*
    the resilient wrapper (only measurements that actually completed are
    journaled; provenance downgraded to ``degraded`` when the resilient
    layer answered from its fallback).

    ``benchmark_batch_times`` — the paired hill-climb's accept primitive —
    is journaled too, keyed by (batch-member schedule ids, seed, fidelity)
    and answered from the restored :attr:`_batch_cache` on resume: a
    resumed climb re-runs **zero** accept batches (the ROADMAP
    paired-resume item).  The driver's verdict batches deliberately bypass
    this wrapper (``bench.py`` calls the resilient layer directly), so the
    final verdict stays freshly measured on every run."""

    def __init__(self, inner, checkpoint: SearchCheckpoint):
        self.inner = inner
        self.checkpoint = checkpoint
        self.rank_coherent = getattr(inner, "rank_coherent", False)
        self._batch_cache: Dict[Tuple, List[List[float]]] = {}
        # journal-answered batch queries (a resumed climb's accept steps):
        # exposed like CachingBenchmarker.hits so budgeted callers
        # (solve/local.py) can treat replayed batches as free
        self.batch_hits = 0
        if hasattr(inner, "benchmark_batch_times"):
            # exposed conditionally, like every wrapper in the stack: the
            # batch protocol is only offered when the wrapped benchmarker
            # has it (hill_climb probes with getattr)
            self.benchmark_batch_times = self._batch_times

    def was_degraded(self, order) -> bool:
        fn = getattr(self.inner, "was_degraded", None)
        return bool(fn(order)) if fn is not None else False

    def benchmark(self, order, opts: Optional[BenchOpts] = None) -> BenchResult:
        res = self.inner.benchmark(order, opts)
        prov = (PROVENANCE_DEGRADED if self.was_degraded(order)
                else PROVENANCE_MEASURED)
        self.checkpoint.record(order, opts, res, provenance=prov)
        return res

    @staticmethod
    def _batch_key(ids, seed: int, opts: Optional[BenchOpts]) -> Tuple:
        ok = _opts_key(opts)
        return (tuple(ids), int(seed), tuple(ok) if ok is not None else None)

    def _batch_times(self, orders, opts: Optional[BenchOpts] = None,
                     seed: int = 0, times_out=None, group_seeds=None):
        from tenzing_tpu.bench.benchmarker import schedule_id

        ids = [schedule_id(o) for o in orders]
        key = self._batch_key(ids, seed, opts)
        if group_seeds is not None:
            # grouped fusion changes each member's permutation stream, so a
            # grouped round and an ungrouped round with the same (ids, seed)
            # are different measurements — keep their journal keys apart
            key = key + (tuple((int(n), int(s)) for n, s in group_seeds),)
        cached = self._batch_cache.get(key)
        if cached is not None:
            self.batch_hits += 1
            get_metrics().counter("fault.checkpoint.batch_hits").inc()
            times = [list(ts) for ts in cached]
            if times_out is not None:
                for dst, src in zip(times_out, times):
                    dst.clear()
                    dst.extend(src)
                return times_out
            return times
        # only forward group_seeds when grouping is requested: inner
        # benchmarkers that predate fused rounds keep their old signature
        kw = {} if group_seeds is None else {"group_seeds": group_seeds}
        out = self.inner.benchmark_batch_times(orders, opts, seed=seed,
                                               times_out=times_out, **kw)
        recorded = [list(ts) for ts in out]
        self._batch_cache[key] = recorded
        self.checkpoint.record_batch(ids, opts, seed, recorded,
                                     groups=group_seeds)
        return out
