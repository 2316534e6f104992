"""The in-process schedule service: warm / query / merge / stats.

Composes the serving pieces into the one object a host embeds (and the
``python -m tenzing_tpu.serve`` CLI wraps, serve/__main__.py):

* ``warm`` — mine recorded search databases (``bench.py --dump-csv``
  corpora) into the store under the corpus workload's fingerprint:
  per-file in-file paired ratios against the row-0 naive anchor (the
  same regime-honest ranking bench/recorded.py warm-starts from), top-k
  distinct winners by ``canonical_key`` equivalence, sha256 source
  digests in provenance.  Optionally trains the PR-2 surrogate on the
  same corpus (the near tier's pricing model) and stamps driver-JSON
  verdict provenance onto the warmed entries.
* ``query`` — tiered resolution (serve/resolver.py).
* ``merge`` — combine independently-warmed stores (commutative,
  idempotent — serve/store.py).
* ``stats`` — store + queue occupancy for dashboards and the corpus
  report CLI (``python -m tenzing_tpu.obs.report --store``).

The service never opens a device: warm deserializes and featurizes
against the driver's device-free graphs
(:func:`~tenzing_tpu.bench.driver.graph_for`), and resolution is
store/model arithmetic.  Measurement happens only when a driver drains
the cold-request work queue.
"""

from __future__ import annotations

import glob as _glob
import os
from typing import Any, Callable, Dict, List, Optional

from tenzing_tpu.bench.driver import DriverRequest
from tenzing_tpu.bench.workloads import graph_for, metric_for
from tenzing_tpu.obs.metrics import get_metrics
from tenzing_tpu.obs.tracer import get_tracer
from tenzing_tpu.serve.fingerprint import fingerprint_of, schedule_key
from tenzing_tpu.serve.resolver import Resolution, Resolver
from tenzing_tpu.serve.store import ScheduleStore, WorkQueue, open_store


def default_model_path(store_path: str) -> str:
    """Where ``warm --train`` saves the surrogate next to its store —
    one convention shared by the CLI and the service so a warmed store
    directory is self-contained.  Works for both backends: a trailing
    separator on a segmented store *directory* is stripped so the model
    lands beside the store, never hidden inside it."""
    return store_path.rstrip(os.sep).rstrip("/") + ".model.json"


class ScheduleService:
    """See module docstring.  ``model_path`` defaults next to the store;
    an existing model loads eagerly (the near tier needs it), a missing
    one leaves near-miss resolution disabled until ``warm(train=True)``
    creates it.

    Point ``model_path`` only at a surrogate trained with the SAME
    device-free ``nbytes`` map resolution featurizes with — i.e. one
    ``warm(train=True)`` produced.  A model from ``bench.py
    --learn-train`` on a TPU host was trained against real device-buffer
    sizes; for workloads where :func:`~tenzing_tpu.bench.driver.
    graph_for` returns an empty map (full-size halo), its comm-bytes and
    makespan features would be systematically shifted at predict time,
    miscalibrating the near tier's uncertainty gate (the train/predict
    feature contract, learn/train.py)."""

    def __init__(self, store_path: str, queue_dir: Optional[str] = None,
                 model_path: Optional[str] = None, tenant: str = "local",
                 verify: bool = True, near_max_sigma: float = 0.75,
                 log: Optional[Callable[[str], None]] = None):
        self._log = log
        # .json paths open the legacy monolithic store; anything else
        # opens the segmented store (serve/store.py open_store — one
        # dispatch rule for every entry point)
        self.store = open_store(store_path, tenant=tenant, log=log)
        self.queue = WorkQueue(queue_dir) if queue_dir else None
        self.verify = verify
        self.model_path = model_path or default_model_path(store_path)
        self.model = self._load_model()
        self.resolver = Resolver(self.store, queue=self.queue,
                                 model=self.model, verify=verify,
                                 near_max_sigma=near_max_sigma, log=log)

    def _note(self, msg: str) -> None:
        if self._log is not None:
            self._log(msg)

    def _load_model(self):
        if not os.path.exists(self.model_path):
            return None
        from tenzing_tpu.learn import FEATURE_NAMES, RidgeEnsemble

        return RidgeEnsemble.load(self.model_path,
                                  expect_features=list(FEATURE_NAMES))

    # -- warm ----------------------------------------------------------------
    def warm(self, req: DriverRequest, csv_globs: List[str],
             bench_globs: Optional[List[str]] = None, topk: int = 3,
             train: bool = True) -> Dict[str, Any]:
        """Mine recorded corpora for ``req``'s workload into the store
        (see module docstring); returns a summary dict."""
        from tenzing_tpu.bench.recorded import scored_rows

        tr = get_tracer()
        paths = sorted(p for pat in csv_globs for p in _glob.glob(pat))
        fp = fingerprint_of(req)
        graph, nbytes = graph_for(req)
        with tr.span("serve.warm", workload=req.workload,
                     n_files=len(paths)):
            # THE shared admission/ranking rule (bench/recorded.py):
            # the serving corpus and the search's warm-start loader can
            # never drift on which recorded rows count
            scored, stats = scored_rows(paths, graph, log=self._note)
            seen: set = set()
            added = rejected = 0
            verifier = None
            for ratio, pct50, seq, path in scored:
                if added >= topk:
                    break
                key = schedule_key(seq)
                if key in seen:
                    continue
                seen.add(key)
                # ADMISSION-TIME verification (docs/serving.md): verify
                # once, here, under this fingerprint's graph — the exact
                # tier then serves the stamped record with zero per-query
                # verifier invocations.  An unsound row is stored flagged
                # (visible in stats/report, never served, never counted
                # against topk) — the PR-7 never-serve-unsound guarantee
                # moves to the door instead of being re-proved per query.
                verified = None
                if self.verify:
                    if verifier is None:
                        from tenzing_tpu.verify import ScheduleVerifier

                        verifier = ScheduleVerifier(graph)
                    verified = bool(verifier(seq).ok)
                    if not verified:
                        get_metrics().counter(
                            "serve.admission.unsound").inc()
                        self._note(f"serve: admission rejected unsound "
                                   f"{key[:8]} from "
                                   f"{os.path.basename(path)} — stored "
                                   "flagged, never served")
                        self.store.add(fp, seq, pct50_us=pct50 * 1e6,
                                       vs_naive=ratio, source=path,
                                       verified=False)
                        rejected += 1
                        continue
                    get_metrics().counter("serve.admission.verified").inc()
                self.store.add(fp, seq, pct50_us=pct50 * 1e6,
                               vs_naive=ratio, source=path,
                               verified=verified)
                added += 1
            summary: Dict[str, Any] = {
                "workload": req.workload, "exact": fp.exact_digest,
                "bucket": fp.bucket_digest, "files": stats["files"],
                "rows": stats["rows"], "candidates": len(scored),
                "added": added,
                "admission": {"verified": added if self.verify else None,
                              "rejected_unsound": rejected},
            }
            if bench_globs:
                summary["driver_provenance"] = self._stamp_driver_jsons(
                    req, fp, bench_globs)
            if train:
                summary["model"] = self._train(req, paths, graph, nbytes)
            self.store.flush()
        get_metrics().counter("serve.warmed").inc(added)
        return summary

    def _stamp_driver_jsons(self, req: DriverRequest, fp,
                            bench_globs: List[str]) -> Dict[str, Any]:
        """Attach driver-JSON verdict provenance (vs_baseline, the
        result-integrity gate's ``verified`` stamp) to the warmed
        fingerprint — the store records not just what the corpus says
        but what the last full driver runs concluded."""
        from tenzing_tpu.obs.report import load_driver_json

        metric = metric_for(req.workload, req)
        matched = 0
        best_vs = None
        verified = None
        for pat in bench_globs:
            for path in sorted(_glob.glob(pat)):
                try:
                    d = load_driver_json(path)
                except (OSError, ValueError):
                    continue
                if d.get("metric") != metric:
                    continue
                matched += 1
                vs = d.get("vs_baseline")
                if vs is not None and (best_vs is None or vs > best_vs):
                    best_vs = vs
                    verified = (d.get("fault") or {}).get("verified")
        out = {"matched": matched, "best_vs_baseline": best_vs,
               "verified": verified}
        rec = self.store.best(fp.exact_digest)
        if rec is not None and matched:
            rec.setdefault("provenance", {})["driver"] = out
        return out

    def _train(self, req: DriverRequest, paths: List[str], graph,
               nbytes) -> Dict[str, Any]:
        """Train the near tier's surrogate on the warmed corpus through
        THE shared recipe (learn/train.py — the same call behind
        ``bench.py --learn-train``), with this workload's device-free
        ``nbytes`` map so train-time and resolve-time features agree by
        construction."""
        from tenzing_tpu.learn import train_from_corpus

        model, info = train_from_corpus(paths, graph, nbytes=nbytes,
                                        log=self._note)
        if model is None:
            return info
        # warm trains before the store's first flush creates the
        # directory — the model save must not trip over it either
        os.makedirs(os.path.dirname(os.path.abspath(self.model_path)),
                    exist_ok=True)
        model.save(self.model_path)
        self.model = model
        self.resolver.model = model
        return {"path": self.model_path, "rows": info["rows"],
                "train_spearman": info["train_spearman"]}

    # -- query / merge / stats ----------------------------------------------
    def query(self, req: DriverRequest,
              fp_key: Optional[tuple] = None) -> Resolution:
        """Tiered resolution.  ``fp_key`` (the verbatim request-kwargs
        tuple, :func:`~tenzing_tpu.serve.resolver.fp_cache_key`) seeds
        the fingerprint cache and the lock-free fast path for callers
        that have the raw kwargs (the listen loop)."""
        return self.resolver.resolve(req, fp_key=fp_key)

    def merge(self, other_path: str) -> Dict[str, Any]:
        other = ScheduleStore(other_path, log=self._note)
        n = self.store.merge_from(other)
        self.store.flush()
        return {"merged_records": n, "from": other_path,
                "records": len(self.store)}

    def stats(self) -> Dict[str, Any]:
        out = {"store": self.store.stats(),
               "model": (self.model_path
                         if os.path.exists(self.model_path) else None)}
        if self.queue is not None:
            # full queue stats (serve/store.py WorkQueue.stats): depth by
            # reason plus the drain-daemon protocol state — the torn set
            # (visible rot, never silently dropped), live leases with
            # heartbeat ages, and the poison quarantine
            out["queue"] = self.queue.stats()
        return out
