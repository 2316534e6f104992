"""Stable workload fingerprints: the serving store's key space.

A serving key must be (a) **stable** — the same request yields the same
bytes across process restarts, hosts, and argument orderings, so
independently-warmed stores merge instead of fragmenting; and (b)
**bucketed** — nearby shapes share an entry, because a schedule searched
at ``n=512`` is the right warm answer for ``n=480`` (the schedule is a
*structure*; only its measured numbers are shape-specific).

The fingerprint is the tuple the ISSUE names:

* **workload kind + variant** — ``halo``/``spmv``/``attn``/``moe``,
  smoke vs full (the two build different choice graphs, so their
  schedules are not interchangeable);
* **shape** — the exact builder-resolved shape parameters
  (:func:`~tenzing_tpu.bench.driver.workload_shape` — THE single source,
  kept next to the builders), plus their power-of-two **bucket**;
* **mesh signature** — the search platform's lane count
  (:func:`~tenzing_tpu.bench.driver.search_lanes`, the same default rule
  the driver applies);
* **engine kind-sets** — ``bench/model.py``'s ``ICI_KINDS``/``PCIE_KINDS``:
  the transfer-engine vocabulary the analytic model and the surrogate
  featurizer agree on.  A change to the engine model changes every
  fingerprint, which is correct: stored schedules were searched (and the
  surrogate trained) under the old vocabulary.

Two digests derive from it: ``exact_digest`` keys exact hits (precise
shape), ``bucket_digest`` keys the near-miss neighborhood (bucketed
shape).  Both are ``sha1`` short digests of sorted-key canonical JSON —
no Python ``hash()``, no dict-order dependence, no ``PYTHONHASHSEED``
sensitivity (tests/test_serve_fingerprint.py pins this across
subprocesses with different hash seeds).

Schedules themselves key by the existing
:func:`~tenzing_tpu.core.sequence.canonical_key` modulo redundant syncs
(:func:`schedule_key`) — the same equivalence every benchmark cache,
verifier and recorded database already matches on.
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from functools import cached_property
from typing import Any, Dict, Tuple

from tenzing_tpu.bench.model import ICI_KINDS, PCIE_KINDS
from tenzing_tpu.obs.tracer import short_digest

FINGERPRINT_VERSION = 1


def shape_bucket(n: int) -> int:
    """THE bucketing rule: the next power of two at or above ``n`` (0 for
    non-positive).  Geometric buckets match how schedule structure scales
    — a halo at 300^3 and 512^3 cells wants the same overlap discipline,
    while 512 vs 513 crossing a boundary is the price of a rule simple
    enough to pin with golden tests (boundaries: 2^k maps to 2^k, 2^k+1
    to 2^(k+1))."""
    if n <= 0:
        return 0
    return 1 << (int(n) - 1).bit_length()


def _canonical(doc: Any) -> str:
    """Deterministic serialization: sorted keys, no whitespace variance,
    ASCII-safe — the byte stream both digests hash."""
    return json.dumps(doc, sort_keys=True, separators=(",", ":"))


@dataclass(frozen=True)
class WorkloadFingerprint:
    """The serving key of one workload configuration (see module
    docstring).  ``shape``/``bucket``/``mesh`` are sorted name/value
    tuples so construction order can never leak into the digest."""

    workload: str
    variant: str  # "smoke" | "full"
    shape: Tuple[Tuple[str, int], ...]
    bucket: Tuple[Tuple[str, int], ...]
    mesh: Tuple[Tuple[str, int], ...]
    engines: Tuple[Tuple[str, Tuple[str, ...]], ...]

    def _digest(self, shape_field: Tuple) -> str:
        return short_digest(_canonical({
            "v": FINGERPRINT_VERSION,
            "workload": self.workload,
            "variant": self.variant,
            "shape": [list(kv) for kv in shape_field],
            "mesh": [list(kv) for kv in self.mesh],
            "engines": [[k, list(v)] for k, v in self.engines],
        }))

    # cached: one resolution touches each digest several times (cache
    # probe, span attributes, response serialization), and each compute
    # is a canonical-JSON dump + sha1 — real microseconds on the
    # serving hot path.  ``cached_property`` stores into ``__dict__``
    # directly, which a frozen dataclass permits; the fingerprint is
    # immutable, so the cache can never go stale.
    @cached_property
    def exact_digest(self) -> str:
        """Keys exact hits: precise shape."""
        return self._digest(self.shape)

    @cached_property
    def bucket_digest(self) -> str:
        """Keys the near-miss neighborhood: bucketed shape."""
        return self._digest(self.bucket)

    def to_json(self) -> Dict[str, Any]:
        return {
            "version": FINGERPRINT_VERSION,
            "workload": self.workload,
            "variant": self.variant,
            "shape": {k: v for k, v in self.shape},
            "bucket": {k: v for k, v in self.bucket},
            "mesh": {k: v for k, v in self.mesh},
            "engines": {k: list(v) for k, v in self.engines},
            "exact": self.exact_digest,
            "bucket_digest": self.bucket_digest,
        }

    @classmethod
    def from_json(cls, j: Dict[str, Any]) -> "WorkloadFingerprint":
        return cls(
            workload=j["workload"],
            variant=j["variant"],
            shape=_sorted_items(j["shape"]),
            bucket=_sorted_items(j["bucket"]),
            mesh=_sorted_items(j["mesh"]),
            engines=tuple(sorted(
                (k, tuple(v)) for k, v in j["engines"].items())),
        )


def _sorted_items(d: Dict[str, int]) -> Tuple[Tuple[str, int], ...]:
    return tuple(sorted((str(k), int(v)) for k, v in d.items()))


def fingerprint_of(req) -> WorkloadFingerprint:
    """The fingerprint of a :class:`~tenzing_tpu.bench.driver.
    DriverRequest` — pure request arithmetic (no jax, no buffers, no
    backend): the serving front door must fingerprint a request on a host
    with no accelerator."""
    from tenzing_tpu.bench.workloads import search_lanes, workload_shape

    shape = workload_shape(req)
    return WorkloadFingerprint(
        workload=req.workload,
        variant="smoke" if req.smoke else "full",
        shape=_sorted_items(shape),
        bucket=_sorted_items({k: shape_bucket(v) for k, v in shape.items()}),
        mesh=_sorted_items({"lanes": search_lanes(req)}),
        engines=tuple(sorted((("ici", tuple(ICI_KINDS)),
                              ("pcie", tuple(PCIE_KINDS))))),
    )


def schedule_key(seq) -> str:
    """The store's schedule key: a short digest of the canonical form
    modulo redundant syncs — the SAME equivalence the benchmark cache,
    the verifier cache, and ``CsvBenchmarker(normalize=True)`` match on,
    so a DFS-dumped and an MCTS-cleaned spelling of one program occupy
    one store slot."""
    from tenzing_tpu.core.schedule import remove_redundant_syncs
    from tenzing_tpu.core.sequence import canonical_key

    return short_digest(repr(canonical_key(remove_redundant_syncs(seq))))
