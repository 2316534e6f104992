"""Failure taxonomy of the measurement loop.

The empirical loop — compile a candidate schedule, run it fenced on real
hardware, reduce across hosts — fails in three
fundamentally different ways, and each demands a different response
(docs/robustness.md):

* **transient** — the runtime dropped an RPC, a socket reset, a watchdog
  timeout on a hung fetch: *the measurement* failed, not the schedule.
  Retrying (with backoff, fault/backoff.py) is correct and usually works.
* **deterministic** — the *schedule* is broken: it does not compile, its
  liveness exceeds device memory, a shape contract is violated.  Retrying
  re-pays the failing compile for the same verdict; the candidate is
  quarantined (fault/quarantine.py) so it is never measured again, even
  across process restarts.
* **device_lost** — the chip is gone (reboot, preemption, the host lost it
  for good).  No retry can help; the runtime either degrades to recorded +
  predicted answers (fault/resilient.py) or aborts.

:func:`classify_error` maps an arbitrary exception to one of these classes.
Explicit marker types (raised by the fault layer itself and by the
fault-injection harness) classify by ``isinstance``; everything else by
exception type and message patterns.  Unknown errors default to
**deterministic**: an unrecognized failure is most often a broken candidate,
and mis-classifying a transient as deterministic costs one quarantined
candidate, while mis-classifying a deterministic as transient costs
``retries`` failing compiles *per encounter, forever*.
"""

from __future__ import annotations

import errno as _errno


class FaultClass:
    """The three failure classes, ordered by severity (the rank-agreement
    protocol allreduce-maxes the numeric codes, so the *worst* class seen on
    any rank wins — fault/resilient.py)."""

    OK = "ok"
    TRANSIENT = "transient"
    DETERMINISTIC = "deterministic"
    DEVICE_LOST = "device_lost"

    CODES = {OK: 0, TRANSIENT: 1, DETERMINISTIC: 2, DEVICE_LOST: 3}
    FROM_CODE = {v: k for k, v in CODES.items()}


class TransientError(RuntimeError):
    """A measurement attempt failed for reasons unrelated to the schedule
    (runtime/RPC flake); retry with backoff."""


class MeasurementTimeout(TransientError):
    """The watchdog wall-clock bound fired: the measurement hung (a stuck
    collective, a runtime that stalls without erroring).  Transient — the retry
    gets a fresh dispatch — but also the deadlock breaker: a rank that
    would have blocked forever in a barrier instead reports a fault code."""


class StoreLockTimeout(TransientError):
    """The serving store's manifest lock stayed contended past the bounded
    backoff (serve/segments.py: every manifest read-modify-write takes a
    non-blocking flock through fault/backoff.py).  Transient by nature —
    the rival writer will finish; retrying the whole operation later is
    correct, waiting forever inside a serving request is not."""


class DeterministicScheduleError(RuntimeError):
    """The schedule itself is broken (compile/shape/liveness); quarantine."""


class QuarantinedScheduleError(DeterministicScheduleError):
    """Raised instead of re-measuring a schedule already quarantined."""


class UnsoundScheduleError(DeterministicScheduleError):
    """The independent soundness verifier (tenzing_tpu/verify) rejected the
    schedule: a data dependency is unordered or a cross-lane race exists.
    Deterministic by nature — the schedule is *wrong*, not unlucky — so the
    resilient layer quarantines it and it is never measured."""


class DeviceLostError(RuntimeError):
    """The device is unrecoverable; escalate (degrade or abort)."""


class FencedWriteError(RuntimeError):
    """A write was rejected by the lease epoch fence (serve/lease.py): a
    rival claim with a newer epoch exists, so this holder is a zombie —
    reclaimed during a stall on a coarse/skewed-mtime filesystem — and
    its write would be stale.  Classified transient (the item is in
    better hands, never evidence against the request), but the daemon
    treats it specially: abandon, don't retry, don't poison."""


class StoreReadonlyError(TransientError):
    """The schedule store is latched read-only (ENOSPC/EROFS/quota —
    serve/store.py ``store_readonly``): cold/near resolution would need
    a durable write that cannot land.  Transient by nature — space comes
    back, the latch clears on a successful probe — so shed-and-retry-later
    is the designed response (serve/listen.py's ``store_readonly`` shed)."""


# errno values that mean "the filesystem will not take more bytes" — not
# a flake, not worth millisecond-scale retries: latch read-only instead
_UNWRITABLE_ERRNOS = frozenset(
    getattr(_errno, name) for name in ("ENOSPC", "EDQUOT", "EROFS")
    if hasattr(_errno, name))


def is_unwritable_io(exc: BaseException) -> bool:
    """True iff ``exc`` is the full-disk family of OSError (ENOSPC /
    EDQUOT / EROFS): retrying on a backoff timescale cannot help, the
    store must degrade to read-only until a probe write succeeds."""
    return (isinstance(exc, OSError)
            and getattr(exc, "errno", None) in _UNWRITABLE_ERRNOS)


def is_transient_io(exc: BaseException) -> bool:
    """The retry predicate for hardened storage writers (THE shared
    fault/backoff.py): plain I/O flakes (EIO and friends) retry;
    the unwritable family does not (see :func:`is_unwritable_io`)."""
    return isinstance(exc, OSError) and not is_unwritable_io(exc)


# message fragments checked lowercase; order matters only across lists
# (device-lost checked first: "device lost while connection reset" is a loss)
_DEVICE_LOST_PATTERNS = (
    "device lost",
    "device_lost",
    "device or resource busy",
    "chip rebooted",
    "failed to connect to device",
    "device unreachable",
)
_TRANSIENT_PATTERNS = (
    "deadline exceeded",
    "deadline_exceeded",
    "unavailable",
    "connection reset",
    "connection refused",
    "broken pipe",
    "socket closed",
    "rpc error",
    "transient",
    "temporarily",
    "timed out",
    "timeout",
)
# deterministic patterns beat the generic transient words when both match
# ("RESOURCE_EXHAUSTED ... try again" is an OOM, not a flake)
_DETERMINISTIC_PATTERNS = (
    "resource_exhausted",
    "resource exhausted",
    "out of memory",
    "invalid_argument",
    "invalid argument",
    "unimplemented",
    "failed to compile",
    "compilation failure",
    "shape",
)


def classify_error(exc: BaseException) -> str:
    """Map an exception to a :class:`FaultClass` string (see module doc)."""
    if isinstance(exc, DeviceLostError):
        return FaultClass.DEVICE_LOST
    if isinstance(exc, FencedWriteError):
        # a zombie's rejected write is never evidence against the
        # request — the rival that fenced us is draining it right now
        return FaultClass.TRANSIENT
    if isinstance(exc, DeterministicScheduleError):
        return FaultClass.DETERMINISTIC
    if isinstance(exc, TransientError):
        return FaultClass.TRANSIENT
    msg = str(exc).lower()
    for pat in _DEVICE_LOST_PATTERNS:
        if pat in msg:
            return FaultClass.DEVICE_LOST
    for pat in _DETERMINISTIC_PATTERNS:
        if pat in msg:
            return FaultClass.DETERMINISTIC
    if isinstance(exc, (ConnectionError, TimeoutError, InterruptedError)):
        return FaultClass.TRANSIENT
    for pat in _TRANSIENT_PATTERNS:
        if pat in msg:
            return FaultClass.TRANSIENT
    if isinstance(exc, OSError):
        return FaultClass.TRANSIENT
    # shape/type/value errors from a broken candidate; also the default —
    # see module docstring for why unknown leans deterministic
    return FaultClass.DETERMINISTIC


def fault_code(exc: BaseException) -> int:
    """Numeric severity code of an exception's class — what the control
    plane allreduce-maxes in the rank-agreement protocol."""
    return FaultClass.CODES[classify_error(exc)]
