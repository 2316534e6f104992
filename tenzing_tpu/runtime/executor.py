"""Schedule execution: lower a searched schedule to one compiled XLA program.

This is the TPU-native answer to the reference's dispatch model (SURVEY.md
§7.0/§7.2).  Where the reference *runs* each op at benchmark time — CUDA kernels
enqueued on ``cudaStream_t``, ordered by ``cudaEvent_t``
(benchmarker.cpp:83-119 hot loop, ops_cuda.cpp:48-130) — here the schedule's
happens-before structure is *traced into the HLO dependency graph* and XLA's
latency-hiding scheduler executes under exactly those constraints:

* each **lane** is a chain of ordering tokens: ops bound to the same lane are
  serialized in sequence order, ops on different lanes share no chain and may
  overlap (async DMA / collective / host-transfer overlap is XLA's to exploit);
* an **EventRecord** snapshots a lane's token; **WaitEvent** joins it into
  another lane's chain; **EventSync**/**LaneSync** join into the HOST chain —
  exact analogs of cudaEventRecord / cudaStreamWaitEvent / cudaEventSynchronize
  / cudaStreamSynchronize;
* **host ops** (CpuOp) form their own chain (host program order), and every
  device op joins the host token — a kernel cannot launch before prior host ops,
  matching CUDA dispatch semantics;
* **data dependencies are always honored**: buffers are SSA values in a dict, so
  a searched schedule cannot race — the token edges it chose are a superset of
  the graph's data edges (the reference achieves the same by the
  EventSynchronizer's construction, SURVEY.md §5).

Token realization — WHY NOT ``optimization_barrier``: measured on real TPU
hardware (v5e), the TPU backend *strips* ``opt-barrier`` during compilation
(post-optimization HLO contains zero ``opt-barrier`` instructions), so
barrier-chained schedules all lower to the same executable and timing is
schedule-independent.  Tokens here are therefore **real data dependencies** the
compiler cannot erase: a token is a finite float32 scalar derived from the
producer's output, and ``tie(x, t)`` computes ``x + select(t != t, t, 0)`` — a
value-preserving add (tokens are NaN-cleaned at creation so the select always
yields 0 at runtime) that XLA cannot constant-fold because proving the select
is zero would require value analysis it does not do.  Measured effect (64 MB
host-offload + 16x4096^3 bf16 matmul chain, TPU v5e): fully-serialized schedule
20.8 ms/iter (= sum of parts), 2-lane schedule 14.0 ms/iter (= overlap) — the
schedule space is physically real on hardware under this encoding.

Because each candidate schedule is its own compiled program, compile time is
excluded from measurement (compile once, cache by schedule JSON) and the
benchmarker fences with a device->host fetch per measurement (a fence that
is correct on any backend, including one whose ``block_until_ready`` returns
early; see bench/benchmarker.py).
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from typing import Any, Callable, Dict, Iterator, List, Optional

import jax
import jax.numpy as jnp
from jax import lax

from tenzing_tpu.core.operation import BoundDeviceOp, OpBase, unbound
from tenzing_tpu.core.platform import Platform
from tenzing_tpu.core.resources import Event, Lane
from tenzing_tpu.core.sequence import Sequence
from tenzing_tpu.core.serdes import sequence_to_json_str
from tenzing_tpu.obs import scopes
from tenzing_tpu.obs.metrics import get_metrics
from tenzing_tpu.obs.tracer import get_tracer, short_digest


# -- the first call of a program, in its parts --------------------------------
#
# jax.jit traces, lowers, compiles and runs inside ONE call, and an AOT
# ``.lower().compile()`` is two; both tell ``jax.monitoring`` when the
# StableHLO module is built and when the backend's compile (and load)
# returns, on the thread that did the work.  An open first call
# (:meth:`TraceExecutor._first_call`) walks its three child spans on those
# two signals, so the lazy path keeps its lazily jitted callable and the
# spans are real-time intervals (mirrored into a profiler session).

_LOWERED = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILED = "/jax/core/compile/backend_compile_duration"
_FIRST_CALL_PARTS = ("executor.lower", "executor.xla_compile",
                     "executor.first_run")
# a compiled program's sizes, as ``memory_analysis()`` names them
_PROGRAM_SIZES = ("temp", "argument", "output", "alias", "generated_code")
_first_call_open = threading.local()  # .parts: this thread's open first call


class _FirstCallParts:
    """The child spans of one ``executor.first_call``, entered in order and
    back to back: ``executor.lower`` from the call until the module is
    lowered, ``executor.xla_compile`` until the backend hands back a loaded
    executable, ``executor.first_run`` (``n``; none for an AOT compile,
    which runs nothing) until the fence is fetched."""

    def __init__(self, run_n: Optional[int]):
        self._attrs = ({}, {}, {"n": run_n})
        self._n_parts = 2 if run_n is None else 3
        self._at = -1
        self._ctx = None
        self.advance(0)

    def advance(self, to: int) -> None:
        if to != self._at + 1:
            return  # a nested or repeated signal: parts only move forward
        self.close()
        self._at = to
        if to < self._n_parts:
            self._ctx = get_tracer().span(_FIRST_CALL_PARTS[to],
                                          **self._attrs[to])
            self._ctx.__enter__()

    def close(self) -> None:
        if self._ctx is not None:
            self._ctx.__exit__(None, None, None)
            self._ctx = None


def _on_jax_duration(event: str, duration: float, **kw) -> None:
    parts = getattr(_first_call_open, "parts", None)
    if parts is not None:
        if event == _LOWERED:
            parts.advance(1)
        elif event == _COMPILED:
            parts.advance(2)


jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)


def _vertex_scope(op):
    """The ``jax.named_scope`` of a schedule's vertex (obs/scopes.py: the
    grammar, who reads it, what it costs)."""
    return jax.named_scope(scopes.vertex_scope(op.name()))


def _sync_scope(kind: str):
    return jax.named_scope(scopes.sync_scope(kind))


def _scalarize(leaf) -> Any:
    """A float32 scalar data-dependent on ``leaf`` (its first element)."""
    x = jnp.asarray(leaf).reshape(-1)[0]
    if jnp.issubdtype(x.dtype, jnp.complexfloating):
        x = jnp.real(x)
    return x.astype(jnp.float32)


def _clean(t):
    """Scrub a token scalar to a finite value (select is opaque to constant
    folding).  Inf must go too: joins sum tokens, and inf + (-inf) = NaN would
    poison every downstream tie."""
    return lax.select(jnp.isfinite(t), t, jnp.zeros((), t.dtype))


def datatie(value, tok):
    """``value`` unchanged, but consumers now also wait for ``tok``.

    ``tok`` must be a cleaned (never-NaN) float32 scalar, so the select always
    takes the zero branch at runtime; the compiler cannot prove that, so the
    data edge survives TPU compilation (unlike ``optimization_barrier``).
    """
    z = lax.select(tok != tok, tok, jnp.zeros((), tok.dtype))
    if jnp.issubdtype(jnp.asarray(value).dtype, jnp.bool_):
        return jnp.logical_or(value, z != 0.0)
    return value + z.astype(jnp.asarray(value).dtype)


class TraceContext:
    """Mutable tracing state threaded through one schedule trace: the buffer
    dict (SSA), one token per lane, the host token, and one token per event.

    ``tokens`` (optional) seeds the chains — the benchmark loop carries token
    state across samples so a serialized schedule stays serialized from one
    sample to the next (the reference's cudaStream chains likewise persist
    across the hot loop's samples, benchmarker.cpp:93-99)."""

    def __init__(
        self,
        bufs: Dict[str, Any],
        axis_names=(),
        tokens: Optional[Dict[str, Any]] = None,
        host_space: Optional[set] = None,
    ):
        self.bufs = bufs
        self.axis_names = tuple(axis_names)
        # names of buffers resident in host memory: the TPU toolchain only
        # supports pure copies on host-space tensors (no arithmetic/slicing —
        # measured: host-side add/reshape/slice fail to compile), so ties,
        # awaits and fences must skip them
        self.host_space: set = set(host_space) if host_space else set()
        # in-flight transfers with an explicit completion handle: buffer name
        # -> closure(value) that blocks on the transfer's semaphores and
        # returns the completed value (split-kernel RDMA, ops/rdma.py).
        # Transient within one trace: the posting op stashes the closure, the
        # awaiting op settles it — a schedule always contains both, so nothing
        # here ever crosses the benchmark loop's carry.
        self.inflight: Dict[str, Any] = {}
        # int32 zero tied to the CURRENT op's token — set by trace_default
        # only for INDEX_TIE ops (None otherwise, so stale consumption by an
        # op outside the contract fails loudly)
        self.tok_index_zero: Any = None
        self._zero = jnp.zeros((), jnp.float32)
        if tokens is None:
            self._lane_tok: Dict[int, Any] = {}
            self._ev_tok: Dict[int, Any] = {}
            self._host_tok = self._zero
        else:
            self._lane_tok = dict(tokens["lanes"])
            self._ev_tok = dict(tokens["events"])
            self._host_tok = tokens["host"]

    def token_state(self) -> Dict[str, Any]:
        """The chains' current tips, in a fori_loop-carryable pytree."""
        return {
            "host": self._host_tok,
            "lanes": dict(self._lane_tok),
            "events": dict(self._ev_tok),
        }

    # -- token plumbing ----------------------------------------------------
    def _lane(self, lane: Lane):
        return self._lane_tok.get(lane.id, self._zero)

    def _join(self, *toks):
        toks = [t for t in toks if t is not None]
        if not toks:
            return self._zero
        out = toks[0]
        for t in toks[1:]:
            out = out + t
        return out

    def _tie(self, value, tok):
        """Value unchanged, but consumers now also wait for ``tok``."""
        with jax.named_scope("tie"):
            return datatie(value, tok)

    def tie_named(self, name: str, value, tok):
        """Tie, unless ``name`` is host-resident (host-space tensors admit no
        arithmetic; ordering then rests on data dependencies alone)."""
        if name in self.host_space:
            return value
        return self._tie(value, tok)

    def tie_by_index(self, tok) -> None:
        """Hand the op about to be applied its token as
        ``tok_index_zero``, an int32 zero that depends on ``tok``, and
        count it (``executor.index_ties``)."""
        with jax.named_scope("tie"):
            self.tok_index_zero = jnp.where(tok != tok, 1, 0).astype(jnp.int32)
        get_metrics().counter("executor.index_ties").inc()

    def trace_op(self, op) -> None:
        """``op.trace(self)`` inside the vertex's scope: whatever the op
        puts on the device, through :meth:`_apply_op` or a ``trace`` of its
        own (ops/comm_ops.py), carries its name.  A sync op is no vertex:
        its hook below scopes what it emits."""
        if getattr(op, "is_sync", lambda: False)():
            op.trace(self)
        else:
            with _vertex_scope(op):
                op.trace(self)

    # -- op tracing --------------------------------------------------------
    @staticmethod
    def _approx_nbytes(val) -> int:
        total = 0
        for l in jax.tree_util.tree_leaves(val):
            size = getattr(l, "size", None)
            dt = getattr(l, "dtype", None)
            if size is not None and dt is not None:
                total += int(size) * jnp.dtype(dt).itemsize
        return total

    def trace_default(self, op) -> None:
        """Trace a BoundOp: tie ONE of its reads to its chain token, apply,
        chain the written values back into the token."""
        is_device = isinstance(op, BoundDeviceOp)
        if is_device:
            with jax.named_scope("tie"):
                tok_in = self._join(self._lane(op.lane()), self._host_tok)
        else:
            tok_in = self._host_tok
        tok_out = self._apply_op(op, tok_in)
        if is_device:
            self._lane_tok[op.lane().id] = tok_out
        else:
            self._host_tok = tok_out

    def trace_fused(self, op) -> None:
        """Trace a multi-lane fused-region op (runtime/fused.py): join EVERY
        member lane's chain plus the host chain, tie one read, apply the
        fused kernel, and advance ALL member lanes to the output token.

        Advancing every lane makes the fused region a conservative barrier
        across the lanes it absorbed — a strict superset of the ordering
        the member ops had individually, so replacing them with the fused
        op can never drop a happens-before edge (it can only add them; the
        cost is overlap the megakernel now owns internally)."""
        lanes = op.lanes()
        with jax.named_scope("tie"):
            tok_in = self._join(
                *[self._lane(l) for l in lanes], self._host_tok)
        tok_out = self._apply_op(op, tok_in)
        for l in lanes:
            self._lane_tok[l.id] = tok_out

    def _apply_op(self, op, tok_in):
        """The shared tie-apply-writeback-join body of ``trace_default`` and
        ``trace_fused``: returns the output token (callers route it into
        the right chain(s)).

        One tied read is sufficient for the happens-before semantics — an op
        cannot start until EVERY input is ready, so making any one input
        depend on the token delays the whole op.  How the op takes the
        token is the op's to declare:

        * by value (the default): a value-preserving add of the token's zero
          onto the op's SMALLEST read — a full pass over that buffer, and a
          new version of it that whoever else reads it does not share;
        * by index (``INDEX_TIE = True``): the op consumes
          ``ctx.tok_index_zero`` (an int32 0 that depends on the token) in
          its slice/update indices and gets its reads untouched.  For an op
          whose only read is large and shared — the halo packs, which is
          where the cost was measured: models/halo.py ``Pack``.

        The program's counters ``executor.index_ties`` and
        ``executor.value_tied_bytes`` say, per traced program body, how many
        ops took the token by index and how many bytes got a value-add: a
        tie that lands on a large buffer reads there, not only as a
        ``broadcast_add_fusion`` in a device trace."""
        view = self.bufs
        reg = get_metrics()
        if getattr(unbound(op), "INDEX_TIE", False):
            self.tie_by_index(tok_in)
        else:
            self.tok_index_zero = None  # stale-consumption guard
            reads = [n for n in op.reads() if n not in self.host_space]
            if reads:
                view = dict(self.bufs)
                name = min(reads, key=lambda n: (self._approx_nbytes(view[n]), n))
                reg.counter("executor.value_tied_bytes").inc(
                    self._approx_nbytes(view[name]))
                view[name] = self._tie(view[name], tok_in)
        with jax.named_scope("apply"):
            out = op.apply(view, self)
        for name, val in out.items():
            if name not in self.bufs:
                raise KeyError(
                    f"op {op.desc()!r} writes undeclared buffer {name!r}; declare "
                    "it in the executor's initial buffers"
                )
            self.bufs[name] = val
        leaves = [
            l
            for name, val in out.items()
            if name not in self.host_space
            for l in jax.tree_util.tree_leaves(val)
        ]
        with jax.named_scope("join"):
            return self._join(
                tok_in, *[_clean(_scalarize(l)) for l in leaves])

    # -- sync-op hooks (core/sync_ops.py) ----------------------------------
    def record_event(self, lane: Lane, event: Event) -> None:
        self._ev_tok[event.id] = self._lane(lane)  # emits nothing

    def wait_event(self, lane: Lane, event: Event) -> None:
        ev = self._ev_tok.get(event.id, self._zero)
        with _sync_scope("wait_event"):
            self._lane_tok[lane.id] = self._join(self._lane(lane), ev)

    def sync_event_host(self, event: Event) -> None:
        ev = self._ev_tok.get(event.id, self._zero)
        with _sync_scope("event_sync"):
            self._host_tok = self._join(self._host_tok, ev)

    def sync_lane_host(self, lane: Lane) -> None:
        with _sync_scope("lane_sync"):
            self._host_tok = self._join(self._host_tok, self._lane(lane))

    def wait_lane(self, waiter: Lane, waitee: Lane) -> None:
        with _sync_scope("lane_wait"):
            self._lane_tok[waiter.id] = self._join(
                self._lane(waiter), self._lane(waitee))


def _compiled_of(jitted, *args):
    """The ``Compiled`` a jitted callable's call on ``args`` just ran: after
    that call, lowering the same arguments again finds jax's traced jaxpr,
    its lowering and its executable where the call left them (under a
    millisecond, no second compile: tests/test_op_scopes.py holds it to
    that)."""
    return jitted.lower(*args).compile()


def _fence_of(values) -> Any:
    """The full-reduction fence: one float32 scalar summed from every leaf
    of ``values``, under ``tz.fence``."""
    with jax.named_scope(scopes.SCOPE + scopes.FENCE):
        fence = jnp.zeros((), jnp.float32)
        for leaf in jax.tree_util.tree_leaves(list(values)):
            x = jnp.asarray(leaf)
            if jnp.issubdtype(x.dtype, jnp.complexfloating):
                x = jnp.real(x)
            fence = fence + jnp.sum(x).astype(jnp.float32)
    return fence


def evolve_host_space(names: set, op: OpBase) -> None:
    """Apply ONE op's transfer semantics to the host-space name set, in
    place: an op declaring ``DST_SPACE`` (ops/comm_ops.py) deterministically
    moves its writes into ("host") or out of ("device") host memory; every
    other op leaves the set untouched.  THE one copy of the space-evolution
    rule — ``TraceExecutor._host_space_after`` folds it over a schedule and
    the fusion partitioner (``runtime/fused.py::partition_regions``) steps
    it op-by-op while cutting regions, so a new memory space or a changed
    DST_SPACE convention lands in both or neither."""
    dst_space = getattr(unbound(op), "DST_SPACE", None)
    if dst_space is not None:
        for w in op.writes():
            if dst_space == "host":
                names.add(w)
            else:
                names.discard(w)


def _check_inflight_drained(tc: "TraceContext") -> None:
    """End-of-trace guard: a split-kernel transfer posted without a matching
    await would leave its wait closure in ``tc.inflight`` and downstream
    consumers would read an in-flight buffer on TPU — a *silent* data race.
    Every schedule the solvers emit pairs post with await (the graph contains
    both), so leftovers are a graph-construction bug; fail loudly (ADVICE r3)."""
    if tc.inflight:
        raise ValueError(
            "schedule ended with un-awaited in-flight transfers for buffers "
            f"{sorted(tc.inflight)}; every split-kernel post (e.g. "
            "RdmaCopyStart) needs a matching AwaitTransfer/MultiAwait in the "
            "schedule"
        )


class TraceExecutor:
    """Compiles schedules to XLA programs and runs them (the ``ScheduleRunner``
    the EmpiricalBenchmarker consumes).

    All buffer names must be declared in ``init_bufs``; when the platform has a
    mesh, the trace runs under ``shard_map`` with the platform's per-buffer
    partition specs, and comm ops may use collectives over the mesh axes.
    """

    def __init__(self, platform: Platform, init_bufs: Dict[str, Any],
                 one_shot_as_loop: bool = False):
        self.platform = platform
        self.init_bufs = dict(init_bufs)
        # the one-shot program (compile / run) as the repeat-n loop run
        # once: see compile()
        self._one_shot_as_loop = one_shot_as_loop
        self._cache: Dict[str, Callable] = {}
        # "n:" keys that precompile() put there and nobody has run yet
        self._unrun: set = set()
        # compile-provenance tallies (the driver's ``perf`` meta block):
        # programs actually traced+XLA-compiled by THIS process and the wall
        # seconds they took — cache hits (in-memory or the persistent
        # compile cache's fast path) are visible as cheap entries, never as
        # missing ones.  Guarded by a lock: the prefetch pipeline
        # (bench/pipeline.py) compiles on background threads.
        self.compile_count = 0
        self.compile_secs = 0.0
        self._stats_lock = threading.Lock()

    def _note_compile(self, secs: float) -> None:
        with self._stats_lock:
            self.compile_count += 1
            self.compile_secs += secs

    @contextmanager
    def _first_call(self, sched_json: str, run_n: Optional[int],
                    repeat_n: bool = True) -> Iterator[Callable]:
        """Round the first call of a newly built program — where jax traces
        and lowers, XLA compiles and loads, and (unless ahead of time:
        ``run_n`` None, span attr ``aot``) the program runs ``run_n``
        samples for the first time.  Always timed into the
        ``compile_count``/``compile_secs`` tallies (the driver's ``perf``
        provenance: seconds of first calls, not of XLA alone); when the
        tracer records, one ``executor.first_call`` span with the parts of
        :class:`_FirstCallParts` as children.  ``schedule`` hashes the
        UNPREFIXED schedule JSON, so it matches the ``bench.benchmark``
        span's id for the same schedule.

        Yields ``note_sizes(compiled_of)``: from the ``Compiled`` the call
        ended with (``compiled_of()``), it sets the span's ``temp_bytes``, ``argument_bytes``,
        ``output_bytes``, ``alias_bytes`` and ``generated_code_bytes``
        (``memory_analysis()``: one device's) and, for a repeat-n program
        (``repeat_n``), raises the gauge ``executor.program_temp_bytes_max``
        to its temporaries: the memory the runtime reserves for a program
        beside its buffers, which no buffer count holds."""
        attrs = {"aot": True} if run_n is None else {}
        t0 = time.perf_counter()
        with get_tracer().span("executor.first_call",
                               schedule=short_digest(sched_json),
                               **attrs) as span:

            def note_sizes(compiled_of: Callable) -> None:
                try:
                    mem = compiled_of().memory_analysis()
                except Exception as e:  # telemetry never fails a first call
                    span.set("sizes_error", f"{type(e).__name__}: {e}"[:200])
                    return
                if mem is None:  # a backend without the analysis
                    return
                for attr in _PROGRAM_SIZES:
                    span.set(attr + "_bytes",
                             int(getattr(mem, attr + "_size_in_bytes")))
                if repeat_n:
                    self._note_temp_bytes(int(mem.temp_size_in_bytes))

            parts = _first_call_open.parts = _FirstCallParts(run_n)
            try:
                yield note_sizes
            finally:
                _first_call_open.parts = None
                parts.close()
        self._note_compile(time.perf_counter() - t0)

    def _note_temp_bytes(self, temp_bytes: int) -> None:
        gauge = get_metrics().gauge("executor.program_temp_bytes_max")
        with self._stats_lock:
            if temp_bytes > gauge.value:
                gauge.set(temp_bytes)

    @staticmethod
    def place_host_buffers(bufs: Dict[str, Any], host_names) -> Dict[str, Any]:
        """jnp arrays for ``bufs`` with ``host_names`` device_put into
        pinned_host — the placement `_initial_host_space` detects (single
        shared helper for every workload's host-staged buffers)."""
        import jax
        import jax.numpy as jnp

        host_sh = jax.sharding.SingleDeviceSharding(
            jax.devices()[0], memory_kind="pinned_host"
        )
        host_names = set(host_names)
        return {
            k: jax.device_put(jnp.asarray(v), host_sh)
            if k in host_names
            else jnp.asarray(v)
            for k, v in bufs.items()
        }

    # -- build -------------------------------------------------------------
    def _initial_host_space(self) -> set:
        """Buffer names whose initial arrays live in host memory."""
        names = set()
        for k, v in self.init_bufs.items():
            mk = getattr(getattr(v, "sharding", None), "memory_kind", None)
            if mk is not None and "host" in str(mk):
                names.add(k)
        return names

    def _host_space_after(self, ops: List[OpBase]) -> set:
        """Host-space buffer names once the schedule has traced (transfer ops
        move names between spaces deterministically via DST_SPACE)."""
        names = self._initial_host_space()
        for op in ops:
            evolve_host_space(names, op)
        return names

    def _traced(self, ops: List[OpBase], bufs: Dict[str, Any]) -> Dict[str, Any]:
        tc = TraceContext(
            dict(bufs),
            axis_names=self.platform.axis_names,
            host_space=self._initial_host_space(),
        )
        for op in ops:
            tc.trace_op(op)
        _check_inflight_drained(tc)
        return tc.bufs

    @staticmethod
    def _token_template(ops: List[OpBase]) -> Dict[str, Any]:
        """Zero-token state covering every lane/event the schedule can touch —
        a stable carry structure for the benchmark loop."""
        zero = jnp.zeros((), jnp.float32)
        lanes: Dict[int, Any] = {}
        events: Dict[int, Any] = {}
        for op in ops:
            for l in getattr(op, "lanes", lambda: [])():
                lanes[l.id] = zero
            for e in getattr(op, "events", lambda: [])():
                events[e.id] = zero
        return {"host": zero, "lanes": lanes, "events": events}

    def _has_pallas(self, ops: List[OpBase]) -> bool:
        return any(getattr(op, "uses_pallas", lambda: False)() for op in ops)

    def _build(self, order: Sequence) -> Callable[[Dict[str, Any]], Dict[str, Any]]:
        """The (unjitted) program for a schedule: trace, then shard_map over the
        platform mesh when present."""
        ops = order.vector()

        def fn(bufs: Dict[str, Any]) -> Dict[str, Any]:
            return self._traced(ops, bufs)

        mesh = self.platform.mesh
        if mesh is not None:
            specs = {name: self.platform.spec(name) for name in self.init_bufs}
            # check_vma=False only when a Pallas kernel is in the schedule: the
            # Pallas interpreter's internal slicing fails jax's varying-axes
            # check under shard_map (upstream limitation).  Plain-XLA schedules
            # keep the safety check on (ADVICE r1).
            kw = {"check_vma": False} if self._has_pallas(ops) else {}
            fn = jax.shard_map(
                fn, mesh=mesh, in_specs=(specs,), out_specs=specs, **kw
            )
        return fn

    def program(self, order: Sequence) -> Callable[[Dict[str, Any]], Dict[str, Any]]:
        """The (unjitted) traced program for a schedule — the public surface
        for compile checks and external jitting (the driver's ``entry()``)."""
        return self._build(order)

    def compile(self, order: Sequence) -> Callable[[Dict[str, Any]], Dict[str, Any]]:
        """One jitted program per schedule, cached by schedule JSON.

        The FIRST invocation of the returned callable — where jax.jit
        actually traces and XLA-compiles — goes through
        :meth:`_first_call`; steady-state calls pay one branch.

        ``one_shot_as_loop`` (the constructor's): the program is the
        repeat-n loop of :meth:`prepare_n` run once (:meth:`_looped_fn`, the
        repeat count an argument), handing back the carry.  XLA fuses a
        straight-line program otherwise than a loop's body (a division
        merged with the update that stores it, a convert in a fusion of its
        own), so where bfloat16 stations follow one another through a dozen
        products the two round a value in a million apart, which is nothing
        to a reference and everything to a comparison bit for bit
        (``longcat-lite-scmoe-decode``, PERF.md PR 46); the loop run once
        shares the timed program's body."""
        key = sequence_to_json_str(order)
        if key in self._cache:
            return self._cache[key]
        with get_tracer().span("executor.build", schedule=short_digest(key),
                               n_ops=len(order.vector())):
            if self._one_shot_as_loop:
                jitted = jax.jit(self._looped_fn(order.vector()))
                once = (jnp.int32(1),)  # the loop's repeat count
            else:
                jitted = jax.jit(self._build(order))
                once = ()
        state = {"cold": True}

        def wrapped(bufs: Dict[str, Any]) -> Dict[str, Any]:
            if state["cold"]:
                state["cold"] = False
                with self._first_call(key, run_n=1,
                                      repeat_n=False) as note_sizes:
                    # its first run: to the call's return
                    out = jitted(bufs, *once)
                    note_sizes(lambda: _compiled_of(jitted, bufs, *once))
                    return out
            return jitted(bufs, *once)

        self._cache[key] = wrapped
        return wrapped

    # -- run ---------------------------------------------------------------
    def run(self, order: Sequence) -> Dict[str, Any]:
        """Execute once and return the final buffers (numerical validation)."""
        return self.compile(order)(self.init_bufs)

    def prepare(self, order: Sequence) -> Callable[[], None]:
        """Fenced zero-arg runner for the benchmarker: dispatch + block."""
        f = self.compile(order)
        bufs = self.init_bufs

        def run_once() -> None:
            jax.block_until_ready(f(bufs))

        return run_once

    def prepare_n(self, order: Sequence) -> Callable[[int], None]:
        """Repeat-``n``-inside-one-program runner — the benchmark hot loop.

        The reference times ``for sample in 0..n: for op in order: op->run()``
        between two fences (benchmarker.cpp:83-119).  Here the sample loop is a
        ``fori_loop`` *inside* the compiled program carrying the buffer dict
        (ops re-run on their own outputs, exactly like the reference re-running
        ops on the same device buffers), and the fence is a ``device_get`` of
        one scalar reduced from every output buffer: a device->host fetch
        cannot return before execution finishes on any backend (one was
        measured whose ``block_until_ready`` did: timing flat in n); the
        full-reduction fence also makes every op's output live (no dead-code
        narrowing of the final ops) and costs one pass *after* the loop,
        amortized over all n samples."""
        ops = order.vector()
        sched_json = sequence_to_json_str(order)
        key = "n:" + sched_json
        newly_built = key not in self._cache
        if not newly_built:
            f = self._cache[key]
        else:
            f = jax.jit(self._stepped_fn(ops))
            self._cache[key] = f
        bufs = self.init_bufs
        # the first invocation of a newly-built program is where jax traces
        # and XLA compiles (device_get blocks through both): a first call,
        # kept apart from steady-state measurement in tallies and spans.  A
        # program a prefetch worker compiled ahead has only its first run
        # left, which loads it onto the device: that too is kept apart.
        state = {"first": "call" if newly_built
                 else "run" if key in self._unrun else None}

        def run_n(n: int) -> None:
            first = state["first"]
            if first is not None:
                state["first"] = None
                n_dev = jnp.int32(n)  # its own tiny program, the first time
                if first == "call":
                    with self._first_call(sched_json, run_n=n) as note_sizes:
                        jax.device_get(f(bufs, n_dev)[0])
                        note_sizes(lambda: _compiled_of(f, bufs, n_dev))
                else:
                    self._unrun.discard(key)
                    with get_tracer().span("executor.first_run", n=n):
                        jax.device_get(f(bufs, n_dev)[0])
                return
            tr = get_tracer()
            with tr.span("executor.enqueue"):
                # only the fence is kept: the host-space outputs are dropped
                # while the program runs.  Held until the fence is fetched,
                # their release (an unmap) costs the flagship 43-51 ms a
                # dispatch after the wait (PERF.md, PR 25)
                fence = f(bufs, jnp.int32(n))[0]
            with tr.span("executor.fence_wait"):
                jax.device_get(fence)

        return run_n

    def _looped_fn(self, ops: List[OpBase]) -> Callable:
        """The repeat-n loop of :meth:`_stepped_fn` itself, ``(bufs, n) ->
        bufs``: the same ``fori_loop`` body under the same ``shard_map``,
        handing back the carry where the timed program reduces it to its
        fence."""
        return self._stepped_fn(ops, return_buffers=True)

    def _stepped_fn(self, ops: List[OpBase],
                    return_buffers: bool = False) -> Callable:
        """The (unjitted) repeat-n program ``stepped(bufs, n) -> (fence,
        host_outs)`` shared by :meth:`prepare_n` (lazy jit) and
        :meth:`precompile` (AOT): the fori_loop sample body carrying the
        buffer dict and token state, shard_mapped over the platform mesh
        when present, fenced by one reduced scalar."""
        axis_names = self.platform.axis_names
        tok0 = self._token_template(ops)
        host_space0 = self._initial_host_space()
        host_space_final = self._host_space_after(ops)

        def body(state):
            bufs, toks = state
            tc = TraceContext(
                dict(bufs), axis_names=axis_names, tokens=toks, host_space=host_space0
            )
            for op in ops:
                tc.trace_op(op)
            _check_inflight_drained(tc)
            return (tc.bufs, tc.token_state())

        mesh = self.platform.mesh

        def loop(bufs: Dict[str, Any], n) -> Dict[str, Any]:
            toks = tok0
            if mesh is not None:
                # comm ops make tokens shard-varying mid-loop; the carry
                # type must be varying from iteration 0
                toks = jax.tree_util.tree_map(
                    lambda t: lax.pcast(t, tuple(mesh.axis_names), to="varying"),
                    toks,
                )
            out, _ = lax.fori_loop(0, n, lambda i, s: body(s), (bufs, toks))
            return out

        if mesh is not None:
            # the whole sample loop runs inside one shard_map region: the
            # token carry is per-shard state (comm-op tokens vary across
            # mesh axes) and must not cross the shard_map boundary, where
            # it would need a replicated out_spec it cannot satisfy
            specs = {name: self.platform.spec(name) for name in self.init_bufs}
            from jax.sharding import PartitionSpec

            kw = {"check_vma": False} if self._has_pallas(ops) else {}
            loop = jax.shard_map(
                loop,
                mesh=mesh,
                in_specs=(specs, PartitionSpec()),
                out_specs=specs,
                **kw,
            )

        if return_buffers:
            return loop

        def stepped(bufs: Dict[str, Any], n) -> Any:
            out = loop(bufs, n)
            host_outs = {
                # host-space tensors admit no arithmetic; returning them
                # as program outputs keeps a trailing un-fetched spill
                # alive (only the fence scalar is device_get)
                name: val for name, val in out.items()
                if name in host_space_final}
            fence = _fence_of(
                val for name, val in out.items() if name not in host_outs)
            return fence, host_outs

        return stepped

    # -- ahead-of-time compilation (the prefetch pipeline's entry point) ----
    def is_compiled(self, order: Sequence) -> bool:
        """True when the benchmark (repeat-n) program for ``order`` is
        already in the program cache (compiled or mid-first-invocation)."""
        return ("n:" + sequence_to_json_str(order)) in self._cache

    def precompile(self, order: Sequence) -> bool:
        """AOT-compile the benchmark program for ``order`` off the hot path:
        ``jax.jit(stepped).lower(init_bufs, n).compile()`` against the same
        buffer/token template :meth:`prepare_n` traces, cached under the
        same ``"n:"``-prefixed schedule-JSON key — so the foreground
        ``prepare_n``/``run_n`` (the measurement path) hit instead of
        compiling inline.  ``compile()``/``run()`` key the un-prefixed
        single-shot program and are NOT warmed by this (the integrity
        gate's ``run()`` still compiles its own program).

        Returns True when this call actually compiled, False on a cache hit.
        Thread-safe by design: meant to run on the prefetch pipeline's
        background workers (bench/pipeline.py) while the main thread
        measures — tracing is pure, XLA compilation releases the GIL, and
        the cache insert is a GIL-atomic ``setdefault`` (a racing duplicate
        compile is wasted work, never wrong results).  Touches NO platform
        state (``provision_events`` is per-candidate foreground bookkeeping
        the trace never reads), so a speculative precompile cannot perturb
        the search."""
        sched_json = sequence_to_json_str(order)
        key = "n:" + sched_json
        if key in self._cache:
            return False
        stepped = self._stepped_fn(order.vector())
        one = jnp.int32(1)
        with self._first_call(sched_json, run_n=None) as note_sizes:
            compiled = jax.jit(stepped).lower(self.init_bufs, one).compile()
            note_sizes(lambda: compiled)
        # first writer wins: a foreground prepare_n racing this insert keeps
        # its own (equivalent) program; both callables answer identically
        if self._cache.setdefault(key, compiled) is compiled:
            self._unrun.add(key)  # compiled, never run (prepare_n)
        return True

    def compiled_n(self, order: Sequence):
        """The ``Compiled`` of ``order``'s repeat-n program, as
        :meth:`prepare_n` or :meth:`precompile` left it (a lazily jitted
        one must have run: :func:`_compiled_of`): its ``as_text()`` names
        every instruction's owner (obs/attrib/hlo.py)."""
        f = self._cache["n:" + sequence_to_json_str(order)]
        if hasattr(f, "as_text"):
            return f
        return _compiled_of(f, self.init_bufs, jnp.int32(1))

    # -- timed execution mode (the attribution profiler's entry point) ------
    def op_stepped(self, order: Sequence):
        """Per-op stepped sub-programs — the attribution profiler's timed
        execution mode (obs/attrib/timeline.py).  Returns ``[(positions,
        fn)]`` covering every schedule position in order:

        * a sync op gets ``fn=None`` (token bookkeeping has no device work
          to time; its happens-before role is reconstructed by the analysis
          layer from the full op list);
        * every other op gets its own jitted ``fn(bufs) -> (fence, bufs)``
          sub-program tracing JUST that op against the buffer state the
          previous steps produced, fenced by a sum over the op's written
          buffers (full reduction, so the op's outputs stay live — the
          fence read is part of the step's measured cost and is documented
          as the stepped-mode bias in docs/observability.md);
        * split-kernel transfer posts (``rdma_copy_start`` /
          ``rdma_shift_start``) are grouped with everything through their
          matching ``await_transfer`` / ``multi_await`` into ONE step: the
          posted wait closure (``TraceContext.inflight``) cannot cross a
          jit trace boundary, so post→await is the smallest timeable unit.

        Mesh platforms are rejected: per-op stepping would have to carry
        shard-varying token state across program boundaries; multi-chip
        attribution goes through the xplane path (obs/attrib/xplane.py).
        """
        if self.platform.mesh is not None:
            raise RuntimeError(
                "op_stepped: per-op stepped profiling is single-chip only "
                "(use obs/attrib/xplane.py jax.profiler capture on meshes)")
        ops = order.vector()
        steps = []
        cur: List[int] = []
        pending: set = set()
        for p, op in enumerate(ops):
            if getattr(op, "is_sync", lambda: False)():
                if cur:
                    cur.append(p)  # keep position; trace skips it
                else:
                    steps.append(((p,), None))
                continue
            cur.append(p)
            kind = getattr(op, "KIND", "")
            if kind in ("rdma_copy_start", "rdma_shift_start"):
                pending.update(op.writes())
            elif kind == "await_transfer":
                pending.discard(op.buf())
            elif kind == "multi_await":
                pending.difference_update(op.bufs())
            if not pending:
                steps.append((tuple(cur), self._op_step_fn(ops, tuple(cur))))
                cur = []
        if cur:  # un-awaited tail: still timeable as one group
            steps.append((tuple(cur), self._op_step_fn(ops, tuple(cur))))
        return steps

    def _op_step_fn(self, ops: List[OpBase], positions) -> Callable:
        """The jitted sub-program for one stepped group: trace the group's
        non-sync ops with a fresh TraceContext (steps run to completion
        before the next starts, so zero token seeds are exact) and fence on
        a full reduction of the group's written device-space buffers."""
        group = [ops[p] for p in positions
                 if not getattr(ops[p], "is_sync", lambda: False)()]
        host_space0 = self._host_space_after(ops[: positions[0]])
        host_space_after = self._host_space_after(ops[: positions[-1] + 1])
        axis_names = self.platform.axis_names
        written = [n for op in group
                   for n in (op.writes() if hasattr(op, "writes") else [])]
        fence_names = [n for n in dict.fromkeys(written)
                       if n not in host_space_after]

        def fn(bufs: Dict[str, Any]) -> Any:
            tc = TraceContext(dict(bufs), axis_names=axis_names,
                              host_space=set(host_space0))
            for op in group:
                tc.trace_op(op)
            _check_inflight_drained(tc)
            return _fence_of(tc.bufs[name] for name in fence_names), tc.bufs

        return jax.jit(fn)

    def lowered_text(self, order: Sequence) -> str:
        """Lowered (pre-optimization) HLO of a schedule (debugging / tests)."""
        return jax.jit(self._build(order)).lower(self.init_bufs).as_text()

    def compiled_text(self, order: Sequence) -> str:
        """Post-optimization HLO — what actually runs; the token data edges
        must still be visible here (the whole point of ``datatie``)."""
        return jax.jit(self._build(order)).lower(self.init_bufs).compile().as_text()
