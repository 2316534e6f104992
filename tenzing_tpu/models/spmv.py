"""Distributed SpMV workload: y = A @ x, row-partitioned, local/remote split.

Parity target: reference ``include/tenzing/spmv/`` + ``src/spmv/`` (C12 in
SURVEY.md §2): CSR/COO host structures (csr_mat.hpp, coo_mat.hpp), random band
matrix generators (csr_mat.hpp:299-369), 1-D block partition helpers
(partition.hpp:11-75), local/remote column split + renumbering
(split_mat.hpp:22-136), the ``RowPartSpmv`` setup engine (row_part_spmv.cuh), the
device ops SpMVKernel/Scatter/VectorAdd (ops_spmv.cuh:61-215 — VectorAdd is
actually implemented here, fixing the reference's no-op defect,
src/spmv/ops_spmv.cu:44-46 / SURVEY.md §7.3), and the ``SpMV`` CompoundOp wiring
the whole dataflow (ops_spmv.cuh:306-436).

TPU-native design: the sparse kernel avoids cuSPARSE-style scalar gathers.  A CSR
matrix is lowered once, host-side, to a dense **band/ELL slab**: values padded to
a fixed row width ``w`` with a companion column-index slab.  The workload's
buffers hold the slab transposed, ``(w, m)`` and contiguous: the matrix's rows
lie along the lanes, slab row ``j`` holds every matrix row's ``j``-th entry.
The SpMV is a **column sweep**: a ``fori_loop`` over the ``w`` slab rows,
``acc += vals_t[j] * x[cols_t[j]]`` — every operand 1-D, no reshape, the loop
body compiled once whatever ``m`` is.  (The row-major form ``sum(vals *
x[cols], axis=1)`` computes the same, but its minor dimension, ``w`` = 23..26,
is no lane multiple: XLA flattens the slab for the gather and reshapes back,
two physical relayouts whose code emission took 2.4 s of host time at
m = 16 384 and 72 s at 150 000 — PERF.md, PR 26.)  The gather is where the time
goes (7 ns an index on a v5e), so each half is swept only over the contiguous
range of matrix rows that hold an entry of it (``A_*_rows``: the local half of
a band matrix has none below the band, the remote half none above); y outside
the range is 0.  The remote half runs against the renumbered remote columns
exactly like the reference's split SpMV.

The comm ops here are the single-device slice (device-local gather standing for
the ICI exchange); the multi-chip exchange ops live in models/spmv_dist.py.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

from tenzing_tpu.core.graph import Graph
from tenzing_tpu.core.operation import ChoiceOp, CompoundOp, DeviceOp, OpBase


# -- host-side matrix structures (reference coo_mat.hpp / csr_mat.hpp) -----------


@dataclass
class CooMat:
    """Coordinate-format host matrix (reference CooMat, coo_mat.hpp:12-76)."""

    m: int
    n: int
    rows: np.ndarray
    cols: np.ndarray
    vals: np.ndarray

    def nnz(self) -> int:
        return len(self.vals)

    def to_csr(self) -> "CsrMat":
        order = np.lexsort((self.cols, self.rows))
        rows, cols, vals = self.rows[order], self.cols[order], self.vals[order]
        indptr = np.zeros(self.m + 1, dtype=np.int32)
        np.add.at(indptr, rows + 1, 1)
        indptr = np.cumsum(indptr).astype(np.int32)
        return CsrMat(self.m, self.n, indptr, cols.astype(np.int32), vals)


@dataclass
class CsrMat:
    """CSR host matrix (reference CsrMat<host>, csr_mat.hpp:34-155)."""

    m: int
    n: int
    indptr: np.ndarray
    cols: np.ndarray
    vals: np.ndarray

    def nnz(self) -> int:
        return len(self.vals)

    def retain_rows(self, lo: int, hi: int) -> "CsrMat":
        """Row slice [lo, hi) (reference retain_rows, csr_mat.hpp:101-155)."""
        a, b = self.indptr[lo], self.indptr[hi]
        return CsrMat(
            hi - lo,
            self.n,
            (self.indptr[lo : hi + 1] - a).astype(np.int32),
            self.cols[a:b],
            self.vals[a:b],
        )

    def row_widths(self) -> np.ndarray:
        return np.diff(self.indptr)

    def to_slab(self, width: Optional[int] = None) -> Tuple[np.ndarray, np.ndarray]:
        """Lower to a dense (m, width) ELL slab: (vals, cols), zero-padded.
        Padded entries point at column 0 with value 0 so the gather stays in
        bounds and contributes nothing."""
        wmax = int(self.row_widths().max(initial=0))
        w = int(width) if width is not None else max(1, wmax)
        if w < wmax:
            raise ValueError(
                f"slab width {w} would truncate rows (widest row has {wmax} nonzeros)"
            )
        vals = np.zeros((self.m, w), dtype=self.vals.dtype)
        cols = np.zeros((self.m, w), dtype=np.int32)
        if self.nnz():
            rows = np.repeat(np.arange(self.m), self.row_widths())
            pos = np.arange(self.nnz()) - self.indptr[rows]
            vals[rows, pos] = self.vals
            cols[rows, pos] = self.cols
        return vals, cols

    def matvec(self, x: np.ndarray) -> np.ndarray:
        """Host-side reference y = A @ x (vectorized; no dense materialization)."""
        if not self.nnz():
            return np.zeros(self.m, dtype=self.vals.dtype)
        rows = np.repeat(np.arange(self.m), self.row_widths())
        prods = (self.vals.astype(np.float64)) * x.astype(np.float64)[self.cols]
        return np.bincount(rows, weights=prods, minlength=self.m).astype(self.vals.dtype)

    def toarray(self) -> np.ndarray:
        """Dense form — small matrices / tests only."""
        out = np.zeros((self.m, self.n), dtype=self.vals.dtype)
        for i in range(self.m):
            for j in range(self.indptr[i], self.indptr[i + 1]):
                out[i, self.cols[j]] += self.vals[j]
        return out


def random_band_matrix(
    m: int, bw: int, nnz: int, seed: int = 0, dtype=np.float32
) -> CsrMat:
    """Random square band matrix: nnz entries within ``bw`` of the diagonal
    (reference random_band_matrix, csr_mat.hpp:335-369)."""
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, m, size=nnz)
    offs = rng.integers(-bw, bw + 1, size=nnz)
    cols = np.clip(rows + offs, 0, m - 1)
    vals = rng.random(nnz, dtype=np.float64).astype(dtype)
    return CooMat(m, m, rows, cols, vals).to_csr()


def random_matrix(m: int, n: int, nnz: int, seed: int = 0, dtype=np.float32) -> CsrMat:
    """Uniform random sparse matrix (reference random_matrix, csr_mat.hpp:299-333)."""
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, m, size=nnz)
    cols = rng.integers(0, n, size=nnz)
    vals = rng.random(nnz, dtype=np.float64).astype(dtype)
    return CooMat(m, n, rows, cols, vals).to_csr()


def read_matrix_market(path: str, dtype=np.float32) -> CsrMat:
    """Load a MatrixMarket coordinate file (the reference reads .mtx inputs via
    the vendored ``mm`` reader, tenzing-dfs/examples/spmv.cu:23,35-37).

    Supports ``coordinate`` matrices with field real/integer/pattern and
    symmetry general/symmetric/skew-symmetric (off-diagonal entries mirrored,
    skew negated).  Indices in the file are 1-based per the format."""
    with open(path) as f:
        header = f.readline().split()
        if (
            len(header) < 5
            or header[0] != "%%MatrixMarket"
            or header[1].lower() != "matrix"
            or header[2].lower() != "coordinate"
        ):
            raise ValueError(f"{path}: not a MatrixMarket coordinate file: {header}")
        field, symmetry = header[3].lower(), header[4].lower()
        if field not in ("real", "integer", "pattern"):
            raise ValueError(f"{path}: unsupported field {field!r}")
        if symmetry not in ("general", "symmetric", "skew-symmetric"):
            raise ValueError(f"{path}: unsupported symmetry {symmetry!r}")
        line = f.readline()
        while line and (line.lstrip().startswith("%") or not line.strip()):
            line = f.readline()
        if not line:
            raise ValueError(f"{path}: truncated file (no size line)")
        m, n, nnz = (int(t) for t in line.split())
        rows = np.empty(nnz, dtype=np.int64)
        cols = np.empty(nnz, dtype=np.int64)
        vals = np.ones(nnz, dtype=dtype)
        k = 0
        for line in f:
            t = line.split()
            if not t or t[0].startswith("%"):
                continue
            rows[k], cols[k] = int(t[0]) - 1, int(t[1]) - 1
            if field != "pattern":
                vals[k] = float(t[2])
            k += 1
        if k != nnz:
            raise ValueError(f"{path}: header promised {nnz} entries, found {k}")
    if symmetry != "general":
        off = rows != cols
        sign = -1.0 if symmetry == "skew-symmetric" else 1.0
        rows, cols, vals = (
            np.concatenate([rows, cols[off]]),
            np.concatenate([cols, rows[off]]),
            np.concatenate([vals, (sign * vals[off]).astype(dtype)]),
        )
    return CooMat(m, n, rows, cols, vals).to_csr()


# -- partition helpers (reference partition.hpp:11-75) ---------------------------


def part_by_rows(m: int, parts: int) -> List[Tuple[int, int]]:
    """Contiguous 1-D row partition: ``parts`` (lo, hi) ranges."""
    base, rem = divmod(m, parts)
    out = []
    lo = 0
    for p in range(parts):
        hi = lo + base + (1 if p < rem else 0)
        out.append((lo, hi))
        lo = hi
    return out


def get_owner(m: int, parts: int, row: int) -> int:
    """Owning partition of a row (reference get_owner, partition.hpp:43-75)."""
    for p, (lo, hi) in enumerate(part_by_rows(m, parts)):
        if lo <= row < hi:
            return p
    raise IndexError(row)


# -- local/remote split (reference split_mat.hpp:22-136) -------------------------


@dataclass
class SplitMat:
    """A row-partition's matrix split by column ownership: ``local`` covers
    owned columns (renumbered to local x indices), ``remote`` covers off-part
    columns renumbered densely; ``remote_cols`` maps the dense remote index back
    to the global column."""

    local: CsrMat
    remote: CsrMat
    remote_cols: np.ndarray  # global column of each renumbered remote column


def split_local_remote(a: CsrMat, col_lo: int, col_hi: int) -> SplitMat:
    """Split by column range ownership, renumbering both halves
    (reference split_local_remote, split_mat.hpp:22-136)."""
    loc_rows, loc_cols, loc_vals = [], [], []
    rem_rows, rem_cols, rem_vals = [], [], []
    for i in range(a.m):
        for j in range(a.indptr[i], a.indptr[i + 1]):
            c = a.cols[j]
            if col_lo <= c < col_hi:
                loc_rows.append(i)
                loc_cols.append(c - col_lo)
                loc_vals.append(a.vals[j])
            else:
                rem_rows.append(i)
                rem_cols.append(c)
                rem_vals.append(a.vals[j])
    uniq = np.unique(np.asarray(rem_cols, dtype=np.int64)) if rem_cols else np.array([], dtype=np.int64)
    renum = {c: k for k, c in enumerate(uniq)}
    local = CooMat(
        a.m,
        col_hi - col_lo,
        np.asarray(loc_rows, dtype=np.int64),
        np.asarray(loc_cols, dtype=np.int64),
        np.asarray(loc_vals, dtype=a.vals.dtype),
    ).to_csr()
    remote = CooMat(
        a.m,
        max(1, len(uniq)),
        np.asarray(rem_rows, dtype=np.int64),
        np.asarray([renum[c] for c in rem_cols], dtype=np.int64),
        np.asarray(rem_vals, dtype=a.vals.dtype),
    ).to_csr()
    return SplitMat(local=local, remote=remote, remote_cols=uniq)


# -- device ops ------------------------------------------------------------------


class SpMVOp(DeviceOp):
    """ELL-slab SpMV over the transposed slab ``(w, m)``:
    ``y = sum_j vals_t[j] * x[cols_t[j]]``, swept one slab row a loop step
    (reference SpMVKernel, ops_spmv.cuh:61-163 — cuSPARSE there, a lane-dense
    gather + VPU multiply-add here).

    ``rows`` names an int32 buffer ``arange(lo, hi)``: the contiguous range of
    matrix rows that hold an entry (``arange(m)`` for a matrix with entries in
    every row).  Only that range is swept and y is 0 outside it.  The graph is
    built before the matrix is known, so the range travels in a buffer: its
    extent is the buffer's shape (static, as the loop's operands need it), its
    start the buffer's first value.  (A slab cut to the range on the host,
    with the start in a 1-element buffer, iterated 2% slower on the chip:
    4.271 against 4.188 ms at 16 384 rows, PERF.md, PR 26.)"""

    def __init__(self, name: str, x: str, y: str, vals: str, cols: str,
                 rows: str):
        super().__init__(name)
        self._x, self._y, self._vals, self._cols = x, y, vals, cols
        self._rows = rows

    def reads(self):
        return [self._x, self._vals, self._cols, self._rows]

    def writes(self):
        return [self._y]

    def _product(self, vals_t, cols_t, x):
        """``sum_j vals_t[j] * x[cols_t[j]]`` for a ``(w, r)`` slab."""
        import jax.numpy as jnp
        from jax import lax

        w, r = vals_t.shape

        def column(j, acc):
            return acc + vals_t[j] * x[cols_t[j]]

        return lax.fori_loop(0, w, column, jnp.zeros((r,), vals_t.dtype))

    def apply(self, bufs, ctx):
        import jax.numpy as jnp
        from jax import lax

        vals_t, cols_t, x = bufs[self._vals], bufs[self._cols], bufs[self._x]
        w, m = vals_t.shape
        rows = bufs[self._rows]
        lo, r = rows[0], rows.shape[0]
        held = self._product(lax.dynamic_slice(vals_t, (0, lo), (w, r)),
                             lax.dynamic_slice(cols_t, (0, lo), (w, r)), x)
        return {self._y: lax.dynamic_update_slice(
            jnp.zeros((m,), held.dtype), held, (lo,))}

    # megakernel fusion (runtime/fused.py): a pure buffer->buffer function,
    # so it fuses; the row range counts from the whole slab's first row, so
    # the op declares no tiling and its regions are single-tile kernels
    def fusible(self) -> bool:
        return True


class SpMVPallasOp(SpMVOp):
    """ELL-slab SpMV via the Pallas masked vreg-gather kernel
    (ops/spmv_pallas.py).  Falls back to the XLA gather (the parent op) when x
    is too large for the in-kernel gather decomposition (see ops/spmv_pallas.py
    hardware note) so the op is always valid; where both kernels apply, which
    is faster is the solver's ChoiceOp question."""

    def _product(self, vals_t, cols_t, x):
        from tenzing_tpu.ops.spmv_pallas import ell_spmv_pallas, supports

        if not supports(x.shape[0]):
            return super()._product(vals_t, cols_t, x)
        return ell_spmv_pallas(vals_t, cols_t, x)

    def uses_pallas(self) -> bool:
        return True


class SpMVImplChoice(ChoiceOp):
    """Implementation menu for one SpMV: XLA-gather vs Pallas vreg-gather
    (reference ChoiceOp, operation.hpp:90-93; the scheduler replaces it via a
    ChooseOp decision, state.cpp:61-65).

    When the x-vector length is known at graph construction (``x_size``), the
    Pallas choice is offered only if the kernel actually supports it — otherwise
    SpMVPallasOp would silently fall back to the XLA path and the menu would
    double the structural-variant space with duplicate candidates (ADVICE r1)."""

    def __init__(self, name: str, x: str, y: str, vals: str, cols: str,
                 rows: str, x_size: Optional[int] = None):
        super().__init__(name)
        self._args = (x, y, vals, cols, rows)
        self._x_size = x_size

    def choices(self) -> List[OpBase]:
        from tenzing_tpu.ops.spmv_pallas import supports

        out: List[OpBase] = [SpMVOp(self.name() + ".xla", *self._args)]
        if self._x_size is None or supports(self._x_size):
            out.append(SpMVPallasOp(self.name() + ".pallas", *self._args))
        return out


class Scatter(DeviceOp):
    """Gather owned x entries into a contiguous send buffer (reference Scatter,
    ops_spmv.cuh:194-215)."""

    def __init__(self, name: str, x: str, idx: str, out: str):
        super().__init__(name)
        self._x, self._idx, self._out = x, idx, out

    def reads(self):
        return [self._x, self._idx]

    def writes(self):
        return [self._out]

    def apply(self, bufs, ctx):
        return {self._out: bufs[self._x][bufs[self._idx]]}

    # fusion: each gathered entry depends only on its own index row
    def fusible(self) -> bool:
        return True

    def fuse_tiling(self):
        return {self._x: None, self._idx: 0, self._out: 0}


class VectorAdd(DeviceOp):
    """y = yl + yr (reference VectorAdd — a no-op there,
    src/spmv/ops_spmv.cu:44-46; implemented here per SURVEY.md §7.3)."""

    def __init__(self, name: str, a: str, b: str, out: str):
        super().__init__(name)
        self._a, self._b, self._out = a, b, out

    def reads(self):
        return [self._a, self._b]

    def writes(self):
        return [self._out]

    def apply(self, bufs, ctx):
        return {self._out: bufs[self._a] + bufs[self._b]}

    # fusion: elementwise
    def fusible(self) -> bool:
        return True

    def fuse_tiling(self):
        return {self._a: 0, self._b: 0, self._out: 0}


class LocalExchange(DeviceOp):
    """Single-device stand-in for the ICI exchange: moves the scattered send
    buffer into the remote-x buffer (the multi-chip version is a ppermute-based
    neighbor exchange, models/spmv_dist.py)."""

    def __init__(self, name: str, src: str, dst: str):
        super().__init__(name)
        self._src, self._dst = src, dst

    def reads(self):
        return [self._src]

    def writes(self):
        return [self._dst]

    def apply(self, bufs, ctx):
        return {self._dst: bufs[self._src]}

    # fusion: a device-local copy, trivially row-independent
    def fusible(self) -> bool:
        return True

    def fuse_tiling(self):
        return {self._src: 0, self._dst: 0}


# -- synthesized exchange (collectives/synth.py) ---------------------------------

#: The synth site name of the host x-exchange: the directive rides the
#: executed schedule as ``x_exchange.synth.pipe.c<K>``.
SPMV_SYNTH_BASE = "x_exchange"


def spmv_synth_counts(n_remote: Optional[int]) -> List[int]:
    """Structurally valid pipe chunk counts for an ``n_remote``-entry
    exchange payload: 2 and 4 where they fit (k=1 staged routing IS the
    fixed round trip — offering it would duplicate the fixed alternative).
    Unknown payload -> no counts, never guessed."""
    return [k for k in (2, 4) if 2 <= k <= int(n_remote or 0)]


def spmv_synth_plans(n_remote: Optional[int]):
    """The pipe-sketch instantiations of the host x-exchange — the single
    source of truth for BOTH the graph's step chains and the buffer
    builder's staging decls (same plan, same names, same shapes)."""
    from tenzing_tpu.collectives.synth import plan_host_pipe

    return [plan_host_pipe(SPMV_SYNTH_BASE, "send_buf", "x_remote",
                           int(n_remote), k)
            for k in spmv_synth_counts(n_remote)]


class SpMVCompound(CompoundOp):
    """The whole SpMV iteration as one compound op (reference SpMV CompoundOp,
    ops_spmv.cuh:306-436): start -> {local spmv, scatter -> exchange}; exchange
    -> remote spmv; {local, remote} -> add -> finish.

    With ``impl_choice=True`` the two SpMV kernels become implementation
    ChoiceOps (XLA gather vs Pallas vreg-gather) and the solver searches the
    kernel menu alongside order and lane assignment.

    ``exchange`` picks the single-chip stand-in for the reference's MPI x
    exchange (PostSend/WaitRecv ops, ops_spmv.cuh:217-304):

    * ``"local"`` (default) — a device-to-device copy.  All-compute DAG: on a
      TPU core, compute ops cannot overlap across lanes, so schedule order
      barely matters (measured: paired speedup CI straddles 1.0).
    * ``"host"`` — an async host round-trip DMA with the post/wait split
      (spill -> fetch -> await), the same substrate as the halo pipeline.
      This is the faithful analog of the reference's network hop: the search
      can hide the transfer behind the local SpMV, and the naive
      serialization pays it in full.

    ``synth=True`` (requires ``exchange="host"``) additionally decomposes
    the exchange through the synthesized-collectives subsystem
    (collectives/synth.py): the fixed round trip becomes one alternative of
    a :class:`~tenzing_tpu.collectives.synth.SynthCollectiveChoice` whose
    other alternatives pipeline the payload device->host->device in k
    chunks (the ``pipe`` sketch — pure movement, bit-identical), so the
    solvers search the chunk routing of the exchange itself.  The remote-x
    length must be known (``x_sizes["x_remote"]``) — an unknown payload is
    never synthesized, the ``pow2_counts`` never-guess discipline.
    ``synth_relax`` keeps analytically-losing instantiations searchable
    (tests / toy smoke shapes), the ``chunk_relax`` twin."""

    def __init__(self, name: str = "spmv", impl_choice: bool = False,
                 x_sizes: Optional[Dict[str, int]] = None,
                 exchange: str = "local", synth: bool = False,
                 synth_relax: bool = False):
        super().__init__(name)
        self._impl_choice = impl_choice
        # buffer-name -> x length, when known (prunes unsupported Pallas choices)
        self._x_sizes = dict(x_sizes) if x_sizes else {}
        if exchange not in ("local", "host"):
            raise ValueError(f"exchange must be 'local' or 'host', got {exchange!r}")
        if synth and exchange != "host":
            raise ValueError("synth=True needs the exchange='host' round trip "
                             "(the PCIE link is what the pipe sketch routes)")
        self._exchange = exchange
        self._synth = synth
        self._synth_relax = synth_relax

    def graph(self) -> Graph:
        g = Graph()
        if self._impl_choice:
            def mk(name, x, y, vals, cols, rows):
                return SpMVImplChoice(name, x, y, vals, cols, rows,
                                      x_size=self._x_sizes.get(x))
        else:
            mk = SpMVOp
        yl = mk("spmv_local", "x_local", "y_local", "A_loc_vals", "A_loc_cols",
                "A_loc_rows")
        scatter = Scatter("scatter", "x_local", "send_idx", "send_buf")
        yr = mk("spmv_remote", "x_remote", "y_remote", "A_rem_vals",
                "A_rem_cols", "A_rem_rows")
        add = VectorAdd("y_add", "y_local", "y_remote", "y")
        g.start_then(yl)
        g.start_then(scatter)
        if self._exchange == "host":
            from tenzing_tpu.ops.comm_ops import (
                AwaitTransfer,
                HostFetchStart,
                HostSpillStart,
            )

            spill = HostSpillStart("spill_x", "send_buf", "host_x")
            fetch = HostFetchStart("fetch_x", "host_x", "x_remote")
            await_ = AwaitTransfer("await_x", "x_remote")
            variants = []
            if self._synth:
                from tenzing_tpu.collectives.synth import (
                    FixedCollective,
                    SynthCollectiveChoice,
                    sketch_menu,
                )
                from tenzing_tpu.collectives.topology import host_topology

                n_rem = self._x_sizes.get("x_remote")
                variants, menu = sketch_menu(
                    spmv_synth_plans(n_rem), host_topology(),
                    # the fixed floor: the round trip's bytes in one
                    # optimistic post (spill+fetch move them twice)
                    fixed_bytes=2.0 * 4 * int(n_rem or 0),
                    relax=self._synth_relax, collective="exchange")
            if variants:
                choice = SynthCollectiveChoice(
                    SPMV_SYNTH_BASE,
                    FixedCollective(SPMV_SYNTH_BASE, [spill, fetch, await_]),
                    variants, menu)
                g.then(scatter, choice)
                g.then(choice, yr)
            else:
                g.then(scatter, spill)
                g.then(spill, fetch)
                g.then(fetch, await_)
                g.then(await_, yr)
        else:
            exch = LocalExchange("exchange", "send_buf", "x_remote")
            g.then(scatter, exch)
            g.then(exch, yr)
        g.then(yl, add)
        g.then(yr, add)
        g.then_finish(add)
        return g


def _held_rows(a: CsrMat) -> np.ndarray:
    """``arange(lo, hi)`` over the contiguous range of rows of ``a`` that hold
    an entry (``SpMVOp``'s ``rows``); row 0 alone for an empty matrix."""
    held = np.flatnonzero(a.row_widths())
    lo, hi = (held[0], held[-1] + 1) if len(held) else (0, 1)
    return np.arange(lo, hi, dtype=np.int32)


def make_spmv_buffers(
    m: int = 4096,
    nnz_per_row: int = 10,
    bw: Optional[int] = None,
    seed: int = 0,
    slab_width: Optional[int] = None,
    matrix: Optional[CsrMat] = None,
    synth: bool = False,
) -> Tuple[Dict[str, np.ndarray], np.ndarray]:
    """Build the buffer dict for the single-device SpMV slice and the dense
    reference answer.  The matrix is split at the column midpoint to mimic the
    distributed local/remote structure (reference spmv_run_strategy.cuh:44-47
    config: m rows, nnz=10*m, band bw).  Pass ``matrix`` (e.g. from
    ``read_matrix_market``) to benchmark a concrete input instead of the random
    band matrix, matching the reference's .mtx path (spmv.cu:35-37)."""
    if matrix is not None:
        if matrix.m != matrix.n:
            raise ValueError(f"SpMV slice needs a square matrix, got {matrix.m}x{matrix.n}")
        a, m = matrix, matrix.m
    else:
        bw = bw if bw is not None else max(1, m // 8)
        a = random_band_matrix(m, bw, nnz_per_row * m, seed=seed)
    half = m // 2
    sp = split_local_remote(a, 0, half)
    # the slabs as SpMVOp sweeps them: (w, m), the matrix's rows contiguous
    lv, lc = (np.ascontiguousarray(s.T) for s in sp.local.to_slab(slab_width))
    rv, rc = (np.ascontiguousarray(s.T) for s in sp.remote.to_slab(slab_width))
    rng = np.random.default_rng(seed + 1)
    x = rng.random(m, dtype=np.float32)
    # remote x entries come from the "other rank"'s region via scatter+exchange
    send_idx = sp.remote_cols.astype(np.int32)
    if len(send_idx) == 0:  # degenerate split: keep buffer shapes static
        send_idx = np.zeros(1, dtype=np.int32)
    bufs = {
        "x_local": x,  # this slice owns columns [0, half) but keeps full x for the gather
        "A_loc_vals": lv,
        "A_loc_cols": lc,
        "A_rem_vals": rv,
        "A_rem_cols": rc,
        "A_loc_rows": _held_rows(sp.local),
        "A_rem_rows": _held_rows(sp.remote),
        "send_idx": send_idx,
        "send_buf": np.zeros(len(send_idx), dtype=np.float32),
        # staging buffer for the exchange="host" round trip (place in
        # pinned_host, see spmv_host_buffer_names); unused by exchange="local"
        "host_x": np.zeros(len(send_idx), dtype=np.float32),
        "x_remote": np.zeros(len(send_idx), dtype=np.float32),
        "y_local": np.zeros(m, dtype=np.float32),
        "y_remote": np.zeros(m, dtype=np.float32),
        "y": np.zeros(m, dtype=np.float32),
    }
    if synth:
        # staging decls of the synthesized exchange (pipe sketch): the same
        # plans the graph builds from, so names/shapes cannot drift
        for plan in spmv_synth_plans(len(send_idx)):
            for d in plan.buffers:
                bufs[d.name] = np.zeros(d.shape, dtype=np.float32)
    want = a.matvec(x)
    return bufs, want


def spmv_host_buffer_names(n_remote: Optional[int] = None,
                           synth: bool = False) -> List[str]:
    """Buffers to device_put into pinned_host for ``exchange="host"`` (the
    executor detects host residency from the array's sharding memory kind).
    With ``synth=True`` the pipe sketch's per-chunk host staging pieces are
    included (``n_remote`` = the exchange payload length, i.e. the
    ``send_idx`` extent the buffers were built with)."""
    out = ["host_x"]
    if synth:
        for plan in spmv_synth_plans(n_remote):
            out += [d.name for d in plan.buffers if d.space == "host"]
    return out
