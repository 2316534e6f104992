"""SpMV workload: data structures, split, compound graph, end-to-end numerics
(reference test/test_expand_spmv.cu:16-51 and the C12 data layer)."""

import numpy as np
import pytest

from tenzing_tpu.core.graph import Graph
from tenzing_tpu.core.platform import Platform
from tenzing_tpu.core.state import State
from tenzing_tpu.models.spmv import (
    CooMat,
    CsrMat,
    SpMVCompound,
    make_spmv_buffers,
    part_by_rows,
    get_owner,
    random_band_matrix,
    random_matrix,
    split_local_remote,
)
from tenzing_tpu.runtime.executor import TraceExecutor
from tenzing_tpu.solve.dfs import get_all_sequences


def test_coo_to_csr_roundtrip():
    coo = CooMat(
        3,
        3,
        np.array([2, 0, 0]),
        np.array([1, 0, 2]),
        np.array([5.0, 1.0, 2.0], dtype=np.float32),
    )
    csr = coo.to_csr()
    dense = csr.toarray()
    want = np.zeros((3, 3), dtype=np.float32)
    want[2, 1], want[0, 0], want[0, 2] = 5.0, 1.0, 2.0
    np.testing.assert_array_equal(dense, want)


def test_band_matrix_stays_in_band():
    m, bw = 100, 5
    a = random_band_matrix(m, bw, 500, seed=1)
    for i in range(m):
        for j in range(a.indptr[i], a.indptr[i + 1]):
            assert abs(int(a.cols[j]) - i) <= bw


def test_slab_spmv_matches_dense():
    a = random_matrix(50, 40, 300, seed=2)
    vals, cols = a.to_slab()
    x = np.random.default_rng(0).random(40, dtype=np.float32)
    y = np.sum(vals * x[cols], axis=1)
    np.testing.assert_allclose(y, a.toarray() @ x, rtol=1e-5)


def test_slab_width_truncation_rejected():
    a = random_matrix(50, 40, 300, seed=2)
    with pytest.raises(ValueError, match="truncate"):
        a.to_slab(width=1)


def test_retain_rows():
    a = random_matrix(20, 20, 100, seed=3)
    sub = a.retain_rows(5, 12)
    np.testing.assert_allclose(sub.toarray(), a.toarray()[5:12], rtol=1e-6)


def test_partition():
    assert part_by_rows(10, 3) == [(0, 4), (4, 7), (7, 10)]
    assert get_owner(10, 3, 0) == 0
    assert get_owner(10, 3, 5) == 1
    assert get_owner(10, 3, 9) == 2


def test_split_local_remote_reassembles():
    a = random_matrix(30, 30, 200, seed=4)
    sp = split_local_remote(a, 0, 15)
    x = np.random.default_rng(1).random(30, dtype=np.float32)
    y_loc = sp.local.toarray() @ x[:15]
    y_rem = sp.remote.toarray() @ x[sp.remote_cols]
    np.testing.assert_allclose(y_loc + y_rem, a.toarray() @ x, rtol=1e-4)
    # remote columns are all outside the local range
    assert all(c >= 15 for c in sp.remote_cols)


def test_spmv_compound_expansion():
    # reference test_expand_spmv.cu: ExpandOp yields the compound's interior
    g = Graph()
    comp = SpMVCompound()
    g.start_then(comp)
    g.then_finish(comp)
    plat = Platform.make_n_lanes(2)
    s = State(g)
    ds = s.get_decisions(plat)
    assert len(ds) == 1 and "Expand" in ds[0].desc()
    s2 = s.apply(ds[0])
    names = {op.name() for op in s2.graph.vertices()}
    assert {"spmv_local", "scatter", "exchange", "spmv_remote", "y_add"} <= names


def test_spmv_end_to_end_all_schedules_correct():
    bufs, want = make_spmv_buffers(m=256, nnz_per_row=4, seed=0)
    g = Graph()
    g.start_then(SpMVCompound())
    g.then_finish(SpMVCompound())
    plat = Platform.make_n_lanes(2)
    ex = TraceExecutor(plat, bufs)
    states = get_all_sequences(g, plat, max_seqs=8)
    assert states
    for st in states:
        out = ex.run(st.sequence)
        np.testing.assert_allclose(np.asarray(out["y"]), want, rtol=2e-3)


def test_read_matrix_market(tmp_path):
    """MatrixMarket loader parity (reference mm reader, spmv.cu:23,35-37):
    general/symmetric/pattern variants against hand-built dense answers."""
    from tenzing_tpu.models.spmv import read_matrix_market

    gen = tmp_path / "gen.mtx"
    gen.write_text(
        "%%MatrixMarket matrix coordinate real general\n"
        "% a comment\n"
        "3 4 4\n"
        "1 1 2.5\n"
        "2 3 -1.0\n"
        "3 4 4.0\n"
        "1 2 0.5\n"
    )
    a = read_matrix_market(str(gen))
    want = np.zeros((3, 4), dtype=np.float32)
    want[0, 0], want[1, 2], want[2, 3], want[0, 1] = 2.5, -1.0, 4.0, 0.5
    np.testing.assert_array_equal(a.toarray(), want)

    sym = tmp_path / "sym.mtx"
    sym.write_text(
        "%%MatrixMarket matrix coordinate real symmetric\n"
        "3 3 3\n"
        "1 1 1.0\n"
        "3 1 2.0\n"
        "3 2 3.0\n"
    )
    s = read_matrix_market(str(sym))
    wants = np.array([[1, 0, 2], [0, 0, 3], [2, 3, 0]], dtype=np.float32)
    np.testing.assert_array_equal(s.toarray(), wants)

    pat = tmp_path / "pat.mtx"
    pat.write_text(
        "%%MatrixMarket matrix coordinate pattern general\n"
        "2 2 2\n"
        "1 2\n"
        "2 1\n"
    )
    p = read_matrix_market(str(pat))
    np.testing.assert_array_equal(
        p.toarray(), np.array([[0, 1], [1, 0]], dtype=np.float32)
    )

    with pytest.raises(ValueError):
        bad = tmp_path / "bad.mtx"
        bad.write_text("%%MatrixMarket matrix array real general\n2 2\n1\n2\n3\n4\n")
        read_matrix_market(str(bad))


def test_spmv_workload_from_mtx(tmp_path):
    """A loaded .mtx drives the full workload path (make_spmv_buffers(matrix=...))
    and every enumerated schedule computes the right y."""
    from tenzing_tpu.models.spmv import read_matrix_market

    rng = np.random.default_rng(3)
    m, nnz = 64, 400
    rows = rng.integers(0, m, nnz) + 1
    cols = rng.integers(0, m, nnz) + 1
    vals = rng.random(nnz)
    path = tmp_path / "rand.mtx"
    path.write_text(
        f"%%MatrixMarket matrix coordinate real general\n{m} {m} {nnz}\n"
        + "".join(f"{r} {c} {v:.6f}\n" for r, c, v in zip(rows, cols, vals))
    )
    mat = read_matrix_market(str(path))
    bufs, want = make_spmv_buffers(matrix=mat)
    g = Graph()
    g.start_then(SpMVCompound())
    g.then_finish(SpMVCompound())
    plat = Platform.make_n_lanes(2)
    ex = TraceExecutor(plat, bufs)
    st = get_all_sequences(g, plat, max_seqs=1)[0]
    out = ex.run(st.sequence)
    np.testing.assert_allclose(np.asarray(out["y"]), want, rtol=2e-3)


def test_read_matrix_market_truncated_raises(tmp_path):
    from tenzing_tpu.models.spmv import read_matrix_market

    t1 = tmp_path / "t1.mtx"
    t1.write_text("%%MatrixMarket matrix coordinate real general\n% only a comment\n")
    with pytest.raises(ValueError, match="truncated"):
        read_matrix_market(str(t1))
    t2 = tmp_path / "t2.mtx"
    t2.write_text("%%MatrixMarket matrix coordinate real general\n3 3 4\n1 1 1.0\n")
    with pytest.raises(ValueError, match="promised"):
        read_matrix_market(str(t2))


@pytest.mark.needs_pinned_host
def test_spmv_host_exchange_schedules_correct():
    """exchange="host": the x exchange is a posted async host round-trip with
    the post/wait split (the reference's PostSend/WaitRecv analog,
    ops_spmv.cuh:217-304); the post and await are distinct schedulable
    vertices, overlap orderings exist in the enumerated space, and a sample of
    schedules stays numerically right."""
    from tenzing_tpu.models.spmv import spmv_host_buffer_names

    bufs, want = make_spmv_buffers(m=128, nnz_per_row=4, seed=1)
    jbufs = TraceExecutor.place_host_buffers(bufs, spmv_host_buffer_names())
    g = Graph()
    g.start_then(SpMVCompound(exchange="host"))
    g.then_finish(SpMVCompound(exchange="host"))
    plat = Platform.make_n_lanes(2)
    states = get_all_sequences(g, plat, max_seqs=500)
    names = {op.name() for op in states[0].sequence}
    assert {"spill_x", "fetch_x", "await_x"} <= names
    ex = TraceExecutor(plat, jbufs)
    for st in states[:6]:
        out = ex.run(st.sequence)
        np.testing.assert_allclose(np.asarray(out["y"]), want, rtol=2e-3)
    # overlap orderings exist: some schedule computes spmv_local between the
    # fetch post and the await
    def overlapped(st):
        ns = [op.name() for op in st.sequence]
        return ("await_x" in ns and "spmv_local" in ns
                and ns.index("fetch_x") < ns.index("spmv_local") < ns.index("await_x"))

    assert any(overlapped(st) for st in states)


# -- the column sweep over the transposed slab (PR 26) ---------------------------

_OPS = ["SpMVOp", "SpMVPallasOp"]  # the latter in the Pallas interpreter


def _op(kind):
    from tenzing_tpu.models import spmv

    return getattr(spmv, kind)("k", "x", "y", "vals", "cols", "rows")


def _rows_of_width(m, width, seed):
    """An m x m matrix whose every row holds exactly ``width`` entries."""
    rng = np.random.default_rng(seed)
    rows = np.repeat(np.arange(m), width)
    cols = rng.integers(0, m, size=m * width)
    vals = rng.random(m * width, dtype=np.float32)
    return CooMat(m, m, rows, cols, vals).to_csr()


@pytest.mark.parametrize("kind", _OPS)
@pytest.mark.parametrize("width", [1, 11])
@pytest.mark.parametrize("m", [1, 8, 64, 513])
def test_spmv_op_matches_matvec(kind, m, width):
    """``SpMVOp`` (the column sweep) and ``SpMVPallasOp`` on the transposed
    ``(w, m)`` slab against the host's ``CsrMat.matvec``: one row, one
    sublane tile, sizes off every tile multiple, slab width 1 and > 8."""
    import jax.numpy as jnp

    a = _rows_of_width(m, width, seed=m + width)
    vals, cols = a.to_slab()
    assert vals.shape == (m, width)
    x = np.random.default_rng(7).random(m, dtype=np.float32)
    out = _op(kind).apply({"vals": jnp.asarray(vals.T),
                           "cols": jnp.asarray(cols.T),
                           "rows": jnp.arange(m, dtype=jnp.int32),
                           "x": jnp.asarray(x)}, None)
    assert out["y"].shape == (m,) and out["y"].dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out["y"]), a.matvec(x), rtol=1e-5)


def _empty_ends(tmp_path):
    """Rows 0-4 and 27-31 hold nothing, on both sides of the column split."""
    rng = np.random.default_rng(11)
    rows = rng.integers(5, 27, size=120)
    cols = rng.integers(0, 32, size=120)
    return CooMat(32, 32, rows, cols, rng.random(120, dtype=np.float32)).to_csr()


def _empty_remote(tmp_path):
    """Every entry in the local columns [0, m/2): the remote half is empty
    and ``send_idx`` degenerates to one index."""
    rng = np.random.default_rng(12)
    rows = rng.integers(0, 24, size=90)
    cols = rng.integers(0, 12, size=90)
    return CooMat(24, 24, rows, cols, rng.random(90, dtype=np.float32)).to_csr()


def _from_matrix_market(tmp_path):
    from tenzing_tpu.models.spmv import read_matrix_market

    path = tmp_path / "sym.mtx"
    path.write_text(
        "%%MatrixMarket matrix coordinate real symmetric\n6 6 7\n"
        "1 1 1.0\n3 1 2.0\n3 2 3.0\n5 4 -1.5\n6 1 0.25\n6 6 4.0\n4 4 2.0\n")
    return read_matrix_market(str(path))


def _band(tmp_path):
    return random_band_matrix(96, 12, 4 * 96, seed=3)


_MATRICES = {"empty_ends": _empty_ends, "empty_remote": _empty_remote,
             "matrix_market": _from_matrix_market, "band": _band}


@pytest.mark.parametrize("case", list(_MATRICES))
def test_spmv_buffers_are_transposed_slabs(case, tmp_path):
    """``make_spmv_buffers`` holds each slab ``(w, m)`` and C-contiguous:
    the matrix's rows along the lanes, slab row j every row's j-th entry;
    beside it the contiguous range of rows that hold an entry."""
    a = _MATRICES[case](tmp_path)
    bufs, _ = make_spmv_buffers(matrix=a)
    sp = split_local_remote(a, 0, a.m // 2)
    for half, part in (("loc", sp.local), ("rem", sp.remote)):
        vals, cols = bufs[f"A_{half}_vals"], bufs[f"A_{half}_cols"]
        want_vals, want_cols = part.to_slab()
        assert vals.shape == cols.shape == (want_vals.shape[1], a.m)
        assert vals.flags["C_CONTIGUOUS"] and cols.flags["C_CONTIGUOUS"]
        assert vals.dtype == np.float32 and cols.dtype == np.int32
        np.testing.assert_array_equal(vals, want_vals.T)
        np.testing.assert_array_equal(cols, want_cols.T)
        rows = bufs[f"A_{half}_rows"]
        held = np.flatnonzero(part.row_widths())
        assert rows.dtype == np.int32
        if len(held):
            np.testing.assert_array_equal(
                rows, np.arange(held[0], held[-1] + 1))
    if case == "empty_ends":
        assert bufs["A_loc_rows"][0] == 5 and bufs["A_rem_rows"][-1] == 26
    if case == "empty_remote":
        assert bufs["send_idx"].shape == (1,) and not sp.remote.nnz()
        np.testing.assert_array_equal(bufs["A_rem_rows"], [0])


@pytest.mark.parametrize("kind", _OPS)
@pytest.mark.parametrize("case", ["empty_ends", "empty_remote",
                                  "matrix_market"])
def test_spmv_halves_match_matvec(kind, case, tmp_path):
    """Both halves of the workload's product, on the buffers as
    ``make_spmv_buffers`` lays them out and over the row ranges it names,
    against ``CsrMat.matvec``."""
    import jax.numpy as jnp

    a = _MATRICES[case](tmp_path)
    bufs, want = make_spmv_buffers(matrix=a)
    x = jnp.asarray(bufs["x_local"])
    op = _op(kind)
    y_loc = op.apply({"vals": jnp.asarray(bufs["A_loc_vals"]),
                      "cols": jnp.asarray(bufs["A_loc_cols"]),
                      "rows": jnp.asarray(bufs["A_loc_rows"]), "x": x},
                     None)["y"]
    y_rem = op.apply({"vals": jnp.asarray(bufs["A_rem_vals"]),
                      "cols": jnp.asarray(bufs["A_rem_cols"]),
                      "rows": jnp.asarray(bufs["A_rem_rows"]),
                      "x": x[jnp.asarray(bufs["send_idx"])]}, None)["y"]
    assert y_loc.shape == y_rem.shape == (a.m,)
    np.testing.assert_allclose(np.asarray(y_loc + y_rem), want, rtol=1e-5,
                               atol=1e-7)
    if case == "empty_ends":
        assert not np.asarray(y_loc + y_rem)[[0, 4, 27, 31]].any()
