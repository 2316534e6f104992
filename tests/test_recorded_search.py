"""Replay of a real recorded TPU search (VERDICT r1 item 6).

``experiments/halo_search_tpu.csv`` is the dumped result database of an MCTS
search over the single-chip halo pipeline (reference config nQ=3, 512^3 cells,
radius 3) run on a TPU v5e: row 0 is the naive sequential baseline, the
remaining rows are searched candidates over order x lane x kernel choice.
These tests drive CsvBenchmarker and postprocess with that real data — the
reference's offline-replay workflow (benchmarker.cpp:169-223,
postprocess.py:27-120) — instead of synthesized rows.
"""

import os

import pytest

from tenzing_tpu.bench.benchmarker import CsvBenchmarker
from tenzing_tpu.core.platform import Platform
from tenzing_tpu.models.halo import HaloArgs
from tenzing_tpu.models.halo_pipeline import build_graph, naive_order

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CSV_PATH = os.path.join(REPO, "experiments", "halo_search_tpu.csv")

# the configuration the search was recorded at (BASELINE.md halo config)
ARGS = HaloArgs(nq=3, lx=512, ly=512, lz=512, radius=3)


@pytest.fixture(scope="module")
def db():
    """The searched rows, anchored to the impl_choice graph (recorded ops carry
    .xla/.pallas choice names, which graph-anchored deserialization resolves by
    descending into the menus).  Row 0 — the naive baseline — was recorded from
    the pre-choice graph and is skipped here; ``db_naive`` covers it."""
    g = build_graph(ARGS, impl_choice=True)
    return CsvBenchmarker.from_file(CSV_PATH, g, strict=False)


@pytest.fixture(scope="module")
def db_naive():
    g = build_graph(ARGS, impl_choice=False)
    return CsvBenchmarker.from_file(CSV_PATH, g, strict=False)


def test_all_recorded_rows_deserialize(db, db_naive):
    # 13 recorded rows: 12 searched (choice graph) + 1 naive (plain graph)
    assert len(db.entries) == 12 and db.skipped == [0]
    assert len(db_naive.entries) == 1 and len(db_naive.skipped) == 12
    for seq, res in list(db.entries) + list(db_naive.entries):
        assert len(seq) >= 32  # 30 pipeline ops + start/finish (+ syncs)
        assert res.pct50 > 0


def test_recorded_rows_answer_their_own_queries(db):
    for seq, res in db.entries:
        assert db.benchmark(seq).pct50 == res.pct50


def _work_ops(seq):
    """``seq`` without its sync ops: the recorded naive rows predate the
    soundness verifier and list the work ops only; today's naive carries
    the EventRecord/EventSync pairs the verifier requires, around the same
    work ops in the same order."""
    from tenzing_tpu.core.sequence import Sequence
    from tenzing_tpu.core.sync_ops import SyncOp

    return Sequence([op for op in seq.vector() if not isinstance(op, SyncOp)])


def test_naive_order_matches_recorded_baseline_row(db_naive):
    """The work ops of the naive schedule as the framework builds it today
    must be bijection-equivalent to the recorded naive row — guards the
    serdes round-trip and the naive_order construction against drift."""
    plat = Platform.make_n_lanes(2)
    res = db_naive.benchmark(_work_ops(naive_order(ARGS, plat)))
    assert res.pct50 == db_naive.entries[0][1].pct50


def test_searched_beats_naive_outside_noise(db, db_naive):
    """The north-star signal (BASELINE.md), on real recorded data: the best
    searched schedule beats the naive baseline by more than one stddev of
    either measurement."""
    naive = db_naive.entries[0][1]
    best = min((r for _, r in db.entries), key=lambda r: r.pct50)
    assert best.pct50 < naive.pct50
    margin = naive.pct50 - best.pct50
    assert margin > max(best.stddev, naive.stddev), (
        f"margin {margin*1e3:.2f} ms not outside noise "
        f"(stddev {naive.stddev*1e3:.2f}/{best.stddev*1e3:.2f} ms)"
    )


def test_round2_recording_also_replays():
    """The round-2 full-budget recording (naive + greedy-overlap incumbent +
    24 MCTS iterations, same config; incumbent/naive rows carry the
    decorrelated final-batch measurements) loads and shows the same structure:
    best candidate under naive."""
    path = os.path.join(REPO, "experiments", "halo_search_tpu_r2.csv")
    n_rows = sum(1 for line in open(path) if line.strip())
    g = build_graph(ARGS, impl_choice=True)
    db2 = CsvBenchmarker.from_file(path, g, strict=False)
    # rows 0 (naive) and 1 (greedy incumbent) come from the pre-choice graph
    g_plain = build_graph(ARGS, impl_choice=False)
    db2_plain = CsvBenchmarker.from_file(path, g_plain, strict=False)
    assert len(db2.entries) == n_rows - 2 and db2.skipped == [0, 1]
    assert len(db2_plain.entries) == 2
    naive = db2_plain.entries[0][1]
    cands = [db2_plain.entries[1][1]] + [r for _, r in db2.entries]
    assert min(r.pct50 for r in cands) < naive.pct50

    # and the postprocess analyzer handles the full-budget recording too
    import io

    from postprocess.postprocess import analyze

    with open(path) as f:
        out = analyze(f.read(), stream=io.StringIO())
    assert out["n"] == n_rows


def test_round2c_recording_replays_with_decisive_margin():
    """The round-2 re-run under the tightened verdict protocol (3x final
    iterations, 20x measurement floor — bench.py): paired speedup 1.198,
    95% CI [1.189, 1.207].  The recording replays, and the recorded final
    -batch margin itself is decisive: best candidate under naive by more
    than both stddevs."""
    path = os.path.join(REPO, "experiments", "halo_search_tpu_r2c.csv")
    n_rows = sum(1 for line in open(path) if line.strip())
    g = build_graph(ARGS, impl_choice=True)
    db = CsvBenchmarker.from_file(path, g, strict=False)
    g_plain = build_graph(ARGS, impl_choice=False)
    db_plain = CsvBenchmarker.from_file(path, g_plain, strict=False)
    assert len(db.entries) == n_rows - 2 and db.skipped == [0, 1]
    assert len(db_plain.entries) == 2
    naive = db_plain.entries[0][1]
    best = min(
        [db_plain.entries[1][1]] + [r for _, r in db.entries],
        key=lambda r: r.pct50,
    )
    assert best.pct50 < naive.pct50
    assert naive.pct50 - best.pct50 > max(best.stddev, naive.stddev)


def test_moe_recording_replays_with_decisive_margin():
    """The MoE dispatch/combine pipeline search recorded on TPU v5e
    (bench.py --workload moe, 8192 tokens, 8 experts, 4 chunk chains):
    paired speedup 1.506, 95% CI [1.498, 1.517] — the searched software
    -pipelined schedule hides the host round-trip DMAs behind expert
    compute.  Rows 0/1 (naive, greedy incumbent) are from the plain graph,
    the rest from the kernel-choice graph."""
    from tenzing_tpu.models.moe_pipeline import (
        MoEPipeArgs,
        build_graph as moe_build,
        make_pipe_buffers,
        naive_order as moe_naive,
    )

    path = os.path.join(REPO, "experiments", "moe_search_tpu.csv")
    n_rows = sum(1 for line in open(path) if line.strip())
    margs = MoEPipeArgs()  # the bench config: 8192 tokens, 8 experts, 4 chunks
    _bufs, _want, cap = make_pipe_buffers(margs, seed=0, with_expected=False)
    db = CsvBenchmarker.from_file(path, moe_build(margs, cap, impl_choice=True),
                                  strict=False)
    db_plain = CsvBenchmarker.from_file(path, moe_build(margs, cap),
                                        strict=False)
    assert len(db.entries) == n_rows - 2 and db.skipped == [0, 1]
    assert len(db_plain.entries) == 2
    naive = db_plain.entries[0][1]
    best = min(
        [db_plain.entries[1][1]] + [r for _, r in db.entries],
        key=lambda r: r.pct50,
    )
    # stddev is dominated by the host-hiccup outlier tail (recorded naive:
    # pct99 22 ms vs pct50 6.6 ms), so the robust margin criterion is
    # percentile-based: the best schedule's *median* beats even naive's 1st
    # percentile, and the margin exceeds naive's pct10-pct90 spread
    assert best.pct50 < naive.pct01
    assert naive.pct50 - best.pct50 > naive.pct90 - naive.pct10
    # today's naive construction keeps the recorded row's work ops and order
    res = db_plain.benchmark(
        _work_ops(moe_naive(margs, cap, Platform.make_n_lanes(1))))
    assert res.pct50 == naive.pct50


def test_attn_bf16_recording_replays_with_decisive_margin():
    """The blocked-attention search recorded on TPU v5e with the 3-way kernel
    menu (XLA / Pallas f32 / Pallas bf16 — bench.py --workload attn, 8k
    context): paired speedup 4.329, 95% CI [4.284, 4.347].  Every row —
    naive, the all-bf16 incumbent, and the MCTS candidates — anchors to the
    kernel-choice graph."""
    from tenzing_tpu.core.graph import Graph
    from tenzing_tpu.models.ring_attention import BlockedAttention, RingAttnArgs

    path = os.path.join(REPO, "experiments", "attn_search_tpu_bf16.csv")
    n_rows = sum(1 for line in open(path) if line.strip())
    aargs = RingAttnArgs(n_devices=8, batch=4, seq_local=1024, head_dim=128)
    g = Graph()
    g.start_then(BlockedAttention(aargs, impl_choice=True))
    g.then_finish(BlockedAttention(aargs, impl_choice=True))
    db = CsvBenchmarker.from_file(path, g, strict=True)
    assert len(db.entries) == n_rows
    naive = db.entries[0][1]
    best_seq, best = min(db.entries, key=lambda e: e[1].pct50)
    assert best.pct50 < naive.pct01  # decisive under percentile criterion
    # the winning schedule uses the bf16 kernel on every block
    n_bf16 = sum(1 for op in best_seq if op.name().endswith(".pallas_bf16"))
    assert n_bf16 == 8


def test_postprocess_on_real_recorded_data():
    """Class-boundary + decision-tree analysis runs on the real CSV and finds
    the searched-fast vs naive-slow structure."""
    from postprocess.postprocess import analyze, load_rows

    with open(CSV_PATH) as f:
        text = f.read()
    import io

    rows = load_rows(text)
    assert len(rows) == 13
    out = analyze(text, stream=io.StringIO())
    assert out["n"] == 13
    assert len(out["classes"]) == 13
    assert max(out["classes"]) >= 0


# -- round-3 recorded databases (transfer-engine menu in the space) ----------

R3C_PATH = os.path.join(REPO, "experiments", "halo_search_tpu_r3c.csv")
ATTN_R3_PATH = os.path.join(REPO, "experiments", "attn_search_tpu_r3.csv")


@pytest.fixture(scope="module")
def db_r3c():
    """The 1.337x flagship database: rows mix host-staged, RDMA and
    mixed-engine schedules over the full kernel x engine choice graph."""
    g = build_graph(ARGS, impl_choice=True, xfer_choice=True)
    return CsvBenchmarker.from_file(R3C_PATH, g, strict=False)


def test_r3_flagship_rows_deserialize_and_answer(db_r3c):
    # the searched rows anchor against the menus (incl. RdmaCopyStart inside
    # TransferChoice and spill/fetch inside the HostRoundTrip compound); the
    # naive row was recorded from the engine-free graph and may be skipped
    assert len(db_r3c.entries) >= 90
    engines = set()
    for seq, res in db_r3c.entries:
        assert res.pct50 > 0
        names = [op.desc() for op in seq.vector()]
        engines.add("rdma" if any(".rdma" in n for n in names) else "host")
        assert db_r3c.benchmark(seq).pct50 == res.pct50
    assert engines == {"rdma", "host"}  # both engines present in the record


def test_r3_attn_rows_deserialize_and_answer():
    import jax.numpy as jnp  # noqa: F401

    from tenzing_tpu.core.graph import Graph
    from tenzing_tpu.models.ring_attention import BlockedAttention, RingAttnArgs

    aargs = RingAttnArgs(n_devices=8, batch=4, seq_local=1024, head_dim=128)
    g = Graph()
    g.start_then(BlockedAttention(aargs, impl_choice=True))
    g.then_finish(BlockedAttention(aargs, impl_choice=True))
    db = CsvBenchmarker.from_file(ATTN_R3_PATH, g, strict=False)
    assert len(db.entries) >= 90
    for seq, res in list(db.entries)[:10]:
        assert db.benchmark(seq).pct50 == res.pct50
