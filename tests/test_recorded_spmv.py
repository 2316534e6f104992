"""Replay of a real recorded TPU DFS search over the SpMV iteration space
(VERDICT r1 item 6: DFS on the chip, recorded CSV as a fixture).

``experiments/spmv_dfs_tpu.csv`` is the dumped result database of
``examples/spmv_dfs.py`` run on a TPU v5e at the reference config (m=150000
rows, nnz=10m band matrix, 2 lanes — spmv_run_strategy.cuh:44-47) with a
capped exhaustive enumeration (reference maxSeqs cap, spmv.cu:117).  Every row
is one deduplicated complete schedule of the expanded SpMV compound.
"""

import os

import pytest

from tenzing_tpu.bench.benchmarker import CsvBenchmarker
from tenzing_tpu.core.graph import Graph
from tenzing_tpu.models.spmv import SpMVCompound

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CSV_PATH = os.path.join(REPO, "experiments", "spmv_dfs_tpu.csv")


@pytest.fixture(scope="module")
def db():
    g = Graph()
    g.start_then(SpMVCompound())
    g.then_finish(SpMVCompound())
    return CsvBenchmarker.from_file(CSV_PATH, g, strict=True)


def test_every_dfs_row_deserializes_and_answers(db):
    n_rows = sum(1 for line in open(CSV_PATH) if line.strip())
    assert len(db.entries) == n_rows and not db.skipped
    for seq, res in db.entries:
        # expanded compound: 5 pipeline ops + start/finish (+ inserted syncs)
        assert len(seq) >= 7
        assert res.pct50 > 0
        assert db.benchmark(seq).pct50 == res.pct50


def test_schedule_classes_exist_in_recorded_dfs(db):
    """The recorded space separates into performance classes (the signal
    postprocess mines; reference postprocess.py:27-120).  The recording chip's timing
    distribution is bimodal within a row, so the robust statistic is pct10 —
    the same choice the reference's ``best()`` makes (dfs.hpp Result): the
    pct10 spread across schedules must be a real fraction of the median."""
    p10 = sorted(r.pct10 for _, r in db.entries)
    spread = p10[-1] - p10[0]
    assert spread > 0.10 * p10[len(p10) // 2], (
        f"pct10 spread {spread*1e3:.2f} ms too small vs median {p10[len(p10)//2]*1e3:.2f} ms"
    )


def test_recorded_dfs_schedules_are_lane_overlapped_and_distinct(db):
    """Every deduplicated schedule in the capped enumeration binds both lanes
    (the all-one-lane serializations live past the cap), and no two recorded
    rows are bijection-equivalent — the DFS dedup held on real data."""
    from tenzing_tpu.core.operation import BoundDeviceOp
    from tenzing_tpu.core.sequence import get_equivalence

    seqs = [s for s, _ in db.entries]
    for s in seqs:
        assert {op.lane().id for op in s if isinstance(op, BoundDeviceOp)} == {0, 1}
    for i in range(len(seqs)):
        for j in range(i + 1, len(seqs)):
            assert not get_equivalence(seqs[i], seqs[j]), (i, j)


def test_postprocess_analyzes_recorded_dfs():
    import io

    from postprocess.postprocess import analyze

    with open(CSV_PATH) as f:
        text = f.read()
    out = analyze(text, stream=io.StringIO())
    assert out["n"] == sum(1 for line in text.splitlines() if line.strip())
