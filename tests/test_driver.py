"""Driver-extraction parity (ISSUE 7 satellite): the library driver and
the bench.py CLI shim can never drift.

The extraction's contract is *identical CLI behavior*: the argparse
surface and :class:`~tenzing_tpu.bench.driver.DriverRequest` are the
same request (field set AND defaults asserted equal), config errors map
onto ``argparse.error``, and the device-free builders
(``workload_shape`` / ``graph_for``) resolve exactly the shapes the
device builders do.
"""

import dataclasses

import pytest

from tenzing_tpu.bench import driver
from tenzing_tpu.bench.driver import (
    BUILDERS,
    DriverConfigError,
    DriverRequest,
    graph_for,
    search_lanes,
    workload_shape,
)


def test_request_matches_cli_surface():
    """Every argparse dest is a DriverRequest field with the same
    default — the one test that makes `bench.py` and the library API a
    single request type instead of two slowly-diverging ones."""
    import bench

    ns = vars(bench.build_arg_parser().parse_args([]))
    fields = {f.name: f.default for f in dataclasses.fields(DriverRequest)}
    assert set(ns) == set(fields), set(ns) ^ set(fields)
    assert ns == fields


def test_request_json_round_trip():
    req = DriverRequest(workload="spmv", m=640, seed_topk=5, resume=False)
    j = req.to_json()
    assert DriverRequest(**j) == req
    import json

    assert DriverRequest(**json.loads(json.dumps(j))) == req


def test_config_errors_raise_not_exit():
    with pytest.raises(DriverConfigError, match="--resume requires"):
        driver.run(DriverRequest(resume=True))
    with pytest.raises(DriverConfigError, match="unknown workload"):
        workload_shape(DriverRequest(workload="nope"))
    # run() validates BEFORE probing the backend: a drainer fed a
    # corrupt work item gets the API's error, not a KeyError (or a
    # backend-failure verdict mislabeled into the fall-through metric)
    with pytest.raises(DriverConfigError, match="unknown workload"):
        driver.run(DriverRequest(workload="hallo"))


def test_workload_shape_goldens():
    # the builder-resolved shapes, pinned: these are the serving
    # fingerprint's inputs (a silent change re-keys every store)
    assert workload_shape(DriverRequest(workload="halo")) == \
        {"nq": 3, "n": 512, "radius": 3}
    assert workload_shape(DriverRequest(workload="halo", smoke=True)) == \
        {"nq": 2, "n": 4, "radius": 1}
    # bw=None resolves to the builder's own default (max(1, m // 8),
    # models/spmv.py) — a default request and an explicit --spmv-bw of
    # the same value must share a fingerprint
    assert workload_shape(DriverRequest(workload="spmv")) == \
        {"m": 150_000, "nnz_per_row": 10, "bw": 18_750}
    assert workload_shape(DriverRequest(workload="spmv")) == \
        workload_shape(DriverRequest(workload="spmv", spmv_bw=18_750))
    assert workload_shape(DriverRequest(workload="spmv", m=640,
                                        spmv_bw=32)) == \
        {"m": 640, "nnz_per_row": 10, "bw": 32}
    assert workload_shape(DriverRequest(workload="attn")) == \
        {"n_devices": 8, "batch": 4, "seq_local": 1024, "head_dim": 128}
    assert workload_shape(DriverRequest(workload="moe", smoke=True)) == \
        {"n_experts": 4, "tokens": 32, "d_model": 8, "d_ff": 16,
         "n_chunks": 2}
    assert workload_shape(DriverRequest(workload="moe",
                                        moe_tokens=4096)) == \
        {"tokens": 4096}


def test_search_lanes_default_rule():
    assert search_lanes(DriverRequest(workload="halo")) == 8
    assert search_lanes(DriverRequest(workload="halo", smoke=True)) == 2
    assert search_lanes(DriverRequest(workload="spmv")) == 2
    assert search_lanes(DriverRequest(workload="halo", lanes=3)) == 3


def test_builders_cover_all_workloads():
    assert set(BUILDERS) == {"halo", "spmv", "attn", "mla_decode", "dsa_decode",
                             "kda_decode", "moe"}


def test_graph_for_is_device_free():
    """The serving builders never place buffers: graphs + nbytes come
    back on a CPU-only host (attn smoke and spmv full both build here;
    full-size halo deliberately skips its 2 GB buffer materialization)."""
    g, nbytes = graph_for(DriverRequest(workload="attn", smoke=True))
    assert len(list(g.vertices())) > 0
    assert nbytes and all(v >= 0 for v in nbytes.values())
    g2, nbytes2 = graph_for(DriverRequest(workload="spmv", m=512))
    assert len(list(g2.vertices())) > 0
    assert nbytes2


def test_graph_for_resolves_recorded_ops_across_nearby_shapes():
    """A schedule serialized against one shape re-materializes against a
    nearby shape's graph — the property the near-miss tier rests on."""
    from tenzing_tpu.core.platform import Platform
    from tenzing_tpu.core.serdes import sequence_from_json, sequence_to_json
    from tenzing_tpu.core.state import State

    g1, _ = graph_for(DriverRequest(workload="spmv", m=512))
    g2, _ = graph_for(DriverRequest(workload="spmv", m=500))
    plat = Platform.make_n_lanes(2)
    st = State(g1)
    while not st.is_terminal():
        st = st.apply(st.get_decisions(plat)[0])
    seq2 = sequence_from_json(sequence_to_json(st.sequence), g2)
    assert len(seq2) == len(st.sequence)


def test_run_scope_disposes_handlers_for_repeat_calls():
    """run() is the work-queue drain step (docs/serving.md): each call's
    atexit/trap registrations must run their finalizers once and then
    disappear, so item N's SIGINT can never fire item N-1's checkpoint
    stamps and closures never pin buffers until process exit."""
    from tenzing_tpu.bench.driver import _RunScope
    from tenzing_tpu.utils import trap

    calls = []
    before = len(trap._callbacks)
    sc = _RunScope()
    sc.on_exit(lambda: calls.append("first"))
    sc.on_exit(lambda: calls.append("second"))
    sc.on_trap(lambda: calls.append("trap"))
    assert len(trap._callbacks) == before + 1
    sc.close()
    # LIFO like atexit (prefetcher.close must finalize its counters
    # before the earlier-registered telemetry flush writes them out);
    # each finalizer ran exactly once, the trap handler not at all
    assert calls == ["second", "first"]
    assert len(trap._callbacks) == before  # trap handler unregistered
    sc.close()  # idempotent: a second close re-runs nothing
    assert calls == ["second", "first"]


def test_run_scope_failed_finalizer_does_not_mask_others(capsys):
    from tenzing_tpu.bench.driver import _RunScope

    calls = []
    sc = _RunScope()
    sc.on_exit(lambda: (_ for _ in ()).throw(RuntimeError("boom")))
    sc.on_exit(lambda: calls.append("second"))
    sc.close()
    assert calls == ["second"]  # the failure was reported, not fatal


def test_bench_shim_reexports_the_builders():
    import bench

    assert bench.build_halo is driver.build_halo
    assert bench.build_attn is driver.build_attn
    assert bench.metric_for is driver.metric_for
    assert bench.ALIAS_UNPACK is driver.ALIAS_UNPACK


def test_non_smoke_run_is_refused_off_the_tpu():
    """A run without --smoke measures on a TPU or not at all: on the CPU
    backend (where this suite runs) the driver answers with the device
    error before it builds anything, and stamps the device it found."""
    res = driver.run(DriverRequest(workload="halo", halo_n=8, mcts_iters=1,
                                   climb_budget=1))
    v = res.verdict
    assert v["error"].startswith("device refused")
    assert v["device"]["platform"] == "cpu" and v["device"]["count"] >= 1
    assert v["value"] == -1.0 and v["metric"] == "halo_iter_pct50_searched_n8"


@pytest.mark.parametrize("verdict,rc", [
    ({"metric": "m", "value": 1.0}, 0),
    ({"metric": "m", "value": -1.0, "error": "device refused: ..."}, 1),
    ({"metric": "m", "value": 1.0, "fault": {"degraded": True}}, 1),
    ({"metric": "m", "value": 1.0, "fault": {"degraded": False}}, 0),
])
def test_bench_exit_code_follows_the_verdict(verdict, rc, monkeypatch,
                                             capsys):
    """bench.py keeps the parseable line and exits non-zero on an ``error``
    verdict and on a degraded one."""
    import json
    import sys

    import bench

    monkeypatch.setattr(bench, "run_driver",
                        lambda req: driver.DriverResult(verdict=verdict))
    monkeypatch.setattr(sys, "argv", ["bench.py", "--smoke"])
    assert bench.main() == rc
    assert json.loads(capsys.readouterr().out.strip()) == verdict
