"""The trace reduction, on a hand-made trace with known answers and on a
small trace recorded on the chip (``data/``, written by ``trace_dump.py``)."""

import json
from pathlib import Path

import pytest

from benchmarks.harness import trace as t

DATA = Path(__file__).parent / "data"


def ms(x):
    return int(x * 1e6)


def synthetic():
    ops = [["while.1", ms(10), ms(30)],            # parent of the next two
           ["fusion.a", ms(10), ms(18)],
           ["copy.b", ms(20), ms(30)],
           ["fusion.a", ms(50), ms(60)],
           ["outside", ms(200), ms(210)]]          # after the last span
    mods = [["jit_stepped(1)", ms(10), ms(30)], ["jit_stepped(2)", ms(50), ms(60)]]
    host = [["tzb:solver", ms(0), ms(8)],
            ["tzb:measure", ms(8), ms(70)],
            ["tzb:verify", ms(8), ms(9)],
            ["tzb:first_call", ms(30), ms(50)],
            ["tzb:solver", ms(70), ms(100)],
            ["PjitFunction(x)", ms(1), ms(2)]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": mods}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": host},
            {"name": "tz-prefetch_0",
             "events": [["tzb:first_call_bg", ms(5), ms(40)]]}]}]}


def test_merge_intervals_counts_each_instant_once():
    assert t.merge_intervals([(0, 5), (3, 8), (10, 12), (12, 13), (4, 4)]) \
        == [[0, 8], [10, 13]]


def test_self_times_take_children_out_of_parents():
    st = t.self_times(synthetic()["planes"][0]["lines"][0]["events"])
    assert st["while.1"] == ms(2)        # 20 ms less 8 and 10
    assert st["fusion.a"] == ms(18)
    assert st["copy.b"] == ms(10)


def test_reduce_window_busy_idle_and_gap_names():
    r = t.reduce_window(synthetic())
    assert r["window_s"] == pytest.approx(0.100)   # solver 0 .. solver 100
    assert r["busy_s"] == pytest.approx(0.030)     # [10,30] + [50,60]
    gaps = dict(r["idle_gaps"])
    # 0-8 solver, 8-9 verify, 9-10 measure, 30-50 first_call, 60-70 measure,
    # 70-100 solver
    assert gaps["solver"] == pytest.approx(0.038)
    assert gaps["verify"] == pytest.approx(0.001)
    assert gaps["first_call"] == pytest.approx(0.020)
    assert gaps["measure"] == pytest.approx(0.011)
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    tr = synthetic()
    tr["planes"][1]["lines"].append({"name": "pjrt-tpu-tasks/1", "events": [
        ["MapDmaBuffer", ms(25), ms(45)], ["MapDmaBuffer", ms(40), ms(55)]]})
    tr["planes"][1]["lines"].append({"name": "pjrt-tpu-tasks/2", "events": [
        ["MapDmaBuffer", ms(30), ms(52)]]})
    # the runtime was in MapDmaBuffer over 25-55, of which 30-50 is idle
    assert dict(t.reduce_window(tr)["idle_gaps"])["host:MapDmaBuffer"] == \
        pytest.approx(0.020)
    assert r["device_ops"][0][0] == "fusion.a"
    assert "outside" not in dict(r["device_ops"])


def test_module_seconds_in_order():
    assert t.module_seconds(synthetic()) == [
        ("jit_stepped(1)", pytest.approx(0.020)),
        ("jit_stepped(2)", pytest.approx(0.010))]


def test_no_device_plane_reads_nothing():
    tr = synthetic()
    tr["planes"] = tr["planes"][1:]
    assert t.reduce_window(tr) == {}
    assert t.module_seconds(tr) == []


@pytest.mark.parametrize("name", sorted(p.name for p in DATA.glob("*.json")))
def test_recorded_trace(name):
    tr = json.loads((DATA / name).read_text())
    want = tr.pop("expect")
    r = t.reduce_window(tr)
    assert r["n_devices"] == want["n_devices"]
    assert r["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert 0 < r["busy_s"] < r["window_s"]
    spans = [v for n, v in r["idle_gaps"] if not n.startswith("host:")]
    assert sum(spans) == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)
    runtime = dict((n, v) for n, v in r["idle_gaps"] if n.startswith("host:"))
    assert runtime and max(runtime.values()) <= r["window_s"]
    assert [n for n, _ in r["device_ops"]][:3] == want["top_ops"]
