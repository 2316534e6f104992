"""``BENCHMARK.json`` keeps to the contract's syntax, every name resolves to
a file, and a run without a chip is refused without a result line."""

import json
import os
import re
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent.parent
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_names_units_and_keys():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["source"]) <= 200
        assert (ROOT / c["file"]).is_file()
        assert all(NAME.match(k) for k in c["reduced"])
        doc = json.loads((ROOT / c["file"]).read_text())
        assert doc["reduced"] == c["reduced"]
    cfgs = {c["name"] for c in BENCH["configs"]}
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in cfgs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        assert (ROOT / "benchmarks" / "mixes" /
                f"{w['traffic']}.json").is_file()
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for w in BENCH["workloads"]:
        mine = [m for m in BENCH["end_to_end"]
                if w["name"] in m.get("workloads", [w["name"]])]
        assert len(mine) >= 2 and any(m["name"] == "setup_s" for m in mine)
        assert any(w["name"] in m.get("workloads", [w["name"]])
                   for m in BENCH["per_layer"])
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e
        assert (ROOT / "benchmarks" / "layer_metrics" /
                f"{m['name']}.py").is_file()
        for w in m.get("workloads", []):
            cell_e2e = {e["name"] for e in BENCH["end_to_end"]
                        if w in e.get("workloads", [w])}
            assert m["moves"] in cell_e2e, (m["name"], w)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))


def test_a_cell_reads_the_per_layer_metrics_of_what_it_reports():
    """Each per-layer metric is read where the end-to-end metric it moves is
    reported; one entry a quantity, and no metric's name carries a cell's."""
    from benchmarks.harness.cell import load_cell

    cells = [w["name"] for w in BENCH["workloads"]]
    for name in cells:
        cell = load_cell(name)
        reported = {m["name"] for m in cell.end_to_end}
        assert cell.per_layer
        assert all(m["moves"] in reported for m in cell.per_layer)
        for m in BENCH["per_layer"]:
            listed = name in m.get("workloads", [name])
            assert (m in cell.per_layer) == (listed and
                                             m["moves"] in reported)
    traffics = {w["traffic"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"] + BENCH["end_to_end"]:
        assert not set(m["name"].split(".")[1:]) & traffics, m["name"]


def test_a_run_without_a_chip_is_refused_without_a_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "halo512.climb",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
    assert "needs a TPU" in p.stderr


def test_an_unknown_device_kind_is_refused():
    import pytest

    from benchmarks.harness.peaks import UnknownDeviceError, peaks_for

    assert peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(UnknownDeviceError):
        peaks_for("TPU v4")
