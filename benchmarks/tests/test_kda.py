"""The hybrid-decode configuration's reference refuses what it must at the
rehearsal shapes (the state carried in bfloat16, a wrong gate in one head,
the state of another sequence, a window not moved on, the latent cache read
as float8), passes a plain float64 spelling of the published recurrence, its
cost function counts what a brute count finds, its readers read a record,
and the cell walks on the CPU."""

import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import cell as cell_mod
from benchmarks.harness.cell import load_module, toy_shapes
from benchmarks.harness.kda_costs import hybrid_decode_cost, kda_decode_cost

CONFIGS = Path(__file__).parent.parent / "configs"
FULL = json.loads((CONFIGS / "kimi-linear-kda-decode.json").read_text())
TOY = toy_shapes(FULL)
ref = load_module("references", "kda_hybrid_decode")
Z = ref.sizes(TOY)
CELL = "kimi-linear-kda-decode.climb"


def values(compared):
    return {c["name"]: (c["value"], c["limit"]) for c in compared}


def plain(seed, tag, **fault):
    """One KDA layer's ``(o, Snew)`` in numpy float64 from the published
    recurrence ``S_t = (I - beta k k^T) Diag(alpha) S_{t-1} + beta k v^T``,
    every sequence and head spelled out with matrix products.  ``fault``:
    ``no_gate`` leaves head 1's output gate out, ``wrong_state`` reads
    sequence 3's state for sequence 5, ``no_decay`` sets alpha to 1."""
    data = {k: np.asarray(v, np.float64)
            for k, v in ref.make_data(TOY, seed).items()}
    t = {k: data[f"{k}.{tag}"] for k in ref.KDA_DRAWN}
    b_, h_, d = len(Z["lens"]), Z["kda_heads"], Z["d"]
    o, snew = np.zeros((b_, h_, d)), np.zeros((b_, h_, d, d))
    sig = lambda x: 1.0 / (1.0 + np.exp(-x))
    for b in range(b_):
        rows = np.concatenate([t["Cv"][b], t["x"][b][None]])
        y = (t["Wc"] * rows).sum(0)
        y = y * sig(y)
        for h in range(h_):
            q, k, v = y[0, h], y[1, h], y[2, h]
            q = q / np.sqrt(q @ q + 1e-6) * d ** -0.5
            k = k / np.sqrt(k @ k + 1e-6)
            alpha = np.exp(-np.exp(t["A_log"][h, 0]) * np.log1p(np.exp(
                t["f"][b, h] + t["dt_bias"][h])))
            if fault.get("no_decay"):
                alpha = np.ones_like(alpha)
            beta = sig(t["b"][b, h, 0])
            s = t["S"][3 if fault.get("wrong_state") and b == 5 else b, h]
            new = (np.eye(d) - beta * np.outer(k, k)) @ np.diag(alpha) @ s \
                + beta * np.outer(k, v)
            out = new.T @ q
            out = out / np.sqrt((out * out).mean() + Z["eps"]) \
                * t["w_norm"][0]
            gate = 1.0 if fault.get("no_gate") and h == 1 \
                else sig(t["go"][b, h])
            o[b, h], snew[b, h] = out * gate, new
    return o, snew


def outputs_of(seed, **fault):
    out = dict(ref.sound(TOY, seed))
    for kind, tag in ref.tags(TOY):
        if kind == "kda":
            o, snew = plain(seed, tag, **fault)
            out[f"o.{tag}"] = jnp.asarray(o, jnp.float32)
            out[f"Snew.{tag}"] = jnp.asarray(snew, jnp.float32)
    return out


def test_sizes_are_the_published_ones_and_the_toy_s():
    full = ref.sizes(FULL)
    assert (full["kda_heads"], full["d"], full["taps"]) == (32, 128, 4)
    assert (full["heads"], full["rank"], full["rope"], full["nope"],
            full["v_dim"]) == (32, 512, 64, 128, 128)
    assert round(full["scale"], 7) == 0.0721688 and full["eps"] == 1e-5
    assert full["pattern"] == ("kda", "kda", "kda", "mla")
    lens = full["lens"]
    assert len(lens) in (64, 128) and not any(n % 512 == 0 for n in lens)
    assert FULL["num_hidden_layers"] == 27 and FULL["reduced"] == ["layers"]
    assert Z["lens"] == (3, 9, 13, 17, 26, 31, 44, 61) and Z["d"] == 16


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11])
def test_sound_layers_pass(seed):
    for out in (ref.sound(TOY, seed), outputs_of(seed)):
        got = values(ref.check(TOY, seed, out))
        assert got["kda_conv_mismatched_rows"] == (0, 0)
        assert got["mla_append_mismatched_rows"] == (0, 0)
        assert got["kda_state_rms_gap"][0] < got["kda_state_rms_gap"][1] / 10
        for name in ("kda_o_rms_gap", "kda_o_widest_row_gap",
                     "mla_o_rms_gap", "mla_o_widest_row_gap"):
            assert got[name][0] <= 1e-5 < got[name][1]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_a_state_carried_in_bfloat16_fails_by_the_state_s_limit(seed):
    got = values(ref.check(TOY, seed, ref.control(TOY, seed)))
    assert got["kda_state_rms_gap"][0] > 100 * got["kda_state_rms_gap"][1]
    # by the state's limit, not by each: the toy's o is float32, and the
    # latent layer is sound
    assert got["kda_o_widest_row_gap"][0] < got["kda_o_widest_row_gap"][1]
    assert got["mla_o_rms_gap"][0] < got["mla_o_rms_gap"][1]


def test_the_latent_cache_read_as_float8_fails_the_latent_limits():
    got = values(ref.check(TOY, 4, ref.cache_control(TOY, 4)))
    assert got["mla_o_rms_gap"][0] > got["mla_o_rms_gap"][1]
    assert got["mla_o_widest_row_gap"][0] > got["mla_o_widest_row_gap"][1]
    assert got["kda_state_rms_gap"][0] < got["kda_state_rms_gap"][1]


@pytest.mark.parametrize("fault,number", [
    ("no_gate", "kda_o_widest_row_gap"), ("wrong_state", "kda_state_rms_gap"),
    ("wrong_state", "kda_o_widest_row_gap"),
    ("no_decay", "kda_state_rms_gap")])
def test_a_fault_fails_its_limit(fault, number):
    got = values(ref.check(TOY, 5, outputs_of(5, **{fault: True})))
    assert got[number][0] > got[number][1]
    assert got[number][0] > 0.05


def test_a_window_not_moved_on_is_counted():
    out = dict(ref.sound(TOY, 4))
    data = ref.make_data(TOY, 4)
    out["Cvnew.L1"] = out["Cvnew.L1"].at[2].set(data["Cv.L1"][2])
    out["Cvnew.L0"] = out["Cvnew.L0"].at[6, 2, 1, 0, 3].add(1.0)
    got = values(ref.check(TOY, 4, out))
    assert got["kda_conv_mismatched_rows"] == (2, 0)


def test_costs_count_what_a_brute_count_finds():
    batch, heads, d, taps = 3, 2, 16, 4
    c = kda_decode_cost(batch, heads, d, taps, layers=2)
    flops = bytes_ = state = 0
    for _seq in range(batch):
        for _head in range(heads):
            flops += d * d + 3 * 2 * d * d + 2 * taps * 3 * d
            st = 2 * 4 * d * d + 2 * 2 * (taps - 1) * 3 * d
            state += st
            bytes_ += st + 2 * (3 * d + d + d + 1 + d)
    bytes_ += heads * (2 * taps * 3 * d + 4 * d + 4) + 4 * d
    assert c["flops"] == 2 * flops and c["hbm_bytes"] == 2 * bytes_
    assert c["state_bytes"] == 2 * state
    # the issue's reckoning at the cell's own size: 1.67 GB of state, the
    # latent layer at a quarter of the ridge
    z = ref.sizes(FULL)
    full = hybrid_decode_cost(z["lens"], 3, z["kda_heads"], z["d"],
                              z["taps"], 1, z["heads"], z["rank"],
                              z["rope"], z["nope"], z["v_dim"])
    if len(z["lens"]) == 128:
        assert 1.66e9 < full["kda_state_bytes"] < 1.68e9
        assert 1.1e9 < full["mla_bytes"] < 1.25e9
    assert full["kda_bytes"] / full["hbm_bytes"] > 0.55
    assert 55 < full["mla_flops"] / full["mla_bytes"] < 65
    assert full["hbm_bytes"] == full["kda_bytes"] + full["mla_bytes"]


def test_readers_read_a_record_and_nothing_from_one_without():
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
    cost = {"hbm_bytes": 2.9e9, "kda_bytes": 1.7e9, "mla_flops": 7e10,
            "traced_kda": [[9, 9], [4, 2], [3, 2]]}
    record = {
        "peaks": peaks, "cost": cost,
        "epilogue": {"best": {"label": "finalist1"}},
        "trace": {"finalist_n": [10, 40],
                  "finalist_modules": [["a", 0.05], ["b", 0.20]],
                  "window": {"busy_s": 2.0, "device_ops": [
                      ["kda_step", 1.0], ["mla_decode", 0.8],
                      ["multiply_reduce_fusion", 0.1], ["copy", 0.06],
                      ["reduce_sum", 0.04]]}}}
    read = {m: load_module("layer_metrics", m).read for m in (
        "kda_step_roofline", "kda_state_roofline", "kda_state_device_share",
        "kda_state_excess_share")}
    iter_s = 0.15 / 30
    assert read["kda_step_roofline"](record) == pytest.approx(
        100 * 2.9e9 / 819e9 / iter_s)
    # the KDA vertices' kinds over busy; over the loop's kinds (all but
    # what a dispatch does once) for the roofline
    assert read["kda_state_device_share"](record) == pytest.approx(
        100 * 1.1 / 2.0)
    assert read["kda_state_roofline"](record) == pytest.approx(
        100 * 1.7e9 / 819e9 / (iter_s * 1.1 / 1.9))
    assert read["kda_state_excess_share"](record) == 50.0
    # a program without the kernel, the counters or the cost: nothing
    bare = {"peaks": peaks, "cost": {"hbm_bytes": 1.0},
            "epilogue": {"best": {"label": "finalist0"}},
            "trace": {**record["trace"], "window": {
                "busy_s": 2.0, "device_ops": [["mla_decode", 0.8]]}}}
    assert all(f(bare) is None for f in read.values())
    assert all(f({**bare, "trace": None}) is None for f in read.values())


def test_cell_walks_on_the_cpu_and_reports_its_metrics():
    seed = 2**31 + 5
    r = cell_mod.run_cell(CELL, seed, 12.0, True, time.perf_counter(),
                          rehearse=True, devices=jax.devices()[:1])
    assert r["correct"] is True
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert {"window_evals", "speedup_vs_naive", "dispatch_fixed_ms",
            "naive_iter_ms", "kda_state_excess_share"} <= set(r["metrics"])
    rec = json.loads((cell_mod.HERE / "out" / f"{CELL}.seed{seed}"
                      / "record.trace1.json").read_text())["record"]
    traced = rec["cost"]["traced_kda"]
    best = 1 + int(rec["epilogue"]["best"]["label"][len("finalist"):])
    least = 3 * len(Z["lens"]) * Z["kda_heads"] * (
        2 * 4 * Z["d"] ** 2 + 2 * 3 * 3 * Z["d"] * 4)
    assert traced[best][1] == least == rec["cost"]["kda_state_bytes"]
    # every kda.* counter of the finalist's traced body is in the record:
    # vertices by engine and the sequences stepped
    fused, chain, rows = traced[best][2:]
    assert fused + chain == 3 * Z["kda_groups"]
    assert rows == 3 * len(Z["lens"])
    assert r["metrics"]["kda_state_excess_share"]["value"] == pytest.approx(
        100.0 * (traced[best][0] / traced[best][1] - 1))
    # a CPU has no device plane: the trace's readers leave theirs out
    assert "kda_step_roofline" not in r["metrics"]
    assert "kda_state_device_share" not in r["metrics"]
