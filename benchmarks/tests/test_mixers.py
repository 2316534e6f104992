"""The mixers-prefill configuration's reference passes what is sound and
refuses its three controls and faults of place at the rehearsal shapes, its
cost function counts what the issue counted, its seven readers read a
record (and nothing where there is nothing), and the cell walks on the
CPU."""

import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import cell as cell_mod
from benchmarks.harness.cell import load_module, toy_shapes
from benchmarks.harness.mixers_costs import (
    mixers_prefill_cost,
    packed_pairs,
    ssd_scan_cost,
)

CELL = "nemotron3-nano-mixers-prefill.climb"
CONFIGS = Path(__file__).parent.parent / "configs"
FULL = json.loads((CONFIGS / "nemotron3-nano-mixers-prefill.json").read_text())
TOY = toy_shapes(FULL)
ref = load_module("references", "mamba2_mixers_prefill")
Z = ref.sizes(TOY)


def values(compared):
    return {c["name"]: (c["value"], c["limit"]) for c in compared}


def test_sizes_are_the_published_ones_and_the_toy_s():
    full = ref.sizes(FULL)
    assert (full["heads"], full["head_dim"], full["groups"], full["state"],
            full["taps"], full["chunk"]) == (64, 64, 8, 128, 4, 128)
    assert (full["attn_heads"], full["kv_heads"], full["attn_head_dim"]) == (
        32, 2, 128)
    assert sum(full["lens"]) == 16384 and len(full["lens"]) == 12
    assert Z["lens"] == (13, 11, 3, 5) and Z["chunk"] == 8
    assert ref.tags(TOY) == [("M", "L0.M"), ("M", "L1.M"), ("M", "L2.M"),
                             ("*", "L3.A")]
    assert len(ref.outputs(TOY)) == 10


@pytest.mark.parametrize("seed", [0, 2**31 + 11])
def test_sound_passes_and_every_limit_holds(seed):
    for value, limit in values(ref.check(TOY, seed,
                                         ref.sound(TOY, seed))).values():
        assert value <= min(limit, 1e-5)


def test_controls_are_refused():
    """At the toy's float32 and short prompts the bfloat16 state moves the
    final states by a few thousandths (the cell's limit is set on the
    chip's readings at 4000-token prompts: PERF.md section 2); the
    boundaries ignored and float8 K and V fail their limits here too."""
    seed = 5
    low = values(ref.check(TOY, seed, ref.control(TOY, seed)))
    assert low["ssd_state_worst_head_gap"][0] > 1e-3
    assert low["conv_tail_rms_gap"][0] == 0.0
    across = values(ref.check(TOY, seed, ref.control_boundaries(TOY, seed)))
    for name in ("mixer_out_rms_gap", "mixer_out_widest_row_gap",
                 "ssd_state_worst_head_gap"):
        assert across[name][0] > 10 * across[name][1], name
    kv8 = values(ref.check(TOY, seed, ref.control_kv8(TOY, seed)))
    assert kv8["mixer_out_rms_gap"][0] > kv8["mixer_out_rms_gap"][1]
    assert kv8["ssd_state_worst_head_gap"][0] == 0.0


@pytest.mark.parametrize("fault,number", [
    ("tail_a_row_early", "conv_tail_rms_gap"),
    ("states_of_two_prompts_swapped", "ssd_state_worst_head_gap"),
    ("one_head_reads_the_other_group", "mixer_out_widest_row_gap"),
    ("one_query_head_reads_the_other_kv_head", "mixer_out_widest_row_gap"),
])
def test_a_fault_of_place_fails_its_limit(fault, number):
    seed = 9
    out = dict(ref.sound(TOY, seed))
    data = ref.make_data(TOY, seed)
    if fault == "tail_a_row_early":
        ends = np.asarray(data["ends"])
        rows = np.stack([np.asarray(data["xBC.L1.M"], np.float32)[e - 3:e]
                         for e in ends[:2]])
        tail = np.asarray(out["tail.L1.M"], np.float32).copy()
        tail[:2] = rows
        out["tail.L1.M"] = jnp.asarray(tail)
    elif fault == "states_of_two_prompts_swapped":
        s = np.asarray(out["Sfin.L0.M"]).copy()
        s[[0, 1]] = s[[1, 0]]
        out["Sfin.L0.M"] = jnp.asarray(s)
    elif fault == "one_head_reads_the_other_group":
        # head 1 (of group 0) with group 1's B and C: another mixer's out
        swapped = np.asarray(data["xBC.L2.M"], np.float32).copy()
        inner, gn = Z["heads"] * Z["head_dim"], Z["groups"] * Z["state"]
        b = swapped[:, inner:inner + gn].copy()
        swapped[:, inner:inner + Z["state"]] = b[:, Z["state"]:]
        swapped[:, inner + Z["state"]:inner + gn] = b[:, :Z["state"]]
        p = {k: jnp.asarray(data[f"{k}.L2.M"], jnp.float32)
             for k in ref.M_PARAMS}
        with jax.default_matmul_precision("highest"):
            out["out.L2.M"] = ref.mamba_mixer(
                Z, data["z.L2.M"], jnp.asarray(swapped), data["dt.L2.M"],
                p)[0]
    else:
        k = np.asarray(data["K.L3.A"], np.float32)
        v = np.asarray(data["V.L3.A"], np.float32)
        with jax.default_matmul_precision("highest"):
            wrong = ref.attention(data["Q.L3.A"][1:2], jnp.asarray(k[1:2]),
                                  jnp.asarray(v[1:2]), data["seg"])
        o = np.asarray(out["O.L3.A"], np.float32).copy()
        o[1] = np.asarray(wrong[0])
        out["O.L3.A"] = jnp.asarray(o)
    got = values(ref.check(TOY, seed, out))
    assert got[number][0] > got[number][1], got


def test_cost_counts_what_the_issue_counted():
    z = ref.sizes(FULL)
    scan = ssd_scan_cost(16384, 12, 64, 64, 8, 128, 128)
    assert scan["flops"] / 16384 == pytest.approx(3.4e6, rel=0.01)
    cost = mixers_prefill_cost(
        z["lens"], z["pattern"], z["heads"], z["head_dim"], z["groups"],
        z["state"], z["taps"], z["chunk"], z["attn_heads"], z["kv_heads"],
        z["attn_head_dim"])
    assert cost["ssd_flops"] == pytest.approx(168e9, rel=0.01)
    assert len(cost["layers"]) == 4
    attn_flops = cost["layers"][3]["flops"]
    assert attn_flops == pytest.approx(0.36e12, rel=0.02)
    assert attn_flops == 4.0 * 128 * 32 * packed_pairs(z["lens"])
    assert cost["flops"] == sum(x["flops"] for x in cost["layers"])
    # a Mamba-2 mixer's floor: its inputs, its out and what it leaves
    mixer = cost["layers"][0]["hbm_bytes"]
    assert mixer == (16384 * ((4096 + 6144 + 4096) * 2 + 64 * 4)
                     + 12 * (4096 * 128 * 4 + 3 * 6144 * 2))
    # the kernel's own floor is not above the layer's
    assert scan["hbm_bytes"] < mixer
    # packing: one prompt of 16 384 sees more pairs than twelve
    assert packed_pairs((16384,)) > 3 * packed_pairs(z["lens"])
    dense = np.tril(np.ones((32, 32), bool))
    seg = np.repeat(np.arange(4), Z["lens"])
    assert packed_pairs(Z["lens"]) == int(
        (dense & (seg[:, None] == seg[None, :])).sum())


NAMES = ("ssd_scan_roofline", "ssd_scan_device_share",
         "mixers_attn_kernel_device_share", "mixers_step_roofline",
         "ssd_boundary_chunk_share", "mixers_attn_masked_work_share",
         "mixers_temp_peak_gb")


def test_readers_read_a_record_and_nothing_where_there_is_nothing():
    from tenzing_tpu.obs.metrics import MetricsRegistry, set_metrics

    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
    counts = {"ssd.chunks": 384, "ssd.boundary_chunks": 24,
              "ssd.fused_vertices": 2, "attn.pairs_useful": 60,
              "attn.pairs_computed": 100}
    cost = {"flops": 5.3e11, "hbm_bytes": 1.8e9, "ssd_flops": 1.68e11,
            "ssd_bytes": 1.1e9,
            "layers": [{"flops": 5.6e10, "hbm_bytes": 5.0e8}] * 3
            + [{"flops": 3.6e11, "hbm_bytes": 2.8e8}],
            "traced_counts": [dict.fromkeys(counts, 1), counts],
            # the start point's profiled dispatch: three scans on the kernel
            "start_point_counts": {**counts, "ssd.fused_vertices": 3},
            "start_point_ops": [["ssd_scan", 0.0081], ["fusion", 0.012],
                                ["attn_fused", 0.0064]]}
    record = {
        "peaks": peaks, "cost": cost,
        "epilogue": {"best": {"label": "finalist0"}},
        "trace": {"finalist_n": [4, 16],
                  "finalist_modules": [["a", 0.1], ["b", 0.34]],
                  "window": {"busy_s": 2.0, "device_ops": [
                      ["fusion", 0.6], ["ssd_scan", 0.2],
                      ["attn_fused", 0.4], ["copy", 0.72],
                      ["reduce_sum", 0.08]]}}}
    read = {m: load_module("layer_metrics", m).read for m in NAMES}
    iter_s = 0.24 / 12
    # the kernel's own seconds, whatever the window's slice held: two of
    # the start point's three calls in the finalist's iteration
    assert read["ssd_scan_device_share"](record) == pytest.approx(
        100 * (0.0081 * 2 / 3) / iter_s)
    assert read["mixers_attn_kernel_device_share"](record) == pytest.approx(
        20.0)
    # the bytes bind (1.34 ms against 0.85), over the three scans' seconds
    assert read["ssd_scan_roofline"](record) == pytest.approx(
        100 * (1.1e9 / 819e9) / 0.0081)
    naive_best = {**record, "epilogue": {"best": {"label": "naive"}}}
    assert read["ssd_scan_device_share"](naive_best) is None
    assert read["ssd_scan_roofline"](naive_best) == read[
        "ssd_scan_roofline"](record)
    least = 3 * 5.0e8 / 819e9 + 3.6e11 / 197e12
    assert read["mixers_step_roofline"](record) == pytest.approx(
        100 * least / iter_s)
    assert read["ssd_boundary_chunk_share"](record) == 6.25
    assert read["mixers_attn_masked_work_share"](record) == pytest.approx(
        40.0)
    reg = MetricsRegistry()
    prev = set_metrics(reg)
    try:
        assert read["mixers_temp_peak_gb"](record) is None
        reg.gauge("executor.program_temp_bytes_max").set(2.5e9)
        assert read["mixers_temp_peak_gb"](record) == 2.5
        # a program without the kernel, the counters or the cost (the
        # parent's, another configuration's): nothing, and no raise
        bare = {"peaks": peaks, "cost": {"hbm_bytes": 1.0, "flops": 1.0},
                "epilogue": {"best": {"label": "finalist0"}},
                "trace": {**record["trace"], "window": {
                    "busy_s": 2.0, "device_ops": [["fusion", 0.8]]}}}
        reg.gauge("executor.program_temp_bytes_max").set(0)
        for name in NAMES:
            assert read[name](bare) is None, name
            assert read[name]({**bare, "trace": None}) is None, name
            assert read[name]({**bare, "cost": None}) is None, name
    finally:
        set_metrics(prev)


def test_cell_walks_on_the_cpu_and_reports_its_metrics():
    seed = 2**31 + 5
    r = cell_mod.run_cell(CELL, seed, 10.0, True, time.perf_counter(),
                          rehearse=True, devices=jax.devices()[:1])
    assert r["correct"] is True
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert {"window_evals", "speedup_vs_naive", "dispatch_fixed_ms",
            "naive_iter_ms", "ssd_boundary_chunk_share",
            "mixers_attn_masked_work_share",
            "mixers_temp_peak_gb"} <= set(r["metrics"])
    assert r["metrics"]["ssd_boundary_chunk_share"]["value"] == 50.0
    compared = r["compared"]
    assert compared["finalist0.timed_fence_gap"] == [0.0, 0]
    assert compared["naive.conv_tail_rms_gap"][0] == 0.0
    rec = json.loads((cell_mod.HERE / "out" / f"{CELL}.seed{seed}"
                      / "record.trace1.json").read_text())["record"]
    assert len(rec["cost"]["layers"]) == 4
    assert len(rec["cost"]["traced_counts"]) >= 2
    # set-up traced the start point, so a finalist that is the start point
    # gains nothing in the epilogue and reads set-up's counts
    start = rec["cost"]["start_point_counts"]
    assert start["ssd.fused_vertices"] == 3 and start["ssd.chunks"] > 0
    assert start in rec["cost"]["traced_counts"][1:]
    assert rec["cost"]["start_point_ops"] == []
    # a CPU has no device plane: the trace's readers leave theirs out
    assert "mixers_step_roofline" not in r["metrics"]
    assert "ssd_scan_roofline" not in r["metrics"]
