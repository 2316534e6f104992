"""The latent-decode configuration's reference refuses what it must at the
rehearsal shapes (the cache read as float8, a sequence read through a wrong
table row, a key past a length let in, the new row left out, a wrong
appended row), its cost function counts what a brute count finds, and the
cell walks on the CPU."""

import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import cell as cell_mod
from benchmarks.harness.cell import load_module, toy_shapes
from benchmarks.harness.mla_costs import latent_decode_cost

CONFIGS = Path(__file__).parent.parent / "configs"
FULL = json.loads((CONFIGS / "dsv3-mla-decode.json").read_text())
TOY = toy_shapes(FULL)
ref = load_module("references", "mla_paged_decode")
Z = ref.sizes(TOY)


def values(compared):
    return {c["name"]: (c["value"], c["limit"]) for c in compared}


def plain(seed, layer, **fault):
    """One layer's ``o`` in numpy float64 from the *published* equations,
    every sequence and head spelled out, its cache made dense through the
    table.  ``fault``: ``extra_key`` lets a sequence see the key after its
    last, ``drop_new`` leaves the new row out, ``wrong_row`` reads sequence
    7 through sequence 5's table row."""
    data = {k: np.asarray(v, np.float64) if v.dtype != jnp.int32
            else np.asarray(v) for k, v in ref.make_data(TOY, seed).items()}
    t = {k: data[f"{k}.L{layer}"] for k in ref.DRAWN}
    page, rank = Z["page"], Z["rank"]
    out = np.zeros((len(Z["lens"]), Z["heads"], Z["v_dim"]))
    for b, length in enumerate(Z["lens"]):
        row = 5 if fault.get("wrong_row") and b == 7 else b
        pages = [t["C"][data["table"][row, j]].T
                 for j in range(length // page)] + [t["Copen"][b].T]
        cache = np.concatenate(pages)[:length + bool(fault.get("extra_key"))]
        new = np.concatenate([t["c_new"][b], t["kr_new"][b]])[None]
        if not fault.get("drop_new"):
            cache = np.concatenate([cache[:length], new, cache[length:]])
        c, k_rope = cache[:, :rank], cache[:, rank:]
        for h in range(Z["heads"]):
            k_nope = c @ t["W_UK"][h].T
            v = c @ t["W_UV"][h]
            s = Z["scale"] * (k_nope @ t["q_nope"][b, h]
                              + k_rope @ t["q_rope"][b, h])
            p = np.exp(s - s.max())
            out[b, h] = (p / p.sum()) @ v
    return out


def outputs_of(seed, **fault):
    out = dict(ref.sound(TOY, seed))
    for i in range(Z["layers"]):
        out[f"o.L{i}"] = jnp.asarray(plain(seed, i, **fault), jnp.float32)
    return out


def test_sizes_are_the_published_ones_and_the_toy_s():
    full = ref.sizes(FULL)
    assert (full["heads"], full["rank"], full["rope"], full["nope"],
            full["v_dim"]) == (128, 512, 64, 128, 128)
    assert round(full["scale"], 6) == 0.135234
    lens = full["lens"]
    # the issue's pre-declared cut: every second of 32 sorted lengths
    assert len(lens) == 16 and (min(lens), max(lens)) == (8278, 131031)
    assert sum(lens) == 564322
    assert not any(n % 512 == 0 for n in lens)
    assert Z["lens"] == (3, 9, 13, 17, 26, 31, 44, 61) and Z["page"] == 8
    # every catalog number of the attention stands as published
    assert FULL["kv_lora_rank"] == 512 and FULL["q_lora_rank"] == 1536
    assert FULL["num_hidden_layers"] == 61 and FULL["reduced"] == ["layers"]


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11])
def test_sound_layers_pass(seed):
    for out in (ref.sound(TOY, seed), outputs_of(seed)):
        got = values(ref.check(TOY, seed, out))
        assert got["mla_append_mismatched_rows"] == (0, 0)
        for name in ("mla_o_rms_gap", "mla_o_widest_row_gap"):
            assert got[name][0] <= 1e-5 < got[name][1]


@pytest.mark.parametrize("fault", ["extra_key", "drop_new", "wrong_row"])
def test_a_fault_of_place_fails_the_widest_row(fault):
    got = values(ref.check(TOY, 5, outputs_of(5, **{fault: True})))
    assert got["mla_o_widest_row_gap"][0] > got["mla_o_widest_row_gap"][1]
    assert got["mla_o_widest_row_gap"][0] > 0.1


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_float8_control_fails(seed):
    got = values(ref.check(TOY, seed, ref.control(TOY, seed)))
    assert got["mla_o_rms_gap"][0] > got["mla_o_rms_gap"][1]
    assert got["mla_o_widest_row_gap"][0] > got["mla_o_widest_row_gap"][1]


def test_a_wrong_appended_row_is_counted():
    out = dict(ref.sound(TOY, 4))
    opened = out["Copen.L2"]
    col = Z["lens"][3] % Z["page"]
    out["Copen.L2"] = opened.at[3, 0, col].add(1.0)      # the new row, off
    out["Copen.L1"] = out["Copen.L1"].at[6, 5, 7].add(1.0)  # another column
    got = values(ref.check(TOY, 4, out))
    assert got["mla_append_mismatched_rows"] == (2, 0)


def test_published_form_in_blocks_is_the_absorbed_reference():
    data = ref.make_data(TOY, 6)
    for i in range(Z["layers"]):
        t = ref.layer_tensors(data, i)
        np.testing.assert_allclose(
            np.asarray(ref.published_layer(Z, t, keys=16)),
            np.asarray(ref.layer_reference(Z, t)), rtol=2e-5, atol=2e-6)


def test_costs_count_what_a_brute_count_finds():
    lens, heads, rank, rope, nope, v_dim = (3, 9, 13), 4, 16, 8, 8, 8
    c = latent_decode_cost(lens, heads, rank, rope, nope, v_dim, layers=2)
    flops = bytes_ = 0
    for n in lens:
        for _key in range(n + 1):
            flops += heads * (2 * (rank + rope) + 2 * rank)
            bytes_ += 2 * (rank + rope)
        flops += heads * (2 * nope * rank + 2 * rank * v_dim)
        bytes_ += 2 * ((rank + rope)            # the appended row, written
                       + heads * (nope + rope)  # q_nope, q_rope
                       + (rank + rope)          # c_new, k_rope_new
                       + heads * v_dim)         # o
    bytes_ += 2 * heads * (nope * rank + rank * v_dim)
    assert c["flops"] == 2 * flops and c["hbm_bytes"] == 2 * bytes_
    assert c["keys"] == 2 * sum(n + 1 for n in lens)
    # the issue's reckoning at the cell's own size: both peaks bind
    z = ref.sizes(FULL)
    full = latent_decode_cost(z["lens"], z["heads"], z["rank"], z["rope"],
                              z["nope"], z["v_dim"], z["layers"])
    by_mxu, by_hbm = full["flops"] / 197e12, full["hbm_bytes"] / 819e9
    assert 3.2e-3 < by_hbm < 3.4e-3 and abs(by_mxu / by_hbm - 1) < 0.05
    assert full["cache_share"] > 0.99


def test_cell_walks_on_the_cpu_and_reports_its_metrics():
    seed = 2**31 + 5
    r = cell_mod.run_cell("dsv3-mla-decode.climb", seed, 12.0, True,
                          time.perf_counter(), rehearse=True,
                          devices=jax.devices()[:1])
    assert r["correct"] is True
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert {"window_evals", "speedup_vs_naive", "dispatch_fixed_ms",
            "naive_iter_ms", "mla_padded_key_share"} <= set(r["metrics"])
    share = r["metrics"]["mla_padded_key_share"]["value"]
    rec = json.loads((cell_mod.HERE / "out" / f"dsv3-mla-decode.climb.seed"
                      f"{seed}" / "record.trace1.json").read_text())["record"]
    traced = rec["cost"]["traced_keys"]
    best = 1 + int(rec["epilogue"]["best"]["label"][len("finalist"):])
    useful = Z["layers"] * sum(n + 1 for n in Z["lens"])
    assert traced[best][0] == useful
    tiles = Z["layers"] * sum(n // Z["page"] + 1 for n in Z["lens"])
    assert traced[best][1] == tiles * Z["page"]
    assert share == 100.0 * (1 - traced[best][0] / traced[best][1])
    # a CPU has no device plane: the trace's readers leave theirs out
    assert "mla_step_roofline" not in r["metrics"]
    assert "mla_kernel_device_share" not in r["metrics"]
