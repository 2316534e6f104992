"""The sparse-decode configuration's reference refuses what it must at the
rehearsal shapes (both caches read as float8, a selection that is no
selection, one that is the wrong one, rows gathered from the next position,
a sequence read through a wrong table row, a wrong appended row or index
key) and passes what it must (a near-tie decided the other way), its cost
function counts what a brute count finds, the configuration holds every
catalog key, and the cell walks on the CPU."""

import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import cell as cell_mod
from benchmarks.harness.cell import load_module, toy_shapes
from benchmarks.harness.dsa_costs import sparse_decode_cost

CONFIGS = Path(__file__).parent.parent / "configs"
FULL = json.loads((CONFIGS / "dsv32-dsa-decode.json").read_text())
DENSE = json.loads((CONFIGS / "dsv3-mla-decode.json").read_text())
TOY = toy_shapes(FULL)
ref = load_module("references", "dsa_paged_decode")
Z = ref.sizes(TOY)
PICKED = ref.picked(Z)
CELL = "dsv32-dsa-decode.climb"


def values(compared):
    return {c["name"]: (c["value"], c["limit"]) for c in compared}


def plain(seed, layer, sel=None, **fault):
    """One layer in numpy float64 from the *published* equations, every
    sequence and head spelled out, its caches made dense through the table:
    ``(o, selections, scores)``.  ``sel``: attend over these positions
    instead of the layer's own selection.  ``fault``: ``wrong_row`` reads
    sequence 7 through sequence 5's table row; ``shift`` attends over the
    positions after the selected ones."""
    data = {k: np.asarray(v, np.float64) if v.dtype != jnp.int32
            else np.asarray(v) for k, v in ref.make_data(TOY, seed).items()}
    t = {k: data[f"{k}.L{layer}"] for k in ref.DRAWN}
    page, rank, w = Z["page"], Z["rank"], Z["rank"] + Z["rope"]
    out = np.zeros((len(Z["lens"]), Z["heads"], Z["v_dim"]))
    picked, scores = [], []
    for b, length in enumerate(Z["lens"]):
        row = 5 if fault.get("wrong_row") and b == 7 else b
        pages = range(length // page)
        cache = np.concatenate(
            [t["C"][data["table"][row, j]] for j in pages]
            + [t["Copen"][b]])[:length, :w]
        keys = np.concatenate(
            [t["KI"][data["table"][row, j]].T for j in pages]
            + [t["KIopen"][b].T])[:length]
        cache = np.concatenate(
            [cache, np.concatenate([t["c_new"][b], t["kr_new"][b]])[None]])
        keys = np.concatenate([keys, t["kI_new"][b][None]])
        index = (np.maximum(t["qI"][b] @ keys.T, 0.0)
                 * t["wI"][b][:, None]).sum(0)
        own = np.sort(np.argsort(-index, kind="stable")[:Z["topk"]])
        s_b = own if sel is None else np.asarray(sel[b])
        if fault.get("shift"):
            s_b = np.minimum(s_b + 1, length)
        picked.append(own)
        scores.append(index)
        c, k_rope = cache[:, :rank], cache[:, rank:]
        for h in range(Z["heads"]):
            k_nope = c @ t["W_UK"][h].T
            v = c @ t["W_UV"][h]
            s = Z["scale"] * (k_nope @ t["q_nope"][b, h]
                              + k_rope @ t["q_rope"][b, h])
            p = np.zeros_like(s)
            p[s_b] = np.exp(s[s_b] - s[s_b].max())
            out[b, h] = (p / p.sum()) @ v
    return out, picked, scores


def slots(picked):
    """A list of selections as the program's ``sel``: ``(batch, topk)``,
    the slots past a sequence's count filled with the first positions past
    its length."""
    sel = np.zeros((len(picked), Z["topk"]), np.int32)
    for b, s in enumerate(picked):
        fill = Z["lens"][b] + 1 + np.arange(Z["topk"] - len(s))
        sel[b] = np.concatenate([s, fill])
    return jnp.asarray(sel)


def outputs_of(seed, picked=None, **fault):
    """The reference's sound outputs with ``o`` and ``sel`` from the plain
    numpy form (over ``picked``, a list of selections a layer, where
    given)."""
    out = dict(ref.sound(TOY, seed))
    for i in range(Z["layers"]):
        o, own, _ = plain(seed, i, sel=picked and picked[i], **fault)
        out[f"o.L{i}"] = jnp.asarray(o, jnp.float32)
        out[f"sel.L{i}"] = slots(picked[i] if picked else own)
    return out


def test_sizes_are_the_published_ones_and_the_toy_s():
    full = ref.sizes(FULL)
    assert (full["heads"], full["rank"], full["rope"], full["nope"],
            full["v_dim"]) == (128, 512, 64, 128, 128)
    assert (full["index_heads"], full["index_dim"], full["topk"]) == (
        64, 128, 2048)
    assert round(full["scale"], 6) == 0.135234
    assert ref.row_width(full) == 640
    # the dense cell's sixteen lengths, letter for letter: every sequence
    # has more than 2048 visible keys
    assert full["lens"] == tuple(sorted(DENSE["shapes"]["lens"]))
    assert sum(full["lens"]) == 564322 and set(ref.picked(full)) == {2048}
    assert full["page"] == DENSE["shapes"]["page_tokens"] == 2048
    # the issue's 4 groups, less its pre-declared cut (2): assumed.groups
    assert full["groups"] == 2 and full["table_seed"] == 35
    assert Z["lens"] == (3, 9, 13, 17, 26, 31, 44, 61) and Z["page"] == 8
    assert PICKED == [4, 10, 14, 16, 16, 16, 16, 16]
    # never cut: a full-size file whose shapes disagree with the model's
    with pytest.raises(ValueError, match="never cut"):
        ref.sizes({**FULL, "shapes": {**FULL["shapes"], "index_topk": 1024}})


def test_the_file_holds_every_catalog_key_as_published():
    """DeepSeek-V3's keys as the dense cell's file has them, and the three
    of the indexer; ``reduced`` names the layers alone."""
    model = {k: v for k, v in DENSE.items() if k in (
        "attention_bias", "ep_size", "first_k_dense_replace", "hidden_act",
        "hidden_size", "intermediate_size", "kv_lora_rank",
        "max_position_embeddings", "moe_intermediate_size", "moe_layer_freq",
        "n_group", "n_routed_experts", "n_shared_experts", "norm_topk_prob",
        "num_attention_heads", "num_experts_per_tok", "num_hidden_layers",
        "num_key_value_heads", "num_nextn_predict_layers", "q_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "rms_norm_eps",
        "rope_scaling", "rope_theta", "routed_scaling_factor",
        "scoring_func", "tie_word_embeddings", "topk_group", "topk_method",
        "v_head_dim", "vocab_size")}
    assert len(model) == 32
    assert {k: FULL[k] for k in model} == model
    assert FULL["model_type"] == "deepseek_v32"
    assert (FULL["index_n_heads"], FULL["index_head_dim"],
            FULL["index_topk"]) == (64, 128, 2048)
    assert FULL["reduced"] == ["layers"] and FULL["layers"] == 4
    assert set(FULL["reduced_why"]) == {"layers"}
    for key in ("cache_layout", "index_cache", "selection", "naive"):
        assert len(FULL["assumed"][key]) > 40, key


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11])
def test_sound_layers_pass(seed):
    for out in (ref.sound(TOY, seed), outputs_of(seed)):
        got = values(ref.check(TOY, seed, out))
        for name in ("dsa_select_malformed_rows",
                     "dsa_selection_outside_margin",
                     "dsa_append_mismatched_rows"):
            assert got[name] == (0, 0), name
        assert got["dsa_selection_differs"][0] == 0
        assert got["dsa_selection_differs"][1] == Z["layers"] * sum(PICKED)
        for name in ("dsa_o_rms_gap", "dsa_o_widest_row_gap"):
            assert got[name][0] <= 1e-5 < got[name][1]


def test_a_near_tie_decided_the_other_way_moves_nothing_but_the_report():
    """The selected key with the lowest score swapped for the unselected
    one with the highest, where the two lie inside the margin (made so: the
    index keys of the two positions made equal but for one part in 2^20):
    no limit moves, ``dsa_selection_differs`` reports it."""
    seed = 9
    data = ref.make_data(TOY, seed)
    _, own, scores = plain(seed, 0)
    # a sequence whose boundary scores lie apart, neither its new row's
    for b in range(3, len(Z["lens"])):
        order = np.argsort(-scores[b], kind="stable")
        last_in, first_out = order[Z["topk"] - 1], order[Z["topk"]]
        if (scores[b][last_in] - scores[b][first_out] > 10 * ref.MARGIN
                and Z["lens"][b] not in (last_in, first_out)):
            break
    else:
        raise AssertionError("no such sequence: another seed")
    picked = [[np.array(s) for s in plain(seed, i)[1]]
              for i in range(Z["layers"])]
    swapped = np.sort(np.concatenate(
        [np.setdiff1d(picked[0][b], [last_in]), [first_out]]))
    picked[0][b] = swapped
    got = values(ref.check(TOY, seed, outputs_of(seed, picked)))
    # outside the margin here: the key let in lies far under the
    # reference's last (the one left out is that last itself: not over it)
    assert got["dsa_selection_outside_margin"][0] == 1
    assert got["dsa_selection_differs"][0] == 1
    # the same swap between scores a hair apart: inside it
    page = Z["page"]
    table, lens = np.asarray(data["table"]), Z["lens"]

    def where(pos):
        slot = pos // page
        name = "KIopen.L0" if slot == lens[b] // page else "KI.L0"
        lead = b if name == "KIopen.L0" else table[b, slot]
        return name, lead, pos % page

    (n_in, l_in, c_in), (n_out, l_out, c_out) = where(last_in), where(
        first_out)
    near = dict(data)
    key = np.asarray(near[n_in])[l_in, :, c_in]
    arr = np.array(near[n_out])
    arr[l_out, :, c_out] = key * (1 - 2.0 ** -20)
    near[n_out] = jnp.asarray(arr)
    ref._DATA[(ref._frozen(Z), seed)] = near
    ref._reference_of.cache_clear()
    try:
        out = outputs_of(seed, picked)
        got = values(ref.check(TOY, seed, out))
    finally:
        ref._DATA.clear()
        ref._reference_of.cache_clear()
    assert got["dsa_selection_outside_margin"] == (0, 0)
    assert got["dsa_select_malformed_rows"] == (0, 0)
    assert got["dsa_o_widest_row_gap"][0] <= 1e-5


def test_both_ways_to_the_reference_s_selection_agree():
    """``lax.top_k`` (the control's and the sound layers') and the search
    without a sort (``check``'s), on scores with many equal and a row
    mostly ``NEG``."""
    rng = np.random.default_rng(0)
    x = rng.standard_normal((6, 200)).astype(np.float32)
    x[:, ::2] = np.round(x[:, ::2], 1)
    x[0, 9:] = ref.NEG
    x[1] = 0.25
    at, kth = ref.exact_selection(Z, jnp.asarray(x))
    member, value = ref.members_without_a_sort(Z, jnp.asarray(x))
    want = np.zeros(x.shape, bool)
    np.put_along_axis(want, np.argsort(-x, axis=1, kind="stable")[
        :, :Z["topk"]], True, axis=1)
    assert np.array_equal(np.asarray(member), want)
    got = np.zeros(x.shape, bool)
    np.put_along_axis(got, np.asarray(at), True, axis=1)
    assert np.array_equal(got, want)
    assert np.array_equal(np.asarray(kth), np.asarray(value))
    assert np.array_equal(np.asarray(kth), np.sort(x, axis=1)[:, -Z["topk"]])


@pytest.mark.parametrize("fault", ["doubled", "past_the_length", "too_few"])
def test_a_selection_that_is_none_is_malformed(fault):
    seed = 3
    picked = [[np.array(s) for s in plain(seed, i)[1]]
              for i in range(Z["layers"])]
    out = outputs_of(seed, picked)
    sel = np.array(out["sel.L1"])
    if fault == "doubled":
        sel[6, 3] = sel[6, 4]
    elif fault == "past_the_length":
        sel[2, 1] = Z["lens"][2] + 1
    else:
        sel[1, PICKED[1] - 1] = Z["lens"][1] + 5  # a slot that counts, empty
    out["sel.L1"] = jnp.asarray(sel)
    got = values(ref.check(TOY, seed, out))
    assert got["dsa_select_malformed_rows"] == (1, 0)


@pytest.mark.parametrize("fault", ["shift", "wrong_row"])
def test_a_fault_of_place_fails_the_widest_row(fault):
    got = values(ref.check(TOY, 5, outputs_of(5, **{fault: True})))
    assert got["dsa_o_widest_row_gap"][0] > got["dsa_o_widest_row_gap"][1]
    assert got["dsa_o_widest_row_gap"][0] > 0.1


def test_the_lowest_scores_selected_lie_outside_the_margin():
    seed = 6
    worst = []
    for i in range(Z["layers"]):
        scores = plain(seed, i)[2]
        worst.append([np.sort(np.argsort(s, kind="stable")[:n])
                      for s, n in zip(scores, PICKED)])
    got = values(ref.check(TOY, seed, outputs_of(seed, worst)))
    assert got["dsa_select_malformed_rows"] == (0, 0)
    assert got["dsa_selection_outside_margin"][0] > 100
    assert got["dsa_selection_differs"][0] > 100


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_float8_control_fails(seed):
    out = ref.control(TOY, seed)
    got = values(ref.check(TOY, seed, out))
    assert got["dsa_o_rms_gap"][0] > got["dsa_o_rms_gap"][1]
    assert got["dsa_o_widest_row_gap"][0] > got["dsa_o_widest_row_gap"][1]
    assert got["dsa_selection_outside_margin"][0] > 0
    assert got["dsa_select_malformed_rows"] == (0, 0)
    read = ref.readings(TOY, seed, out)
    assert read["selection_widest_excess"] > ref.MARGIN
    assert read["score_widest_gap"] > ref.MARGIN
    sound = ref.readings(TOY, seed, ref.sound(TOY, seed))
    assert sound["selection_widest_excess"] == 0.0
    assert sound["score_widest_gap"] == 0.0


def test_a_wrong_appended_row_or_index_key_is_counted():
    out = dict(ref.sound(TOY, 4))
    at = Z["lens"][3] % Z["page"]
    out["Copen.L2"] = out["Copen.L2"].at[3, at, 0].add(1.0)   # the new row
    out["Copen.L1"] = out["Copen.L1"].at[6, 5, 7].add(1.0)    # another row
    out["KIopen.L0"] = out["KIopen.L0"].at[2, 1, 3].add(1.0)  # an index key
    got = values(ref.check(TOY, 4, out))
    assert got["dsa_append_mismatched_rows"] == (3, 0)


def test_costs_count_what_a_brute_count_finds():
    lens, heads, rank, rope, nope, v_dim = (3, 9, 13), 4, 16, 8, 8, 8
    ih, idim, topk = 4, 8, 8
    c = sparse_decode_cost(lens, heads, rank, rope, nope, v_dim, ih, idim,
                           topk, layers=2)
    flops = bytes_ = index_bytes = 0
    for n in lens:
        for _key in range(n + 1):
            flops += ih * 2 * idim
            index_bytes += 2 * idim
        for _key in range(min(topk, n + 1)):
            flops += heads * (2 * (rank + rope) + 2 * rank)
            bytes_ += 2 * (rank + rope)
        flops += heads * (2 * nope * rank + 2 * rank * v_dim)
        bytes_ += 2 * ((rank + rope) + idim     # the appended rows, written
                       + heads * (nope + rope)  # q_nope, q_rope
                       + (rank + rope)          # c_new, k_rope_new
                       + ih * idim + idim       # qI, kI_new
                       + heads * v_dim)         # o
        bytes_ += 4 * ih                        # wI, float32
    bytes_ += 2 * heads * (nope * rank + rank * v_dim)
    assert c["flops"] == 2 * flops
    assert c["hbm_bytes"] == 2 * (bytes_ + index_bytes)
    assert c["index_bytes"] == 2 * index_bytes
    assert c["keys_indexed"] == 2 * sum(n + 1 for n in lens)
    assert c["keys_selected"] == 2 * (4 + 8 + 8)
    # the issue's reckoning at the cell's own size: 0.86 GB, 1.05 ms by HBM (1.06),
    # 0.38 by the MXU
    z = ref.sizes(FULL)
    full = sparse_decode_cost(
        z["lens"], z["heads"], z["rank"], z["rope"], z["nope"], z["v_dim"],
        z["index_heads"], z["index_dim"], z["topk"], z["layers"])
    assert 0.85e9 < full["hbm_bytes"] < 0.87e9
    assert 577e6 < full["index_bytes"] < 579e6
    by_mxu, by_hbm = full["flops"] / 197e12, full["hbm_bytes"] / 819e9
    assert 1.04e-3 < by_hbm < 1.07e-3 and 0.3 < by_mxu / by_hbm < 0.4


def test_cell_walks_on_the_cpu_and_reports_its_metrics():
    seed = 2**31 + 5
    r = cell_mod.run_cell(CELL, seed, 12.0, True, time.perf_counter(),
                          rehearse=True, devices=jax.devices()[:1])
    assert r["correct"] is True
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert {"window_evals", "speedup_vs_naive", "dispatch_fixed_ms",
            "naive_iter_ms", "dsa_select_padded_share"} <= set(r["metrics"])
    share = r["metrics"]["dsa_select_padded_share"]["value"]
    rec = json.loads((cell_mod.HERE / "out" / f"{CELL}.seed{seed}"
                      / "record.trace1.json").read_text())["record"]
    traced = rec["cost"]["traced_candidates"]
    best = 1 + int(rec["epilogue"]["best"]["label"][len("finalist"):])
    assert traced[best][0] == Z["layers"] * sum(n + 1 for n in Z["lens"])
    # a layer's selections are handed, whole pages and at least topk, each
    # group of two the longer's pages or, where the search chose one
    # selection for the layer, every sequence the longest's
    def pages(n):
        return max((n // Z["page"] + 1) * Z["page"], Z["topk"])

    by_group = sum(2 * pages(n) for n in Z["lens"][1::2])
    by_layer = len(Z["lens"]) * pages(max(Z["lens"]))
    assert traced[best][1] in [a * by_group + (Z["layers"] - a) * by_layer
                               for a in range(Z["layers"] + 1)]
    assert share == 100.0 * (1 - traced[best][0] / traced[best][1])
    # a CPU has no device plane: the trace's readers leave theirs out
    for name in ("dsa_step_roofline", "dsa_index_roofline",
                 "dsa_index_device_share", "dsa_read_device_share"):
        assert name not in r["metrics"]
    assert {c.rsplit(".", 1)[1] for c in r["compared"]} >= {
        "dsa_select_malformed_rows", "dsa_selection_outside_margin",
        "dsa_selection_differs", "dsa_o_rms_gap", "dsa_o_widest_row_gap",
        "dsa_append_mismatched_rows", "timed_fence_gap"}
