"""The attention configuration's reference refuses what it must at the
rehearsal shapes (one key beyond the window let in, a wrong key/value head
for one query head, float8 K and V), its cost function counts the pairs a
brute count finds, and the cell walks on the CPU."""

import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import cell as cell_mod
from benchmarks.harness.attn_costs import attention_layers_cost, visible_pairs
from benchmarks.harness.cell import load_module, toy_shapes

CONFIGS = Path(__file__).parent.parent / "configs"
TOY = toy_shapes(json.loads((CONFIGS / "trinity-attn32k.json").read_text()))
ref = load_module("references", "attn_window_gqa")
Z = ref.sizes(TOY)


def values(compared):
    return {c["name"]: (c["value"], c["limit"]) for c in compared}


def plain(q, k, v, window, extra_key=False):
    """One layer in numpy float64 from the equations, every head spelled
    out; ``extra_key`` lets each row see one key beyond its window."""
    h, n, d = q.shape
    group = h // k.shape[0]
    o = np.zeros((h, n, d))
    for head in range(h):
        g = head // group
        s = q[head] @ k[g].T / np.sqrt(d)
        i, j = np.arange(n)[:, None], np.arange(n)[None, :]
        seen = j <= i
        if window is not None:
            seen &= j > i - window - (1 if extra_key else 0)
        p = np.where(seen, np.exp(s - s.max()), 0.0)
        o[head] = (p / p.sum(axis=1, keepdims=True)) @ v[g]
    return o


def layers_of(seed, **kw):
    data = {n: np.asarray(t, np.float64)
            for n, t in ref.make_data(TOY, seed).items()}
    return {f"O.L{i}": jnp.asarray(plain(
        data[f"Q.L{i}"], data[f"K.L{i}"], data[f"V.L{i}"], w, **kw),
        jnp.float32) for i, w in enumerate(Z["windows"])}


def test_sizes_are_the_published_ones_and_the_toy_s():
    full = ref.sizes(json.loads(
        (CONFIGS / "trinity-attn32k.json").read_text()))
    assert (full["heads"], full["kv_heads"], full["d"]) == (32, 4, 128)
    assert full["windows"] == (2048, 2048, 2048, None)
    assert Z["windows"] == (12, 12, 12, None) and Z["n"] == 40


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11])
def test_sound_layers_pass(seed):
    for out in (ref.sound(TOY, seed), layers_of(seed)):
        for value, limit in values(ref.check(TOY, seed, out)).values():
            assert value <= 1e-5 < limit


def test_one_key_beyond_the_window_let_in_fails():
    got = values(ref.check(TOY, 5, layers_of(5, extra_key=True)))
    assert got["attn_o_widest_row_gap"][0] > got["attn_o_widest_row_gap"][1]
    assert got["attn_o_rms_gap"][0] > got["attn_o_rms_gap"][1]


def test_a_wrong_kv_head_for_one_query_head_fails():
    out = dict(ref.sound(TOY, 5))
    data = ref.make_data(TOY, 5)
    q, k, v = (np.asarray(data[f"{t}.L3"], np.float64) for t in "QKV")
    # query head 1 (of key/value head 0's group) reads key/value head 1
    wrong = plain(q[1:2], k[1:2], v[1:2], None)
    out["O.L3"] = out["O.L3"].at[1].set(jnp.asarray(wrong[0], jnp.float32))
    got = values(ref.check(TOY, 5, out))
    assert got["attn_o_widest_row_gap"][0] > 0.5


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_float8_control_fails(seed):
    got = values(ref.check(TOY, seed, ref.control(TOY, seed)))
    assert any(value > limit for value, limit in got.values())


@pytest.mark.parametrize("count", [1, 2, 7, 10, 4097])
def test_the_floor_is_the_median(count):
    x = np.abs(np.random.default_rng(count).standard_normal(count)).astype(
        np.float32)
    x[: count // 3] = x[0]  # ties
    assert float(ref._median(jnp.asarray(x))) == float(np.median(x))
    assert float(ref._median(jnp.zeros(count))) == 0.0


def test_costs_count_what_a_brute_count_finds():
    for n, window in ((40, 12), (40, None), (9, 12), (12, 12), (13, 12)):
        i, j = np.arange(n)[:, None], np.arange(n)[None, :]
        seen = (j <= i) & ((j > i - window) if window else True)
        assert visible_pairs(n, window) == int(seen.sum())
    c = attention_layers_cost(40, (12, 12, 12, None), 4, 2, 8)
    pairs = 3 * visible_pairs(40, 12) + visible_pairs(40)
    assert c["flops"] == 4.0 * 8 * 4 * pairs
    assert c["hbm_bytes"] == 2.0 * 4 * 40 * (2 * 4 + 2 * 2) * 8
    # the issue's reckoning at the cell's own size: 8.80 + 3.20 TFLOP
    full = attention_layers_cost(32768, (2048, 2048, 2048, None), 32, 4, 128)
    assert round(full["flops"] / 1e12, 2) == 11.99


def test_cell_walks_on_the_cpu_and_reports_its_metrics():
    r = cell_mod.run_cell("trinity-attn32k.climb", 2**31 + 5, 12.0, True,
                          time.perf_counter(), rehearse=True,
                          devices=jax.devices()[:1])
    assert r["correct"] is True
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert {"window_evals", "speedup_vs_naive", "dispatch_fixed_ms",
            "naive_iter_ms", "attn_masked_work_share"} <= set(r["metrics"])
    share = r["metrics"]["attn_masked_work_share"]["value"]
    assert 0 < share < 100
    # the share is the best finalist's own program's: its one-shot trace
    # counted every visible pair of the period once, and no other body's
    rec = json.loads((cell_mod.HERE / "out" / f"trinity-attn32k.climb.seed"
                      f"{2**31 + 5}" / "record.trace1.json").read_text())
    rec = rec["record"]
    traced = rec["cost"]["traced_pairs"]
    assert len(traced) == 1 + len(rec["epilogue"]["clocks"]) - 1
    best = 1 + int(rec["epilogue"]["best"]["label"][len("finalist"):])
    pairs = 3 * visible_pairs(40, 12) + visible_pairs(40)
    assert traced[best][0] == Z["heads"] * pairs
    assert share == 100.0 * (1 - traced[best][0] / traced[best][1])
    # a CPU has no device plane: the trace's readers leave theirs out
    assert "attn_mxu_roofline" not in r["metrics"]
