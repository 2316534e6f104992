"""The shortcut-connected decode configuration's reference refuses what it
must at the rehearsal shapes (the expert slots as float8 with bfloat16
scores, the zero picks' term left out, the caches read as float8, two
tokens' outputs swapped, a row appended that the step did not make, an
output gathered onto one device), passes its own float32 step, agrees with
the program's plain reference (``tenzing_tpu/models/
shortcut_moe_reference.py``: this file holds the two copies of the
equations together), its cost function counts what the issue counted, and
its readers read a record."""

import json
from pathlib import Path

import jax
import numpy as np
import pytest

from benchmarks.harness.cell import load_module, toy_shapes
from benchmarks.harness.scmoe_costs import scmoe_step_cost

CONFIGS = Path(__file__).parent.parent / "configs"
FULL = json.loads((CONFIGS / "longcat-lite-scmoe-decode.json").read_text())
TOY = toy_shapes(FULL)
ref = load_module("references", "scmoe_decode")
Z = ref.sizes(TOY)
SEED = 11


def values(compared):
    return {c["name"]: (c["value"], c["limit"]) for c in compared}


def refused(compared):
    return sorted(n for n, (v, lim) in values(compared).items() if v > lim)


def test_sound_passes_and_every_limit_holds():
    got = ref.check(TOY, SEED, ref.sound(TOY, SEED))
    assert refused(got) == []
    v = values(got)
    assert v["scmoe_h_rms_gap"][0] < 1e-6  # float32 against itself
    assert v["scmoe_tokens_left_out"][0] == 0


@pytest.mark.parametrize("kind, first", [
    ("control", "scmoe_expert_rms_gap"), ("zero_control", "scmoe_s_rms_gap"),
    ("cache_control", "scmoe_h_rms_gap")])
def test_controls_are_refused(kind, first):
    """One precision down, or a term left out: ``check`` refuses, by the
    limit named at least."""
    bad = refused(ref.check(TOY, SEED, getattr(ref, kind)(TOY, SEED)))
    assert first in bad
    assert "scmoe_append_mismatched_rows" not in bad


def test_faults_of_place_are_refused():
    out = ref.sound(TOY, SEED)
    last = f"h.B{Z['blocks']}"
    h = np.array(out[last])
    h[[1, 6]] = h[[6, 1]]
    swapped = {**out, last: jax.device_put(h, out[last].sharding)}
    assert "scmoe_h_widest_token_gap" in refused(
        ref.check(TOY, SEED, swapped))
    tag = "B1.a0"
    opened = np.array(out[f"Copen.{tag}"])
    opened[2, :, 0] += 1.0  # a column the step does not own
    moved = {**out, f"Copen.{tag}": jax.device_put(
        opened, out[f"Copen.{tag}"].sharding)}
    got = values(ref.check(TOY, SEED, moved))
    assert got["scmoe_append_mismatched_rows"][0] == 1
    gathered = {**out, last: jax.device_put(out[last], jax.devices()[0])}
    assert values(ref.check(TOY, SEED, gathered))[
        "chips_without_a_shard"][0] == 3


def test_the_two_references_agree():
    """The benchmark's copy (paged, by chip, experts where they live)
    against the program's plain reference (dense caches, one sequence at a
    time, every expert over every token) on the same data."""
    from tenzing_tpu.models import latent_attention as la
    from tenzing_tpu.models import shortcut_moe as sm
    from tenzing_tpu.models import shortcut_moe_reference as plain
    from tenzing_tpu.models.moe import MoEArgs

    mla = la.LatentDecodeArgs(
        lens=Z["lens"], heads=Z["heads"], rank=Z["rank"], rope=Z["rope"],
        nope=Z["nope"], v_dim=Z["v_dim"], scale=Z["scale"], page=Z["page"],
        groups=Z["groups"], fold_pages=Z["fold_pages"], dtype=Z["dtype"])
    moe = MoEArgs(
        n_ep=Z["ranks"], tokens_per_shard=len(Z["lens"]), d_model=Z["d"],
        d_ff=Z["f"], n_chunks=1, dtype=Z["dtype"],
        experts_per_shard=Z["held"], top_k=Z["top_k"], gated=True,
        capacity_factor=Z["capacity_factor"], scoring="softmax",
        routed_scale=Z["route_scale"], zero_experts=Z["zero"],
        gate_in_iteration=True)
    args = sm.ScMoEArgs(mla=mla, moe=moe, blocks=Z["blocks"],
                        q_rank=Z["q_rank"], ffn=Z["ffn"], eps=Z["eps"],
                        rope_theta=Z["theta"], rope_factor=Z["factor"],
                        rope_original=Z["original"],
                        beta_fast=Z["beta_fast"], beta_slow=Z["beta_slow"])
    data = {k: np.asarray(v) for k, v in ref.make_data(TOY, SEED).items()}
    assert np.array_equal(data["table"],
                          la.block_table(mla, Z["table_seed"], Z["ranks"]))
    assert np.allclose(ref.frequencies(Z), sm.rope_frequencies(
        Z["rope"], Z["theta"], Z["factor"], Z["original"], Z["beta_fast"],
        Z["beta_slow"]))
    layout = sm.data_layout(args)
    for name, x in data.items():  # the names and shapes are the program's
        assert tuple(x.shape) == tuple(layout[name][0]), name
    n, b, pool = Z["ranks"], len(Z["lens"]), mla.pool_pages
    caches = {}
    for t in sm.attn_tags(args):
        caches[t] = []
        for s in range(n):
            local = {f"C.{t}": data[f"C.{t}"][s * pool:(s + 1) * pool],
                     f"Copen.{t}": data[f"Copen.{t}"][s * b:(s + 1) * b],
                     "table": data["table"][s * b:(s + 1) * b]}
            caches[t] += la.dense_caches(mla, local, t)
    want = plain.forward(args, data, data["h.B0"], caches,
                         list(Z["lens"]) * n)
    got = ref.forward(TOY, SEED)
    for l in range(Z["blocks"]):
        for a, w in ((got["h"][l], want["h"][l + 1]),
                     (got["s"][l], want["s"][l]),
                     (got["m0"][l], want["m0"][l])):
            w = np.asarray(w)
            assert np.abs(np.asarray(a) - w).max() < 2e-5 * np.abs(w).max()
    for t in sm.attn_tags(args):
        assert np.allclose(np.asarray(got["rows"][t]),
                           np.asarray(want["rows"][t]), atol=2e-5)


def test_cost_counts_what_the_issue_counted():
    z = ref.sizes(FULL)
    n_router = z["experts"] + z["zero"]
    c = scmoe_step_cost(
        z["lens"], z["blocks"], z["d"], z["ffn"], z["f"], z["held"], n_router,
        len(z["lens"]) * z["top_k"] * z["experts"] / n_router, z["heads"],
        z["rank"], z["q_rank"], z["rope"], z["nope"], z["v_dim"], 2)
    experts = 2 * 64 * 3 * 3072 * 1024 * 2
    dense = 4 * 3 * 3072 * 6144 * 2
    assert experts == pytest.approx(2.42e9, rel=0.01)
    assert dense == pytest.approx(0.45e9, rel=0.01)
    assert c["weight_bytes"] == pytest.approx(
        experts + dense + 4 * 65.4e6 - 4 * 8.4e6 + 2 * 4.7e6, rel=0.01)
    keys = sum(n + 1 for n in z["lens"])
    # the visible rows, and 3% more: the appended rows, the absorbed pair,
    # q and o (mla_costs.py counts a layer's operands with its cache)
    assert c["cache_bytes"] == pytest.approx(4 * keys * 576 * 2, rel=0.04)
    assert 4.2e9 < c["hbm_bytes"] < 4.6e9 and c["scmoe_bytes"] == \
        c["hbm_bytes"]
    assert c["flops"] / 197e12 < c["hbm_bytes"] / 819e9  # HBM binds


def test_readers_read_a_record_and_nothing_where_there_is_nothing():
    names = ("scmoe_step_roofline", "scmoe_exchange_device_share",
             "scmoe_mla_kernel_device_share", "scmoe_zero_pick_share",
             "scmoe_slot_fill_share")
    readers = {n: load_module("layer_metrics", n) for n in names}
    bare = {"trace": None, "cost": {"hbm_bytes": 1.0}, "peaks": None}
    for n in names[:3]:
        assert readers[n].read(bare) is None
    record = {
        "cost": {"scmoe_bytes": 4.4e9, "flops": 1.3e11, "hbm_bytes": 4.4e9},
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
        "trace": {"finalist_n": [2, 8],
                  "finalist_modules": [["m", 0.020], ["m", 0.068]],
                  "window": {"busy_s": 2.0, "device_ops": [
                      ["fusion", 1.0], ["mla_decode", 0.5],
                      ["all-to-all", 0.06], ["collective-permute-start",
                                             0.04]]}}}
    assert readers["scmoe_step_roofline"].read(record) == pytest.approx(
        100 * (4.4e9 / 819e9) / 0.008)
    assert readers["scmoe_exchange_device_share"].read(record) == \
        pytest.approx(5.0)
    assert readers["scmoe_mla_kernel_device_share"].read(record) == \
        pytest.approx(25.0)
    from tenzing_tpu.obs.metrics import get_metrics

    reg = get_metrics()
    if not reg.counter("moe.zero_picks").value:
        assert readers["scmoe_zero_pick_share"].read(record) is None
        assert readers["scmoe_slot_fill_share"].read(record) is None
    reg.counter("moe.zero_picks").inc(10)
    reg.counter("moe.routed_slots").inc(20)
    reg.counter("moe.capacity_slots").inc(160)
    z, r, c = (reg.counter(f"moe.{k}").value for k in (
        "zero_picks", "routed_slots", "capacity_slots"))
    assert readers["scmoe_zero_pick_share"].read(record) == pytest.approx(
        100 * z / (z + r))
    assert readers["scmoe_slot_fill_share"].read(record) == pytest.approx(
        100 * r / c)
