"""One decode step of shortcut-connected expert blocks (``models/
shortcut_moe.py``) on four virtual CPU devices at toy widths, against the
plain reference (``models/shortcut_moe_reference.py``), and what the step
forced on ``models/moe.py`` (zero experts, a layer among several, softmax
weights made in the iteration) and ``models/latent_attention.py`` (a mesh)."""

import dataclasses
import json
import random
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding

from tenzing_tpu.core.graph import Graph
from tenzing_tpu.core.platform import Platform
from tenzing_tpu.core.serdes import sequence_to_json_str
from tenzing_tpu.core.state import State
from tenzing_tpu.models import latent_attention as la
from tenzing_tpu.models import moe as moe_mod
from tenzing_tpu.models import shortcut_moe as sm
from tenzing_tpu.models import shortcut_moe_reference as ref
from tenzing_tpu.models.moe import AXIS, LayerNames, MoEArgs, MoELayer
from tenzing_tpu.obs.metrics import get_metrics
from tenzing_tpu.runtime.executor import TraceExecutor
from tenzing_tpu.solve.local import phase_policy
from tenzing_tpu.solve.local import drive as drive_policy
from tenzing_tpu.verify.soundness import ScheduleVerifier

ROOT = Path(__file__).resolve().parent.parent
LENS = (3, 9, 13, 17)
#: float32 at toy widths: a sound schedule reads rounding
TOL = 5e-5


def toy_args(dtype="float32", shards=4, zero=4, held=2, capacity=4.0):
    mla = la.LatentDecodeArgs(
        lens=LENS, heads=2, rank=16, rope=8, nope=8, v_dim=8, scale=0.3,
        page=8, groups=2, fold_pages=2, dtype=dtype)
    moe = MoEArgs(
        n_ep=shards, tokens_per_shard=len(LENS), d_model=32, d_ff=24,
        n_chunks=1, dtype=dtype, experts_per_shard=held, top_k=3, gated=True,
        capacity_factor=capacity, scoring="softmax", routed_scale=6.0,
        zero_experts=zero, gate_in_iteration=True)
    return sm.ScMoEArgs(mla=mla, moe=moe, blocks=2, q_rank=16, ffn=48)


def reference_of(args, data):
    """The plain reference's forward of the global host arrays ``data``:
    every shard's sequences through their dense caches."""
    n, b, pool = args.shards, args.mla.batch, args.mla.pool_pages
    caches = {}
    for t in sm.attn_tags(args):
        per = []
        for s in range(n):
            local = {f"C.{t}": data[f"C.{t}"][s * pool:(s + 1) * pool],
                     f"Copen.{t}": data[f"Copen.{t}"][s * b:(s + 1) * b],
                     "table": data["table"][s * b:(s + 1) * b]}
            per += la.dense_caches(args.mla, local, t)
        caches[t] = per
    return ref.forward(args, data, data["h.B0"], caches,
                       list(args.mla.lens) * n)


def mesh_of(args):
    return Mesh(np.array(jax.devices()[:args.shards]), (AXIS,))


def placed(args, mesh, host):
    layout = sm.data_layout(args)
    return {k: jax.device_put(v, NamedSharding(mesh, layout[k][2]))
            for k, v in host.items()}


@pytest.fixture(scope="module")
def step():
    """``(args, graph, platform, executor, reference's forward, host
    data)`` of the toy step on four devices, the ring exchanges on the
    menus."""
    args = toy_args()
    mesh = mesh_of(args)
    host = ref.make_data(args, seed=3)
    want = reference_of(args, host)
    data = placed(args, mesh, host)
    rows = NamedSharding(mesh, sm.data_layout(args)["h.B0"][2])
    route_on = [jax.device_put(np.asarray(m), rows) for m in want["m0"]]
    bufs, specs = sm.scmoe_buffers(args, mesh, data, route_on, synth=True)
    graph = sm.scmoe_decode_graph(args, synth=True, synth_relax=True)
    plat = Platform.make_n_lanes(2, mesh=mesh, specs=specs)
    return args, graph, plat, TraceExecutor(plat, bufs), want, host


def prefer(*suffixes):
    def f(op_name, choices):
        for want in suffixes:
            hit = next((c for c in choices if c.endswith(want)), None)
            if hit is not None:
                return hit
        return None
    return f


def schedule(step, which):
    args, graph, plat, *_ = step
    if which == "naive":
        one = Platform.make_n_lanes(1)
        return drive_policy(graph, one, phase_policy(
            one, sm.phases(args, sm.WRITTEN),
            prefer(".fixed", ".chain", ".pallas")))[0]
    if which in ("start", "ring"):
        first = ".ring.c1" if which == "ring" else ".fixed"
        return drive_policy(graph, plat, phase_policy(
            plat, sm.phases(args, sm.SHORTCUT),
            prefer(first, ".fused", ".pallas")))[0]
    rng = random.Random(int(which[-1]))  # a random walk of the search's space
    st = State(graph)
    while not st.is_terminal():
        st = st.apply(rng.choice(st.get_decisions(plat)))
    return st.sequence


@pytest.mark.parametrize("which", ["naive", "start", "ring", "walk0",
                                   "walk1", "walk2"])
def test_step_matches_the_plain_reference(step, which):
    """Every block's ``h``, each ``s`` and the appended rows of naive, the
    start point, the start point on the ring exchanges and three random
    verified schedules against the float32 reference."""
    args, graph, plat, ex, want, host = step
    seq = schedule(step, which)
    assert ScheduleVerifier(graph)(seq).ok
    out = ex.run(seq)
    for l in range(args.blocks):
        for got, w in ((out[f"h.B{l + 1}"], want["h"][l + 1]),
                       (out[f"s.B{l}"], want["s"][l])):
            w = np.asarray(w)
            assert np.abs(np.asarray(got) - w).max() < TOL * np.abs(w).max()
    for t in sm.attn_tags(args):
        row = np.concatenate([np.asarray(out[f"c_new.{t}"]),
                              np.asarray(out[f"kr_new.{t}"])], axis=1)
        assert np.allclose(row, np.asarray(want["rows"][t]), atol=TOL)
        # the open pages: the step's own row at column L_b, nothing else
        opened = np.array(host[f"Copen.{t}"])
        for b in range(opened.shape[0]):
            opened[b, :, LENS[b % len(LENS)] % args.mla.page] = row[b]
        assert np.array_equal(np.asarray(out[f"Copen.{t}"]), opened)


def test_the_branches_meet_only_at_m0_and_the_join(step):
    """The graph's freedom: in the start point the dense branch's vertices
    lie between each exchange's post and its await; naive keeps the written
    order; a block's expert branch never reads the dense branch."""
    args, graph, plat, ex, want, _ = step
    names = [op.name() for op in schedule(step, "start")]
    at = {n: i for i, n in enumerate(names)}
    for l in range(args.blocks):
        b = f"B{l}."
        assert at[b + "moe.a2a_disp_0"] < at[b + "f0.ffn"] \
            < at[b + "moe.await_disp_0"]
        assert at[b + "moe.a2a_comb_0"] < at[b + "a1.norm"] \
            < at[b + "f1.ffn"] < at[b + "moe.await_comb_0"] < at[b + "join"]
    names = [op.name() for op in schedule(step, "naive")]
    at = {n: i for i, n in enumerate(names)}
    assert at["B0.moe.moe_concat"] < at["B0.f0.ffn"] < at["B0.a1.norm"]
    dense = {"hF.B0.f0", "hA.B0.a1", "hF.B0.f1", "m.B0.f1"}
    for op in schedule(step, "start"):
        if op.name().startswith("B0.moe.") and hasattr(op, "reads"):
            assert not dense & set(op.reads())


def test_an_iteration_is_idempotent(step):
    """n repeats leave the buffers one leaves: the repeat-n program's fence
    after 1 and after 3 repeats (what ``timed_fence_gap`` needs)."""
    args, graph, plat, ex, *_ = step
    seq = schedule(step, "start")
    ex.prepare_n(seq)(1)
    f = ex._cache["n:" + sequence_to_json_str(seq)]
    one = float(f(ex.init_bufs, jnp.int32(1))[0])
    assert one == float(f(ex.init_bufs, jnp.int32(3))[0])


def test_plan_span_and_counters():
    from tenzing_tpu.obs.tracer import Tracer, set_tracer

    args = toy_args()
    mesh = mesh_of(args)
    host = ref.make_data(args, seed=5)
    want = reference_of(args, host)
    rows = NamedSharding(mesh, sm.data_layout(args)["h.B0"][2])
    reg = get_metrics()
    names = ("moe.zero_picks", "moe.routed_slots", "moe.capacity_slots",
             "moe.dropped_slots", "scmoe.weight_bytes", "scmoe.cache_bytes")
    before = {k: reg.counter(k).value for k in names}
    tr = Tracer(enabled=True)
    prev = set_tracer(tr)
    try:
        bufs, _ = sm.scmoe_buffers(
            args, mesh, placed(args, mesh, host),
            [jax.device_put(np.asarray(m), rows) for m in want["m0"]])
    finally:
        set_tracer(prev)
    plan = [s for s in tr.spans() if s.name == "scmoe.plan"][-1]
    assert plan.attrs["blocks"] == 2 and plan.attrs["capacity"] == 4
    got = {k: reg.counter(k).value - before[k] for k in names}
    picks = args.blocks * args.shards * len(LENS) * args.moe.top_k
    assert got["moe.zero_picks"] + got["moe.routed_slots"] == picks
    assert got["moe.zero_picks"] == sum(
        int((np.asarray(s) >= args.moe.n_experts).sum())
        for s in want["sel"])
    assert got["moe.dropped_slots"] == 0
    assert got["moe.capacity_slots"] == args.blocks * 4 * 8 * 4
    m = args.mla
    assert got["scmoe.cache_bytes"] == 4 * 4 * (
        (m.pool_pages + m.batch) * m.width * m.page)
    held = 3 * 2 * 32 * 24 * 4  # a shard's experts a block, float32
    assert got["scmoe.weight_bytes"] > args.blocks * held


# -- zero experts and a layer among several (models/moe.py) ---------------------

def layer_on(shards, held, x, w, zero=4, capacity=6.0, names=None, bias=None):
    """The expert layer alone over tokens ``x (T, d)`` on ``shards`` shards
    of ``held`` experts each, weights ``w`` (global: Wg, W1, W3, W2): its
    output, and its buffers."""
    names = names or LayerNames()
    args = MoEArgs(
        n_ep=shards, tokens_per_shard=x.shape[0] // shards,
        d_model=x.shape[1], d_ff=w["W1"].shape[2], n_chunks=1,
        dtype="float32", experts_per_shard=held, top_k=3, gated=True,
        capacity_factor=capacity, scoring="softmax", routed_scale=6.0,
        zero_experts=zero, gate_in_iteration=True)
    mesh = Mesh(np.array(jax.devices()[:shards]), (AXIS,))
    layout = moe_mod.buffer_layout(args, 1, names, router_dtype="float32")
    data = {names.buf(k): jax.device_put(
        v, NamedSharding(mesh, layout[names.buf(k)][2]))
        for k, v in {"X": x, **w}.items()}
    if bias is not None:
        data[names.buf("gate_bias")] = jnp.asarray(bias)
    bufs, specs = moe_mod.mesh_moe_buffers(args, mesh, data, names)
    layer = MoELayer(args, names.op("moe"), names=names)
    g = Graph()
    g.start_then(layer)
    g.then_finish(layer)
    plat = Platform.make_n_lanes(1, mesh=mesh, specs=specs)
    st = State(g)
    while not st.is_terminal():
        st = st.apply(st.get_decisions(plat)[0])
    out = TraceExecutor(plat, bufs).run(st.sequence)
    return np.asarray(out[names.buf("Y")]), bufs, args


def layer_weights(d=32, f=24, experts=8, zero=4, seed=1):
    rng = np.random.default_rng(seed)
    wg = np.linalg.qr(rng.standard_normal((d, d)))[0][:, :experts + zero]
    return {"Wg": wg.astype(np.float32),
            "W1": (rng.standard_normal((experts, d, f)) / np.sqrt(d)
                   ).astype(np.float32),
            "W3": (rng.standard_normal((experts, d, f)) / np.sqrt(d)
                   ).astype(np.float32),
            "W2": (rng.standard_normal((experts, f, d)) / np.sqrt(f)
                   ).astype(np.float32)}


def test_four_shares_add_up_to_the_layer_on_one_device():
    """The EP 4 layer (two experts a shard, the exchange between) against
    the same layer on one device with all eight experts: the same output,
    and both the plain reference's."""
    w = layer_weights()
    x = np.random.default_rng(2).standard_normal((16, 32)).astype(np.float32)
    four, _, args = layer_on(4, 2, x, w)
    one, _, _ = layer_on(1, 8, x, w, names=LayerNames("B7.moe", "m", "s"))
    assert np.allclose(four, one, rtol=1e-5, atol=1e-6)
    sc = dataclasses.replace(toy_args(), moe=args)
    with jax.default_matmul_precision("highest"):
        m = jnp.asarray(x)
        sel, wts = ref.select(sc, {"B0.moe.Wg": w["Wg"]}, 0, m)
        want = ref.experts(sc, {f"B0.moe.{k}": v for k, v in w.items()}, 0,
                           m, sel, wts)
    assert np.allclose(four, np.asarray(want), rtol=2e-5, atol=2e-6)


def test_a_token_of_zero_picks_only_and_one_of_none():
    """Token 0 lies along the zero experts' router columns (orthonormal):
    its three picks are all identity experts, it holds no slot, and its
    output is itself times the sum of its weights.  Token 1 lies along
    three real experts': no zero term.  No pick at or above ``n_experts``
    ever holds a slot."""
    w = layer_weights()
    x = np.random.default_rng(4).standard_normal((16, 32)).astype(np.float32)
    x[0] = 5.0 * w["Wg"][:, 8:12].sum(axis=1)
    x[1] = 5.0 * w["Wg"][:, [0, 3, 6]].sum(axis=1)
    y, bufs, args = layer_on(4, 2, x, w)
    topk = np.asarray(bufs["topk_0"])
    assert (topk[0] >= 8).all() and (topk[1] < 8).all()
    assert sorted(topk[1]) == [0, 3, 6]
    slot_tk = np.asarray(bufs["slot_tk_0"])  # (shard, peer, slots)
    for shard in range(4):
        held = slot_tk[shard][slot_tk[shard] >= 0]
        picks = topk[shard * 4:(shard + 1) * 4].reshape(-1)[held]
        assert (picks < 8).all()
    assert not (slot_tk[0][slot_tk[0] >= 0] // 3 == 0).any()  # token 0: none
    p = np.exp(x[0] @ w["Wg"])
    p /= p.sum()
    assert np.allclose(y[0], 6.0 * np.sort(p)[-3:].sum() * x[0], rtol=1e-5)
    n_real = int((topk < 8).sum())
    assert int((slot_tk >= 0).sum()) == n_real


def test_a_selection_beyond_capacity_raises():
    w = layer_weights()
    x = np.tile(5.0 * w["Wg"][:, [0, 3, 6]].sum(axis=1), (16, 1)).astype(
        np.float32)
    with pytest.raises(ValueError, match="beyond the capacity"):
        layer_on(4, 2, x, w, capacity=2.0)  # 2 slots, 4 tokens a shard


def _slot_tables_before(sel, args, cap):
    """``slot_tables`` as it stood before zero experts (PR 45's)."""
    k, tc, n_e = args.top_k, args.chunk_tokens, args.n_experts
    a = jnp.arange(tc * k, dtype=jnp.int32)
    out = {}
    for c in range(args.n_chunks):
        topk = sel[c * tc:(c + 1) * tc]
        e = topk.reshape(-1)
        onehot = (e[:, None] == jnp.arange(n_e, dtype=e.dtype)[None, :])
        rank = jnp.take_along_axis(
            jnp.cumsum(onehot.astype(jnp.int32), axis=0), e[:, None],
            axis=1)[:, 0] - 1
        slot = jnp.where(rank < cap, e * cap + rank, n_e * cap)
        shape = (1, args.n_ep, args.experts_per_shard * cap)
        out[f"disp_idx_{c}"] = jnp.zeros((n_e * cap,), jnp.int32).at[
            slot].set(a // k, mode="drop").reshape(shape)
        out[f"slot_tk_{c}"] = jnp.full((n_e * cap,), -1, jnp.int32).at[
            slot].set(a, mode="drop").reshape(shape)
        out[f"comb_idx_{c}"] = jnp.minimum(slot, n_e * cap - 1).reshape(tc, k)
        out[f"topk_{c}"] = topk
    return out


def test_without_zero_experts_the_layer_is_as_it_was():
    """``zero_experts=0`` (``moonlight-ep4``'s layer at toy widths): the
    slot tables bit for bit those of the function as it stood, the buffer
    names and the vertex names unchanged, no ``zero_w``, and a tag moves
    names and nothing else."""
    args = MoEArgs(n_ep=4, tokens_per_shard=32, d_model=16, d_ff=24,
                   n_chunks=2, dtype="float32", experts_per_shard=2, top_k=2,
                   gated=True, shared_ff=40, capacity_factor=2.5,
                   scoring="sigmoid", routed_scale=2.446)
    assert args.gate_in_iteration and args.n_router == 8
    assert args.fixed_capacity() == 10  # ceil(2.5 x 16 tokens x 2 / 8)
    sel = jnp.asarray(np.random.default_rng(0).integers(0, 8, (32, 2)),
                      jnp.int32)
    now = moe_mod.slot_tables(sel, args, 10)
    for name, was in _slot_tables_before(sel, args, 10).items():
        assert np.array_equal(np.asarray(now[name]), np.asarray(was)), name
    names = set(moe_mod.buffer_layout(args, 10))
    assert {"X", "Y", "Wg", "W1", "W3", "W2", "Ws1", "Ws3", "Ws2",
            "send_disp_0", "recv_comb_1", "disp_w_0", "slot_tk_1", "topk_0",
            "shared_out_1", "Y_0"} <= names
    assert not any(n.startswith("zero_w") for n in names)
    ops = {v.name() for v in MoELayer(args).graph().vertices()}
    assert {"pack_0", "a2a_disp_1", "await_comb_0", "gate_1", "shared_0",
            "ffn_1", "combine_0", "moe_concat"} <= ops
    tagged = LayerNames("L3", x="in", y="out")
    assert moe_mod.buffer_layout(args, 10, tagged)["L3.W1"] == \
        moe_mod.buffer_layout(args, 10)["W1"]
    # the softmax default still fixes its weights at set-up
    assert not MoEArgs(n_ep=2).gate_in_iteration
    with pytest.raises(ValueError, match="zero experts"):
        MoEArgs(n_ep=2, zero_experts=2)


# -- the pieces ------------------------------------------------------------------

def test_yarn_frequencies_and_the_softmax_scale():
    """ISSUE 46's rotary: ``f_j (1 - r_j + r_j / 10)`` with the ramp between
    ``floor(c(32))`` and ``ceil(c(1))``; the pairs rotate as the plain
    reference's loop."""
    import math

    f = sm.rope_frequencies(64, 5e6, 10.0, 32768, 32.0, 1.0)
    c = lambda beta: 64 * math.log(32768 / (2 * math.pi * beta)) / (
        2 * math.log(5e6))
    lo, hi = math.floor(c(32)), math.ceil(c(1))
    assert (lo, hi) == (10, 18)
    plain = 5e6 ** (-2.0 * np.arange(32) / 64)
    assert np.allclose(f[:lo + 1], plain[:lo + 1], rtol=1e-6)
    assert np.allclose(f[hi:], plain[hi:] / 10, rtol=1e-6)
    assert np.all(np.diff(f) < 0)
    assert abs(192 ** -0.5 * (0.1 * math.log(10) + 1) ** 2 - 0.109230) < 1e-6
    x = np.random.default_rng(0).standard_normal((3, 2, 64)).astype(
        np.float32)
    pos = np.asarray([0.0, 7.0, 48000.0], np.float32)
    got = sm.rotate_pairs(jnp.asarray(x), jnp.asarray(pos), jnp.asarray(f))
    for b in range(3):
        want = ref._rotate(jnp.asarray(x[b]), jnp.float32(pos[b]),
                           jnp.asarray(f))
        assert np.allclose(np.asarray(got[b]), np.asarray(want), atol=1e-6)
    assert np.array_equal(np.asarray(got[0]), x[0])  # position 0: no turn


def test_latent_buffers_on_a_mesh():
    """``latent_attention``'s shapes, specs and tables with ``shards``: the
    one-chip ones a shard, one after another; the one-chip call unchanged."""
    m = toy_args().mla
    one = la.buffer_shapes(m, ["L0"])
    four = la.buffer_shapes(m, ["L0"], 4)
    specs = la.mesh_specs(m, ["L0"], "ep")
    for name, (shape, dtype) in one.items():
        whole = name.split(".")[0] in ("W_UK", "W_UV")
        assert four[name] == ((shape if whole else (4 * shape[0],)
                               + tuple(shape[1:])), dtype)
        assert tuple(specs[name]) == ((None,) * 0 if whole else
                                      ("ep",) + (None,) * (len(shape) - 1))
    table = la.block_table(m, 7, 4)
    assert table.shape == (4 * m.batch, m.max_pages)
    assert np.array_equal(table[:m.batch], la.block_table(m, 7))
    assert table.max() < m.pool_pages  # each shard's into its own pool
    bufs = la.make_decode_buffers(m, ["L0"], 1, 7, shards=4)
    assert np.array_equal(bufs["lens"], np.tile(np.asarray(m.visible), 4))


CATALOG = {  # the catalog row's ``config`` (model-configs guide), as published
    "attention_bias": False, "vocab_size": 131072, "hidden_size": 3072,
    "ffn_hidden_size": 6144, "expert_ffn_hidden_size": 1024, "num_layers": 14,
    "num_attention_heads": 32, "kv_lora_rank": 512, "q_lora_rank": 1536,
    "qk_rope_head_dim": 64, "v_head_dim": 128, "qk_nope_head_dim": 128,
    "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
    "routed_scaling_factor": 6, "n_routed_experts": 256,
    "rms_norm_eps": 1e-05, "rope_theta": 5000000,
    "max_position_embeddings": 327680,
    "rope_scaling": {"original_max_position_embeddings": 32768,
                     "rope_type": "yarn", "factor": 10, "beta_fast": 32,
                     "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1},
    "zero_expert_num": 128, "zero_expert_type": "identity", "moe_topk": 12,
    "ngram_vocab_size_ratio": 78, "emb_neighbor_num": 4, "emb_split_num": 4}


@pytest.mark.parametrize("key", sorted(CATALOG))
def test_configuration_holds_the_catalog_row(key):
    cfg = json.loads((ROOT / "benchmarks/configs/"
                      "longcat-lite-scmoe-decode.json").read_text())
    assert cfg[key] == CATALOG[key]


def test_configuration_is_cut_as_the_issue_says():
    cfg = json.loads((ROOT / "benchmarks/configs/"
                      "longcat-lite-scmoe-decode.json").read_text())
    kimi = json.loads((ROOT / "benchmarks/configs/"
                       "kimi-linear-kda-decode.json").read_text())
    for key in ("num_attention_heads", "kv_lora_rank", "qk_rope_head_dim",
                "qk_nope_head_dim", "v_head_dim"):
        assert cfg[key] == kimi[key]  # the attention shared to the number
    assert cfg["reduced"] == ["layers"] and cfg["layers"] == 2
    s = cfg["shapes"]
    assert s["ranks"] * s["experts_per_shard"] == cfg["n_routed_experts"]
    lens = s["lens"]
    assert len(lens) == 64 and lens == sorted(lens)
    assert not any(n % s["page_tokens"] == 0 for n in lens)
    assert 512 <= lens[0] and lens[59] <= 8193 and lens[60] == 12000 \
        and lens[-1] == 48000
    assert 0.26e6 < sum(lens) < 0.28e6
    for key in ("deployment", "assumed", "guarantees", "rehearse",
                "reduced_why"):
        assert cfg[key]
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"]
                if w["name"] == "longcat-lite-scmoe-decode.climb")
    assert cell["chips"] == 4 and cell["traffic"] == "climb"


def test_the_one_shot_program_can_be_the_timed_loop_run_once(step):
    """``TraceExecutor(one_shot_as_loop=True)``: ``run`` hands back what the
    straight-line program hands back (float32 toy: to rounding), through the
    repeat-n loop's body, so the timed program after n repeats and after
    none on its outputs leave the same fence, bit for bit."""
    args, graph, plat, ex, want, _ = step
    seq = schedule(step, "start")
    looped = TraceExecutor(plat, ex.init_bufs, one_shot_as_loop=True)
    straight, once = ex.run(seq), looped.run(seq)
    assert set(straight) == set(once)
    for name in (f"h.B{args.blocks}", "s.B0", "Copen.B1.a1", "o_lat.B0.a0"):
        assert np.allclose(np.asarray(once[name]), np.asarray(straight[name]),
                           rtol=1e-5, atol=1e-6)
    looped.prepare_n(seq)(1)
    f = looped._cache["n:" + sequence_to_json_str(seq)]
    after_n = float(f(looped.init_bufs, jnp.int32(3))[0])
    assert after_n == float(f(once, jnp.int32(0))[0])
    # the cache holds it under the one-shot key, as compile() always has
    assert looped.compile(seq) is looped._cache[sequence_to_json_str(seq)]
