"""One packed prefill step through the mixers of a Mamba-2 hybrid's period
(``models/mixers_prefill.py``) against the benchmark's plain reference
(``benchmarks/references/mamba2_mixers_prefill.py``), and what it forced in
the attention: packed prompts in the plan, the folds and the kernels, and a
one-prompt caller's program as it was.  Toy widths, float32."""

import dataclasses
import hashlib
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness.cell import load_module
from tenzing_tpu.core.graph import Graph
from tenzing_tpu.core.platform import Platform
from tenzing_tpu.models import mamba2, mixers_prefill
from tenzing_tpu.models.ring_attention import (
    BlockedAttention,
    RingAttnArgs,
    make_blocked_buffers,
    mask_crosses,
    period_graph,
    tile_plan,
    visible_pairs,
)
from tenzing_tpu.obs.metrics import MetricsRegistry, set_metrics
from tenzing_tpu.runtime.executor import TraceExecutor
from tenzing_tpu.verify.soundness import ScheduleVerifier

from test_mamba2 import drive

ref = load_module("references", "mamba2_mixers_prefill")

LENS = (13, 11, 3, 5)
T = sum(LENS)
PATTERN = "MMM*"
MAMBA = mamba2.Mamba2Args(lens=LENS, heads=4, head_dim=8, groups=2, state=16,
                          taps=4, chunk=8, dtype="float32")
ATTN = RingAttnArgs(n_devices=T // 8, batch=1, seq_local=8, head_dim=8,
                    dtype="float32", heads=4, kv_heads=2, causal=True,
                    q_block=16, segments=MAMBA.starts)
Z = {"lens": LENS, "heads": 4, "head_dim": 8, "groups": 2, "state": 16,
     "taps": 4, "eps": MAMBA.eps}
NAIVE = (".chain", ".pallas")
START = (".fused", ".pallas")


def step(seed=1):
    """``(graph, buffers, the reference's compared buffers)`` of the toy
    period."""
    kinds = mixers_prefill.layer_tags(PATTERN)
    m_tags = [t for k, t in kinds if k == "M"]
    a_tag = next(t for k, t in kinds if k == "*")
    bufs = mamba2.make_mamba2_buffers(MAMBA, m_tags, seed)
    attn, _ = make_blocked_buffers(ATTN, seed + 1, a_tag)
    bufs.update(attn)
    assert set(bufs) == set(mixers_prefill.buffer_shapes(MAMBA, ATTN,
                                                         PATTERN))
    want = {}
    with jax.default_matmul_precision("highest"):
        for tag in m_tags:
            p = {k: jnp.asarray(bufs[f"{k}.{tag}"], jnp.float32)
                 for k in mamba2.PARAMS}
            got = ref.mamba_mixer(
                Z, *(bufs[f"{k}.{tag}"] for k in mamba2.INPUTS), p)
            want.update(zip((f"out.{tag}", f"Sfin.{tag}", f"tail.{tag}"),
                            got))
        want[f"O.{a_tag}"] = ref.attention(
            *(jnp.asarray(bufs[f"{k}.{a_tag}"]) for k in "QKV"),
            jnp.asarray(bufs["seg"]))
    return (mixers_prefill.mixers_prefill_graph(MAMBA, ATTN, PATTERN),
            {k: jnp.asarray(v) for k, v in bufs.items()}, want)


@pytest.fixture(scope="module")
def period():
    return step()


@pytest.mark.parametrize("which", ["naive", "start", "walk0", "walk1"])
def test_period_against_the_reference(period, which):
    """Every Mamba-2 layer's ``out``, final states and tails and the
    attention's ``O``: naive, the start point and two random walks of the
    search's space (which may take a bfloat16-input kernel: looser)."""
    g, bufs, want = period
    plat = Platform.make_n_lanes(1 if which == "naive" else 2)
    walk = which.startswith("walk")
    seq = (drive(g, plat, rng=random.Random(int(which[-1]))) if walk
           else drive(g, plat, NAIVE if which == "naive" else START))
    assert ScheduleVerifier(g)(seq).ok
    out = TraceExecutor(plat, bufs).run(seq)
    tol = dict(rtol=2e-2, atol=2e-2) if walk else dict(rtol=2e-4, atol=2e-4)
    for name, ref_ in want.items():
        np.testing.assert_allclose(out[name], ref_, err_msg=name, **tol)


@pytest.mark.parametrize("which", ["naive", "start"])
def test_vertices_carry_layer_and_part(period, which):
    g, _, _ = period
    plat = Platform.make_n_lanes(1 if which == "naive" else 2)
    names = [op.name() for op in drive(
        g, plat, NAIVE if which == "naive" else START)]
    for want in ("L0.M.conv", "L1.M.gated_norm", "L2.M.conv"):
        assert want in names, want
    if which == "start":
        assert {"L0.M.ssd.fused", "L2.M.ssd.fused",
                "L3.A.q0.attn_blocks.fused"} <= set(names)
    else:
        assert {"L1.M.ssd_diag", "L1.M.ssd_carry",
                "L3.A.q1.attn_finalize"} <= set(names)
    # a layer's first vertex comes after the layer before's last
    first = {tag: min(i for i, n in enumerate(names) if n.startswith(tag))
             for _, tag in mixers_prefill.layer_tags(PATTERN)}
    last = {tag: max(i for i, n in enumerate(names) if n.startswith(tag))
            for tag in first}
    tags = list(first)
    assert all(last[a] < first[b] for a, b in zip(tags, tags[1:]))


def test_iteration_is_idempotent(period):
    g, bufs, _ = period
    plat = Platform.make_n_lanes(2)
    ex = TraceExecutor(plat, bufs)
    for want in (START, NAIVE):
        seq = drive(g, plat, want)
        once = ex.run(seq)
        twice = ex.compile(seq)(once)
        for name in once:
            assert np.array_equal(np.asarray(once[name]),
                                  np.asarray(twice[name]),
                                  equal_nan=True), name


def test_one_step_one_packing():
    with pytest.raises(ValueError, match="one packed step"):
        mixers_prefill.mixers_prefill_graph(
            MAMBA, dataclasses.replace(ATTN, segments=(0, 16)), PATTERN)
    with pytest.raises(ValueError, match="pattern"):
        mixers_prefill.layer_tags("MEM*")
    assert [t for _, t in mixers_prefill.layer_tags(PATTERN)] == [
        "L0.M", "L1.M", "L2.M", "L3.A"]


# -- packed prompts in the attention ---------------------------------------------

def starts_of(lens):
    return tuple(int(s) for s in np.cumsum((0,) + tuple(lens)[:-1]))


def packed_reference(bufs, args, lens):
    """Each prompt attended alone, causal: dense float64."""
    q, k, v = (np.asarray(bufs[t], np.float64) for t in "QKV")
    group = args.heads // args.kv_heads
    out = np.zeros(q.shape)
    for s0, n in zip(starts_of(lens), lens):
        cut = slice(s0, s0 + n)
        kk, vv = (np.repeat(t[:, cut], group, axis=0) for t in (k, v))
        s = np.einsum("hqd,hkd->hqk", q[:, cut], kk) * args.scale
        s = np.where(np.tril(np.ones((n, n), bool)), s, -np.inf)
        p = np.exp(s - s.max(axis=2, keepdims=True))
        out[:, cut] = np.einsum("hqk,hkd->hqd",
                                p / p.sum(axis=2, keepdims=True), vv)
    return out


@pytest.mark.parametrize("want", [(".chain", ".xla"), (".chain", ".pallas"),
                                  (".fused",)],
                         ids=["xla_folds", "kernel_folds", "fused"])
@pytest.mark.parametrize("lens", [(13, 11, 3, 5), (16, 16), (3, 29)])
def test_packed_attention_is_each_prompt_alone(lens, want):
    args = dataclasses.replace(ATTN, segments=starts_of(lens))
    bufs, _ = make_blocked_buffers(args, seed=2)
    g = Graph()
    layer = BlockedAttention(args, impl_choice=True, fused_choice=True)
    g.start_then(layer)
    g.then_finish(layer)
    plat = Platform.make_n_lanes(2)
    out = TraceExecutor(plat, {k: jnp.asarray(v) for k, v in bufs.items()}
                        ).run(drive(g, plat, want))
    np.testing.assert_allclose(out["O"], packed_reference(bufs, args, lens),
                               rtol=2e-4, atol=2e-4)


def test_plan_skips_blocks_of_other_prompts():
    """A K/V block that lies wholly in other prompts than a query block's
    is not in the graph; the counts are a dense mask's."""
    args = dataclasses.replace(ATTN, segments=(0, 16))
    plan = tile_plan(args)
    assert [qb.blocks for qb in plan] == [(0, 1), (2, 3)]
    assert [qb.skipped for qb in plan] == [2, 2]
    i = np.arange(T)
    seg = np.searchsorted(np.asarray(ATTN.segments), i, side="right")
    dense = (i[None, :] <= i[:, None]) & (seg[None, :] == seg[:, None])
    for q0, rows, k0, keys in ((0, 16, 0, 8), (16, 16, 8, 8), (8, 8, 8, 8),
                               (24, 8, 16, 8), (16, 16, 24, 8)):
        block = dense[q0:q0 + rows, k0:k0 + keys]
        assert visible_pairs(ATTN, q0, rows, k0, keys) == int(block.sum())
        assert mask_crosses(ATTN, q0, rows, k0, keys) == (
            not block.all()), (q0, k0)
    with pytest.raises(ValueError, match="segments"):
        RingAttnArgs(n_devices=2, causal=False, segments=(0, 4))
    with pytest.raises(ValueError, match="segments"):
        RingAttnArgs(n_devices=2, causal=True, segments=(4, 2))


def test_pair_counters_count_packed_prompts(period):
    """``attn.pairs_useful`` is the dense packed mask's count over all
    heads, whatever engine; ``attn.pairs_computed`` the kernel's tiles that
    hold a visible key, whole."""
    from tenzing_tpu.ops.attention_pallas import computed_pairs

    # rows 16..31 over keys 16..31 in tiles of 8: the diagonal's two tiles
    # and the lower-left one, unless a prompt starts with the second query
    # tile; a range wholly in another prompt: none
    assert computed_pairs(16, 16, 16, 16, True, None, bq=8, bkv=8,
                          segments=(0, 13, 22, 27)) == 3 * 64
    assert computed_pairs(16, 16, 16, 16, True, None, bq=8, bkv=8,
                          segments=(0, 13, 24, 27)) == 2 * 64
    assert computed_pairs(16, 16, 16, 0, True, None, bq=8, bkv=8,
                          segments=(0, 16)) == 0
    g, bufs, _ = period
    useful = ATTN.heads * sum(n * (n + 1) // 2 for n in LENS)
    for want in (START, NAIVE):
        reg = MetricsRegistry()
        prev = set_metrics(reg)
        try:
            plat = Platform.make_n_lanes(1)
            TraceExecutor(plat, bufs).run(drive(g, plat, want))
            assert reg.counter("attn.pairs_useful").value == useful
            assert reg.counter("attn.pairs_computed").value >= useful
        finally:
            set_metrics(prev)


# -- a one-prompt caller traces what it traced --------------------------------------

def one_prompt_jaxpr() -> str:
    """The traced program of a toy Trinity period's start point (a window
    layer and a full layer of grouped heads in query blocks, every block on
    the fused kernel): no ``segments``."""
    full = RingAttnArgs(n_devices=4, batch=1, seq_local=8, head_dim=8,
                        dtype="float32", heads=4, kv_heads=2, causal=True,
                        q_block=16)
    layers = [("L0", dataclasses.replace(full, window=12)), ("L1", full)]
    g = period_graph(layers, impl_choice=True, fused_choice=True)
    bufs = {}
    for tag, a in layers:
        bufs.update(make_blocked_buffers(a, seed=3, layer=tag)[0])
    plat = Platform.make_n_lanes(2)
    ex = TraceExecutor(plat, {k: jnp.asarray(v) for k, v in bufs.items()})
    return str(jax.make_jaxpr(ex.program(drive(g, plat, START)))(
        ex.init_bufs))


def test_segments_none_traces_the_program_it_traced():
    """``RingAttnArgs.segments`` left at ``None``: the traced program of a
    one-prompt period is the parent commit's to the letter (the digest is
    read from the commit before ISSUE 50)."""
    assert hashlib.sha256(one_prompt_jaxpr().encode()).hexdigest() == (
        "5bbf80f17976532d085b8b1840d266ec15a72e892e4a974018051558836a4fa0")
