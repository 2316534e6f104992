"""One decode step of Kimi Delta Attention on a recurrent state
(models/delta_attention.py, ops/kda_pallas.py) and the hybrid period with
the latent-attention layer, against the plain references
(models/delta_attention_reference.py, models/latent_attention_reference.py),
at toy widths on the CPU (Pallas in interpret mode).

Four sequences in two groups, two heads of 16 channels, four taps; the
hybrid period is three KDA layers and one MLA layer over eight sequences.
"""

import dataclasses
import json
import os
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tenzing_tpu.core.platform import Platform
from tenzing_tpu.core.state import ChooseOp, State
from tenzing_tpu.models import delta_attention_reference as kda_ref
from tenzing_tpu.models.delta_attention import (
    CHAIN_PASSES,
    DECAY_RANGE,
    FUSED_PASSES,
    DeltaDecodeArgs,
    KdaEngineChoice,
    KdaFused,
    buffer_shapes,
    hybrid_decode_graph,
    kda_graph,
    make_kda_buffers,
)
from tenzing_tpu.models.latent_attention import (
    LatentDecodeArgs,
    decode_graph,
    dense_caches,
    make_decode_buffers,
)
from tenzing_tpu.models.latent_attention_reference import published
from tenzing_tpu.obs.metrics import MetricsRegistry, set_metrics
from tenzing_tpu.ops.kda_pallas import (
    conv_step,
    gates,
    kda_step_pallas,
    out_norm,
    state_step,
)
from tenzing_tpu.runtime.executor import TraceExecutor
from tenzing_tpu.verify.soundness import ScheduleVerifier

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARGS = DeltaDecodeArgs(batch=4, heads=2, d=16, taps=4, groups=2,
                       dtype="float32")
KDA_LAYERS = ("L0", "L1")
LENS = (3, 9, 13, 17, 26, 31, 44, 61)
MLA = LatentDecodeArgs(lens=LENS, heads=4, rank=16, rope=8, nope=8, v_dim=8,
                       scale=16 ** -0.5, page=8, groups=4, fold_pages=2,
                       dtype="float32")
HYBRID = dataclasses.replace(ARGS, batch=len(LENS))
PATTERN = (("kda", "L0"), ("kda", "L1"), ("kda", "L2"), ("mla", "L3"))
ENGINES = {"fused": (".fused",), "chain": (".chain", ".pallas")}
INPUTS = ("x", "f", "b", "go")
PARAMS = ("Wc", "dt_bias", "A_log", "w_norm")
#: float32 rounding of a step: some hundred sums a value
TOL = dict(rtol=2e-5, atol=2e-6)
#: the hybrid period's limits at toy widths in float32: a sound schedule
#: stays under them by an order of magnitude, the state carried in bfloat16
#: (2^-9 a value) passes the first by as much
STATE_RMS_LIMIT = 1e-5
O_RMS_LIMIT = 1e-4


def drive(graph, plat, want=(), rng=None):
    """The schedule of taking, at every menu, the first entry that ends in
    one of ``want`` (``rng``: any decision offered, a random walk of the
    search's space), and else the first decision offered."""
    st = State(graph)
    while not st.is_terminal():
        ds = st.get_decisions(plat)
        pick = rng.choice(ds) if rng else None
        for w in want:
            pick = pick or next(
                (d for d in ds if isinstance(d, ChooseOp)
                 and d.choice.name().endswith(w)), None)
        st = st.apply(pick or ds[0])
    return st.sequence


def step(args=ARGS, layers=KDA_LAYERS, seed=3, lanes=2, **kw):
    bufs = make_kda_buffers(args, layers, seed, **kw)
    g = kda_graph(args, layers)
    plat = Platform.make_n_lanes(lanes)
    ex = TraceExecutor(plat, {k: jnp.asarray(v) for k, v in bufs.items()})
    return g, plat, ex, bufs


def one_step_reference(bufs, layer, state="S", window="Cv", carry=None):
    """``(o, Snew, Cvnew)`` of one layer by the plain reference's one token,
    a sequence at a time.  ``carry``: the dtype the state is carried in
    (the low-precision control)."""
    n = lambda k: np.asarray(bufs[f"{k}.{layer}"], np.float32)
    s = n(state)
    if carry is not None:
        s = np.asarray(jnp.asarray(s).astype(carry).astype(jnp.float32))
    outs = [kda_ref.one_token(
        s[b], n(window)[b], *(n(k)[b] for k in INPUTS),
        *(n(k) for k in PARAMS)) for b in range(s.shape[0])]
    o, snew, cvnew = (np.stack([np.asarray(x[i]) for x in outs])
                      for i in range(3))
    if carry is not None:
        snew = np.asarray(jnp.asarray(snew).astype(carry).astype(jnp.float32))
    return o, snew, cvnew


def rms_gap(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(((got - want) ** 2).sum() / (want ** 2).sum()))


# -- (a) one step of either engine against the reference's one token ----------

@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("engine", list(ENGINES))
def test_a_step_of_either_engine_is_the_reference_s_token(engine, seed):
    g, plat, ex, bufs = step(seed=seed)
    seq = drive(g, plat, ENGINES[engine])
    assert ScheduleVerifier(g)(seq).ok
    names = [op.name() for op in seq]
    assert sum(n.endswith(".kda" + ENGINES[engine][0]) for n in names) == (
        len(KDA_LAYERS) * ARGS.groups if engine == "fused" else 0)
    assert sum(n.endswith(".kda_state") for n in names) == (
        0 if engine == "fused" else len(KDA_LAYERS) * ARGS.groups)
    out = ex.run(seq)
    for layer in KDA_LAYERS:
        o, snew, cvnew = one_step_reference(bufs, layer)
        np.testing.assert_allclose(np.asarray(out[f"o.{layer}"]), o, **TOL)
        np.testing.assert_allclose(np.asarray(out[f"Snew.{layer}"]), snew,
                                   **TOL)
        # the window moves on by one: a copy, exact
        assert np.array_equal(np.asarray(out[f"Cvnew.{layer}"]), cvnew)
        # the state read is not written
        assert np.array_equal(np.asarray(out[f"S.{layer}"]),
                              bufs[f"S.{layer}"])


def test_the_kernel_writes_its_own_rows_and_no_other():
    bufs = {k: jnp.asarray(v) for k, v in make_kda_buffers(
        ARGS, ("L0",), 5).items()}
    marked = dict(bufs)
    for k in KdaFused.WRITES:
        marked[f"{k}.L0"] = jnp.full_like(bufs[f"{k}.L0"], 7.0)
    out = kda_step_pallas(
        *(marked[f"{k}.L0"] for k in KdaFused.READS + KdaFused.WRITES),
        lead0=2, rows=2, interpret=True)
    o, snew, cvnew = one_step_reference(bufs, "L0")
    for got, want in zip(out, (snew, cvnew, o)):
        assert np.all(np.asarray(got[:2]) == 7.0)
        np.testing.assert_allclose(np.asarray(got[2:]), want[2:], **TOL)
    with pytest.raises(ValueError, match="multiple of 16"):
        kda_step_pallas(
            *(bufs[f"{k}.L0"] for k in KdaFused.READS + KdaFused.WRITES),
            lead0=0, rows=2, head_block=1, interpret=True)


def test_the_two_engines_share_three_of_the_four_steps():
    """Steps 1, 2 and 4 are the same functions in the kernel's body and in
    the chain's vertices; the chain's step 3 is the kernel's walk over its
    heads, vectorised."""
    bufs = make_kda_buffers(ARGS, ("L0",), 2)
    n = lambda k: jnp.asarray(bufs[f"{k}.L0"])
    y, moved = conv_step(n("x"), n("Cv"), n("Wc"))
    q, k, v, decay, beta = gates(y, n("f"), n("dt_bias"), n("A_log"), n("b"))
    snew, o = state_step(n("S"), q, k, v, decay, beta)
    o = out_norm(o, n("go"), n("w_norm"), ARGS.eps)
    want = one_step_reference(bufs, "L0")
    for got, ref in zip((o, snew, moved), want):
        np.testing.assert_allclose(np.asarray(got), ref, **TOL)
    # the draws keep the decay in the range the configuration wants: all
    # but the tails of f
    lo, hi = DECAY_RANGE
    inside = (np.asarray(decay) > 0.5 * lo) & (np.asarray(decay) < 1.0)
    assert inside.all()
    assert np.mean((np.asarray(decay) >= lo) & (np.asarray(decay) <= hi)) > .9


# -- (b) the recurrent and the convolution state, held to the model -----------

@pytest.mark.parametrize("engine", list(ENGINES))
def test_steps_from_a_zero_state_are_the_whole_sequence_forward(engine):
    """T steps of the system's step from a zero state and an empty window,
    ``Snew`` and ``Cvnew`` fed back by hand, equal the reference's forward
    over the whole sequence (left-padded convolution, recurrence from zero)
    at every position."""
    T, layer = 7, "L0"
    g, plat, ex, bufs = step(layers=(layer,), seed=4, zero_state=True)
    bufs[f"Cv.{layer}"] = np.zeros_like(bufs[f"Cv.{layer}"])
    rng = np.random.default_rng(9)
    tokens = {k: rng.standard_normal((T,) + bufs[f"{k}.{layer}"].shape)
              .astype(np.float32) * (0.5 if k == "f" else 1.0)
              for k in INPUTS}
    f = ex.compile(drive(g, plat, ENGINES[engine]))
    now = {k: jnp.asarray(v) for k, v in bufs.items()}
    got = []
    for t in range(T):
        now.update({f"{k}.{layer}": jnp.asarray(tokens[k][t])
                    for k in INPUTS})
        out = f(now)
        got.append(tuple(np.asarray(out[f"{k}.{layer}"])
                         for k in ("o", "Snew", "Cvnew")))
        now = dict(out)
        now[f"S.{layer}"] = out[f"Snew.{layer}"]
        now[f"Cv.{layer}"] = out[f"Cvnew.{layer}"]
    for b in range(ARGS.batch):
        o, states, windows = kda_ref.forward(
            *(tokens[k][:, b] for k in INPUTS),
            *(bufs[f"{k}.{layer}"] for k in PARAMS))
        for t in range(T):
            np.testing.assert_allclose(got[t][0][b], o[t], **TOL)
            np.testing.assert_allclose(got[t][1][b], states[t], **TOL)
            assert np.array_equal(got[t][2][b], windows[t])


# -- (c) the hybrid period through the executor -------------------------------

def hybrid(seed=6, table_seed=11, lanes=2):
    kda_tags = [t for k, t in PATTERN if k == "kda"]
    mla_tags = [t for k, t in PATTERN if k == "mla"]
    bufs = make_kda_buffers(HYBRID, kda_tags, seed)
    bufs.update(make_decode_buffers(MLA, mla_tags, seed, table_seed))
    g = hybrid_decode_graph(HYBRID, MLA, PATTERN, impl_choice=True)
    plat = Platform.make_n_lanes(lanes)
    ex = TraceExecutor(plat, {k: jnp.asarray(v) for k, v in bufs.items()})
    return g, plat, ex, bufs


def hybrid_reference(bufs, carry=None):
    """``{name: array}`` of the composed reference: the KDA layers' ``o``,
    ``Snew`` and ``Cvnew``, the MLA layer's ``o``."""
    want = {}
    for kind, tag in PATTERN:
        if kind == "kda":
            o, snew, cvnew = one_step_reference(bufs, tag, carry=carry)
            want.update({f"o.{tag}": o, f"Snew.{tag}": snew,
                         f"Cvnew.{tag}": cvnew})
        else:
            rows = []
            for b, cache in enumerate(dense_caches(MLA, bufs, tag)):
                new = np.concatenate([bufs[f"c_new.{tag}"][b],
                                      bufs[f"kr_new.{tag}"][b]])[None]
                rows.append(published(
                    np.concatenate([cache, new]), bufs[f"q_nope.{tag}"][b],
                    bufs[f"q_rope.{tag}"][b], bufs[f"W_UK.{tag}"],
                    bufs[f"W_UV.{tag}"], MLA.scale))
            want[f"o.{tag}"] = np.asarray(jnp.stack(rows))
    return want


def hybrid_gaps(out, want):
    """The worst layer's gap of the states and of the outputs."""
    return (max(rms_gap(out[k], v) for k, v in want.items()
                if k.startswith("Snew.")),
            max(rms_gap(out[k], v) for k, v in want.items()
                if k.startswith("o.")))


@pytest.mark.parametrize("which", ["naive", "start", "walk0", "walk1"])
def test_hybrid_period_matches_the_composed_reference(which):
    g, plat, ex, bufs = hybrid()
    if which == "naive":
        seq = drive(g, Platform.make_n_lanes(1), (".chain", ".pallas"))
    elif which == "start":
        seq = drive(g, plat, (".fused",))
    else:
        seq = drive(g, plat, rng=random.Random(int(which[-1])))
    assert ScheduleVerifier(g)(seq).ok
    names = [op.name() for op in seq]
    if which.startswith("walk"):  # both engines in one schedule
        assert any(n.endswith(".kda.fused") for n in names)
        assert any(n.endswith(".kda_state") for n in names)
    # the layers in the residual stream's order
    first = {t: min(i for i, n in enumerate(names) if n.startswith(t + "."))
             for _, t in PATTERN}
    last = {t: max(i for i, n in enumerate(names) if n.startswith(t + "."))
            for _, t in PATTERN}
    tags = [t for _, t in PATTERN]
    assert all(last[a] < first[b] for a, b in zip(tags, tags[1:]))
    out = ex.run(seq)
    want = hybrid_reference(bufs)
    state_gap, o_gap = hybrid_gaps(out, want)
    assert state_gap < STATE_RMS_LIMIT / 10 and o_gap < O_RMS_LIMIT / 10
    for tag in tags[:3]:
        assert np.array_equal(np.asarray(out[f"Cvnew.{tag}"]),
                              want[f"Cvnew.{tag}"])
    # the appended row of the latent cache, exact
    opened = np.array(bufs["Copen.L3"])
    for b, n in enumerate(MLA.lens):
        opened[b, :, n % MLA.page] = np.concatenate(
            [bufs["c_new.L3"][b], bufs["kr_new.L3"][b]])
    assert np.array_equal(np.asarray(out["Copen.L3"]), opened)


def test_a_state_carried_in_bfloat16_fails_the_same_limits():
    _, _, _, bufs = hybrid()
    want = hybrid_reference(bufs)
    state_gap, o_gap = hybrid_gaps(
        hybrid_reference(bufs, carry=jnp.bfloat16), want)
    assert state_gap > 10 * STATE_RMS_LIMIT
    assert o_gap > O_RMS_LIMIT / 10  # the read-out averages the rounding


# -- (d) the latent layer at 32 head rows a sequence --------------------------

@pytest.mark.parametrize("engine", ["fused", "chain", "xla"])
def test_latent_decode_at_32_heads_matches_the_reference(engine):
    args = dataclasses.replace(MLA, heads=32, groups=2)
    bufs = make_decode_buffers(args, ("L0",), 2, 7)
    g = decode_graph(args, ("L0",), impl_choice=True)
    plat = Platform.make_n_lanes(2)
    ex = TraceExecutor(plat, {k: jnp.asarray(v) for k, v in bufs.items()})
    want = {"fused": (".fused",), "chain": (".chain", ".pallas"),
            "xla": (".chain", ".xla")}[engine]
    out = ex.run(drive(g, plat, want))
    for b, cache in enumerate(dense_caches(args, bufs, "L0")):
        new = np.concatenate([bufs["c_new.L0"][b], bufs["kr_new.L0"][b]])
        ref = published(np.concatenate([cache, new[None]]),
                        bufs["q_nope.L0"][b], bufs["q_rope.L0"][b],
                        bufs["W_UK.L0"], bufs["W_UV.L0"], args.scale)
        np.testing.assert_allclose(np.asarray(out["o.L0"][b]), ref,
                                   rtol=2e-4, atol=2e-5)


# -- (e) an iteration is idempotent -------------------------------------------

@pytest.mark.parametrize("which", ["naive", "start", "walk0"])
def test_two_iterations_leave_every_buffer_as_one_leaves_it(which):
    g, plat, ex, _ = hybrid()
    seq = {"naive": lambda: drive(g, plat, (".chain", ".pallas")),
           "start": lambda: drive(g, plat, (".fused",)),
           "walk0": lambda: drive(g, plat, rng=random.Random(0))}[which]()
    once = ex.run(seq)
    twice = ex.compile(seq)(once)
    for name in once:
        assert np.array_equal(np.asarray(once[name]),
                              np.asarray(twice[name])), name


# -- the program's counters and span ------------------------------------------

@pytest.mark.parametrize("engine", list(ENGINES))
def test_counters_equal_a_count_from_the_shapes(engine):
    reg = MetricsRegistry()
    prev = set_metrics(reg)
    try:
        g, plat, ex, _ = step()
        jax.make_jaxpr(ex.program(drive(g, plat, ENGINES[engine])))(
            ex.init_bufs)
    finally:
        set_metrics(prev)
    count = {n: reg.counter("kda." + n).value for n in (
        "rows", "state_bytes", "state_min_bytes", "fused_vertices",
        "chain_vertices")}
    layers, a = len(KDA_LAYERS), ARGS
    state = a.heads * a.d * a.d * 4
    window = 3 * 3 * a.heads * a.d * 4  # float32 at the toy's dtype
    assert (a.state_bytes, a.conv_bytes) == (state, window)
    vertices = layers * a.groups
    assert count["rows"] == layers * a.batch
    assert count["fused_vertices"] == (vertices if engine == "fused" else 0)
    assert count["chain_vertices"] == (0 if engine == "fused" else vertices)
    least = layers * a.batch * (2 * state + 2 * window)
    passes = FUSED_PASSES if engine == "fused" else CHAIN_PASSES
    assert count["state_min_bytes"] == least
    assert count["state_bytes"] == layers * a.batch * (
        passes * state + 2 * window)
    assert dataclasses.replace(a, dtype="bfloat16").conv_bytes == window // 2


def test_making_the_buffers_is_one_span_of_the_program_s_tracing():
    from tenzing_tpu.obs.tracer import Tracer, set_tracer

    tr = Tracer(enabled=True)
    prev = set_tracer(tr)
    try:
        bufs = make_kda_buffers(ARGS, KDA_LAYERS, 0)
    finally:
        set_tracer(prev)
    (span,) = [s for s in tr.spans() if s.name == "kda.make_buffers"]
    assert span.attrs == {"layers": 2, "batch": 4}
    shapes = buffer_shapes(ARGS, KDA_LAYERS)
    assert set(bufs) == set(shapes)
    assert shapes["S.L1"] == ((4, 2, 16, 16), "float32")
    assert shapes["Cv.L0"] == ((4, 3, 3, 2, 16), "float32")
    menu = KdaEngineChoice(ARGS, 1, "L0")
    assert [c.name() for c in menu.choices()] == [
        "L0.g1.kda.chain", "L0.g1.kda.fused"]
    with pytest.raises(ValueError, match="groups"):
        DeltaDecodeArgs(batch=5, groups=2)


# -- (f) the configuration is the catalog's row -------------------------------

CATALOG_ROW = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                       21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576,
    "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8,
    "num_hidden_layers": 27, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
    "vocab_size": 163840,
}


def _config(name):
    with open(os.path.join(REPO, "benchmarks", "configs",
                           name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("key", sorted(CATALOG_ROW))
def test_the_configuration_holds_the_catalog_s_key(key):
    """Every key of ``Kimi-Linear-48B-A3B-Instruct``'s row in the
    ``model-configs`` catalog, as published (copied here: the tests read
    nothing outside the repository)."""
    assert _config("kimi-linear-kda-decode")[key] == CATALOG_ROW[key]


def test_the_configuration_is_one_period_on_the_shared_cache():
    c = _config("kimi-linear-kda-decode")
    assert c["reduced"] == ["layers"] and c["layers"] == 4
    assert c["pattern"] == ["kda", "kda", "kda", "mla"]
    lin = c["linear_attn_config"]
    assert [(i in lin["kda_layers"], i in lin["full_attn_layers"])
            for i in (1, 2, 3, 4)] == [(True, False)] * 3 + [(False, True)]
    # the latent layer is dsv3-mla-decode's cache to the column
    shared = _config("dsv3-mla-decode")
    for key in ("kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                "v_head_dim", "hidden_act", "rope_theta"):
        assert c[key] == shared[key], key
    assert c["shapes"]["dtype"] == shared["shapes"]["dtype"] == "bfloat16"
    lens = c["shapes"]["lens"]
    page = c["shapes"]["page_tokens"]
    assert lens == sorted(lens) and len(lens) in (64, 128)
    assert not any(n % page == 0 for n in lens)
    assert len(lens) % c["shapes"]["groups"] == 0
    assert len(lens) % c["shapes"]["kda_groups"] == 0
    if len(lens) == 128:
        assert 0.95e6 <= sum(lens) <= 1.05e6
        assert 1100 <= lens[0] and lens[123] <= 16000
        assert 40000 <= lens[124] and lens[127] <= 131000
