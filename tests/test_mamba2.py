"""The Mamba-2 mixer over packed prompts (``models/mamba2.py``,
``ops/ssd_pallas.py``) against the plain token-by-token recurrence: the
``ssd_scan`` kernel (interpreter) and the four-step XLA chain for prompt
boundaries on, inside and absent from chunks, a prompt shorter than the
convolution, a prompt shorter than a chunk and a last chunk that is not
full; the convolution at a boundary; the final states and convolution tails
a prompt; the head to group mapping; whole layers as a graph, both engines,
against the benchmark's reference; the ``ssd.*`` counters and span.  Toy
widths, float32."""

import json
import os
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness.cell import load_module
from tenzing_tpu.core.graph import Graph
from tenzing_tpu.core.platform import Platform
from tenzing_tpu.core.state import ChooseOp, State
from tenzing_tpu.models import mamba2
from tenzing_tpu.models.mamba2 import Mamba2Args
from tenzing_tpu.obs.metrics import MetricsRegistry, set_metrics
from tenzing_tpu.ops import ssd_pallas
from tenzing_tpu.runtime.executor import TraceExecutor

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ref = load_module("references", "mamba2_mixers_prefill")

H, P, G, N, Q = 4, 8, 2, 16, 8
DIMS = dict(heads=H, head_dim=P, groups=G, state=N, chunk=Q)
#: packings of a step, chunks of 8: what each exercises
PACKINGS = {
    "boundaries_on_chunk_edges": (16, 8, 8),
    "boundaries_inside_chunks": (13, 9, 5, 5),
    "prompts_shorter_than_a_chunk": (3, 2, 1, 20, 6),
    "last_chunk_not_full": (11, 7),
    "one_prompt": (24,),
    "two_ends_in_the_last_chunk": (9, 3, 2, 2),
}
ENGINES = {"kernel": ssd_pallas.ssd_chunk_scan, "chain": ssd_pallas.ssd_chain}
TOL = dict(rtol=2e-5, atol=2e-5)


def drive(graph, plat, want=(), rng=None):
    """The schedule of taking, at every menu, the first entry that ends in
    one of ``want`` (``rng``: a random walk), else the first decision."""
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


def scan_inputs(lens, seed=0):
    rng = np.random.default_rng(seed)
    t, inner = sum(lens), H * P
    xc = jnp.asarray(rng.standard_normal((t, inner + 2 * G * N)),
                     jnp.float32)
    dt = jnp.asarray(np.exp(rng.uniform(np.log(0.01), np.log(0.5), (t, H))),
                     jnp.float32)
    a = -jnp.asarray(rng.uniform(1, 16, H), jnp.float32)
    d = jnp.asarray(rng.standard_normal(H), jnp.float32)
    pk = {k: jnp.asarray(v) for k, v in mamba2.packing(lens).items()}
    return xc, dt, a, d, pk["seg"], pk["ends"]


def recurrence(xc, dt, a, d, lens, group_of=lambda h: h // (H // G)):
    """``(y (T, H P), S_final (prompts, H, P, N))`` token by token in
    float64, every head spelled out."""
    xc, dt, a, d = (np.asarray(t, np.float64) for t in (xc, dt, a, d))
    inner = H * P
    x = xc[:, :inner].reshape(-1, H, P)
    b = xc[:, inner:inner + G * N].reshape(-1, G, N)
    c = xc[:, inner + G * N:].reshape(-1, G, N)
    y, fins, at = np.zeros_like(x), [], 0
    for n in lens:
        s = np.zeros((H, P, N))
        for t in range(at, at + n):
            for h in range(H):
                g = group_of(h)
                s[h] = np.exp(dt[t, h] * a[h]) * s[h] + dt[t, h] * np.outer(
                    x[t, h], b[t, g])
                y[t, h] = s[h] @ c[t, g] + d[h] * x[t, h]
        fins.append(s)
        at += n
    return y.reshape(-1, inner), np.stack(fins)


@pytest.mark.parametrize("engine", list(ENGINES))
@pytest.mark.parametrize("packing", list(PACKINGS))
def test_scan_is_the_recurrence(packing, engine):
    """``y`` of every token and the final state of every prompt, whatever
    lies where in the chunks."""
    lens = PACKINGS[packing]
    xc, dt, a, d, seg, ends = scan_inputs(lens)
    with jax.default_matmul_precision("highest"):
        y, fin = ENGINES[engine](xc, dt, a, d, seg, ends, **DIMS)
    want_y, want_fin = recurrence(xc, dt, a, d, lens)
    assert y.shape == want_y.shape and fin.shape == (len(lens), H, P, N)
    np.testing.assert_allclose(y, want_y, **TOL)
    np.testing.assert_allclose(fin, want_fin, **TOL)


@pytest.mark.parametrize("engine", list(ENGINES))
def test_a_head_reads_its_own_group(engine):
    """Head ``h`` reads ``B`` and ``C`` of group ``h // (H / G)``: the
    interleaved mapping ``h % G`` is another result."""
    lens = (13, 11)
    xc, dt, a, d, seg, ends = scan_inputs(lens, seed=5)
    with jax.default_matmul_precision("highest"):
        y, _ = ENGINES[engine](xc, dt, a, d, seg, ends, **DIMS)
    np.testing.assert_allclose(y, recurrence(xc, dt, a, d, lens)[0], **TOL)
    other, _ = recurrence(xc, dt, a, d, lens, group_of=lambda h: h % G)
    assert np.abs(np.asarray(y) - other).max() > 0.1


@pytest.mark.parametrize("engine", list(ENGINES))
def test_no_token_reaches_another_prompt(engine):
    """Changing the first prompt's tokens leaves the second's ``y`` and
    final state bit for bit; so does a prompt with a strong state (decay
    near 1) before it."""
    lens = (13, 11)
    xc, dt, a, d, seg, ends = scan_inputs(lens, seed=1)
    a = a * 1e-3  # nearly no decay: a leak would carry everything
    with jax.default_matmul_precision("highest"):
        y0, f0 = ENGINES[engine](xc, dt, a, d, seg, ends, **DIMS)
        xc2 = xc.at[:13].multiply(-3.0)
        y1, f1 = ENGINES[engine](xc2, dt, a, d, seg, ends, **DIMS)
    assert np.array_equal(np.asarray(y0[13:]), np.asarray(y1[13:]))
    assert np.array_equal(np.asarray(f0[1]), np.asarray(f1[1]))
    assert not np.array_equal(np.asarray(f0[0]), np.asarray(f1[0]))


def test_chunk_sums_describe_the_packing():
    lens = (13, 9, 5, 5)
    xc, dt, a, d, seg, ends = scan_inputs(lens)
    dt_p, cs, seg_p, last, carry, end_chunk, end_off = ssd_pallas.chunk_sums(
        dt, a, seg, ends, Q)
    assert list(np.asarray(last)) == [0, 1, 2, 3]
    assert list(np.asarray(carry)) == [-1, 0, 1, 2]
    assert list(np.asarray(end_chunk)) == [1, 2, 3, 3]
    assert list(np.asarray(end_off)) == [4, 5, 2, 7]
    # the running sum restarts a chunk, and is at most 0
    np.testing.assert_allclose(cs[8], dt_p[8] * a, rtol=1e-6)
    assert float(cs.max()) <= 0.0


def test_kernel_refuses_shapes_its_index_maps_cannot_count():
    xc, dt, a, d, seg, ends = scan_inputs((8, 8))
    with pytest.raises(ValueError, match="rows of"):
        ssd_pallas.ssd_chunk_scan(xc[:, :-1], dt, a, d, seg, ends, **DIMS)


@pytest.mark.parametrize("packing", ["boundaries_inside_chunks",
                                     "prompts_shorter_than_a_chunk"])
def test_convolution_takes_no_tap_across_a_boundary(packing):
    """The program's convolution over the packed tokens is the plain one a
    prompt at a time (zeros before a start), and so are the tails: a
    prompt's last three rows, zeros where it is shorter than the
    convolution."""
    lens = PACKINGS[packing]
    rng = np.random.default_rng(2)
    t, ch = sum(lens), 12
    xbc = rng.standard_normal((t, ch))
    wc = rng.standard_normal((4, ch))
    bias = rng.standard_normal(ch)
    pk = mamba2.packing(lens)
    got, tails = mamba2.causal_conv(
        *(jnp.asarray(x, jnp.float32) for x in (xbc, wc, bias)),
        jnp.asarray(pk["seg"]), jnp.asarray(pk["ends"]))
    at = 0
    for p, n in enumerate(lens):
        rows = np.concatenate([np.zeros((3, ch)), xbc[at:at + n]])
        pre = bias + sum(wc[k] * rows[k:k + n] for k in range(4))
        np.testing.assert_allclose(got[at:at + n], pre / (1 + np.exp(-pre)),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(tails[p], rows[n:], rtol=1e-6)
        at += n


# -- whole layers as a graph, against the benchmark's reference ----------------

ARGS = Mamba2Args(lens=(13, 11, 3, 5), heads=H, head_dim=P, groups=G,
                  state=N, taps=4, chunk=Q, dtype="float32")
TAGS = ("L0.M", "L1.M")
Z = {"lens": ARGS.lens, "heads": H, "head_dim": P, "groups": G, "state": N,
     "taps": 4, "eps": ARGS.eps}


def layers_graph(args=ARGS, tags=TAGS):
    g, last = Graph(), None
    for tag in tags:
        last = mamba2.add_layer(g, args, tag, last)
    g.then_finish(last)
    return g


def layers_reference(bufs, tags=TAGS):
    out = {}
    for tag in tags:
        p = {k: jnp.asarray(bufs[f"{k}.{tag}"], jnp.float32)
             for k in mamba2.PARAMS}
        with jax.default_matmul_precision("highest"):
            got = ref.mamba_mixer(
                Z, *(bufs[f"{k}.{tag}"] for k in mamba2.INPUTS), p)
        out.update(zip((f"out.{tag}", f"Sfin.{tag}", f"tail.{tag}"), got))
    return out


@pytest.mark.parametrize("which", ["fused", "chain", "walk0", "walk1"])
def test_whole_layers_against_the_reference(which):
    """Two layers one after another (they share their work buffers): each
    layer's ``out``, each prompt's final state and convolution tail (a
    prompt of three tokens is shorter than the convolution and than a
    chunk), on the kernel, on the XLA chain and on two random walks of the
    search's space; the iteration is idempotent."""
    bufs = mamba2.make_mamba2_buffers(ARGS, TAGS, seed=4)
    g = layers_graph()
    plat = Platform.make_n_lanes(2)
    seq = (drive(g, plat, rng=random.Random(int(which[-1])))
           if which.startswith("walk") else drive(g, plat, ("." + which,)))
    ex = TraceExecutor(plat, {k: jnp.asarray(v) for k, v in bufs.items()})
    out = ex.run(seq)
    for name, want in layers_reference(bufs).items():
        np.testing.assert_allclose(out[name], want, rtol=1e-4, atol=1e-4,
                                   err_msg=name)
    again = ex.compile(seq)(out)
    for name in out:
        assert np.array_equal(np.asarray(out[name]), np.asarray(again[name]),
                              equal_nan=True), name


def test_state_carried_in_bfloat16_is_another_result():
    """The control of the benchmark's reference at toy size: the state and
    decays rounded to bfloat16 after every token move the final states by
    1e-3 and more, the program's float32 state by 1e-5."""
    args = Mamba2Args(lens=(120, 40), heads=H, head_dim=P, groups=G, state=N,
                      chunk=Q, dtype="float32")
    z = {**Z, "lens": args.lens}
    bufs = mamba2.make_mamba2_buffers(args, TAGS[:1], seed=3)
    # decays near 1: a state that holds for the whole prompt
    bufs["A_log.L0.M"] = bufs["A_log.L0.M"] - 3.0
    p = {k: jnp.asarray(bufs[f"{k}.L0.M"], jnp.float32)
         for k in mamba2.PARAMS}
    ins = [bufs[f"{k}.L0.M"] for k in mamba2.INPUTS]
    with jax.default_matmul_precision("highest"):
        _, want, _ = ref.mamba_mixer(z, *ins, p)
        _, low, _ = ref.mamba_mixer(z, *ins, p, via=ref.BFLOAT16)
    plat = Platform.make_n_lanes(1)
    got = TraceExecutor(plat, {k: jnp.asarray(v) for k, v in bufs.items()}
                        ).run(drive(layers_graph(args, TAGS[:1]), plat,
                                    (".fused",)))["Sfin.L0.M"]

    def gap(x):
        return float(jnp.sqrt(jnp.sum((x - want) ** 2) / jnp.sum(want ** 2)))

    assert gap(got) < 1e-5
    assert gap(low) > 1e-3


def test_counters_span_and_names():
    """``ssd.*`` count a traced scan; ``ssd.plan`` opens a layer made; the
    vertices carry layer and part."""
    from tenzing_tpu.obs.tracer import Tracer, set_tracer

    reg, tracer = MetricsRegistry(), Tracer()
    prev, prev_tracer = set_metrics(reg), set_tracer(tracer)
    try:
        bufs = mamba2.make_mamba2_buffers(ARGS, TAGS[:1], seed=0)
        g = layers_graph(tags=TAGS[:1])
        plat = Platform.make_n_lanes(1)
        for want, fused, chain in ((".fused", 1, 0), (".chain", 1, 1)):
            seq = drive(g, plat, (want,))
            TraceExecutor(plat, {k: jnp.asarray(v)
                                 for k, v in bufs.items()}).run(seq)
            assert reg.counter("ssd.fused_vertices").value == fused
            assert reg.counter("ssd.chain_vertices").value == chain
        names = {op.name() for op in seq.vector()}
        assert {"L0.M.conv", "L0.M.ssd_diag", "L0.M.ssd_states",
                "L0.M.ssd_carry", "L0.M.ssd_out", "L0.M.gated_norm"} <= names
        # two traced scans of four chunks, two of which a prompt starts
        # inside of (13 and 27; 24 lies on an edge)
        assert reg.counter("ssd.chunks").value == 2 * 4
        assert reg.counter("ssd.boundary_chunks").value == 2 * 2
        assert reg.counter("ssd.prompts").value == 2 * 4
        assert reg.counter("ssd.state_bytes_written").value == (
            2 * 4 * H * P * N * 4)
        plans = [s for s in tracer.spans() if s.name == "ssd.plan"]
        assert plans and plans[0].attrs["chunks"] == 4
        assert plans[0].attrs["head_groups"] == G
        assert plans[0].attrs["prompts"] == 4
    finally:
        set_metrics(prev)
        set_tracer(prev_tracer)


def test_args_describe_the_published_layer():
    a = Mamba2Args(lens=(4000, 2100, 1000, 600, 364, 128) * 2)
    assert (a.tokens, a.prompts, a.chunks) == (16384, 12, 128)
    assert (a.inner, a.conv_width) == (4096, 6144)
    assert a.starts[:7] == (0, 4000, 6100, 7100, 7700, 8064, 8192)
    # eight of the eleven inner boundaries fall inside a chunk; 8064, 8192
    # and 16256 lie on an edge
    assert a.boundary_chunks == 8
    assert [s % 128 for s in a.starts].count(0) == 4
    with pytest.raises(ValueError):
        Mamba2Args(lens=(4, 0))


# -- the configuration is the catalog's row -------------------------------------

CATALOG_ROW = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1, "n_groups": 8,
    "n_routed_experts": 128, "n_shared_experts": 1, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072,
}


def _config():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "nemotron3-nano-mixers-prefill.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("key", sorted(CATALOG_ROW))
def test_the_configuration_holds_the_catalog_s_key(key):
    """Every key of ``NVIDIA-Nemotron-3-Nano-30B-A3B-BF16``'s row in the
    ``model-configs`` catalog, as published (copied here: the tests read
    nothing outside the repository)."""
    assert _config()[key] == CATALOG_ROW[key]


def test_the_configuration_is_the_mixers_of_one_period():
    c = _config()
    assert c["reduced"] == ["layers"] and c["layers"] == 4
    assert c["pattern"] == "MMM*"
    assert c["hybrid_override_pattern"][6:13] == "EMEMEM*"
    z = ref.sizes(c)
    assert sum(z["lens"]) == c["shapes"]["tokens"] == 16384
    assert z["lens"] == (4000, 2100, 1000, 600, 364, 128) * 2
    assert (z["heads"], z["head_dim"], z["groups"], z["state"], z["chunk"],
            z["taps"]) == (64, 64, 8, 128, 128, 4)
    assert (z["attn_heads"], z["kv_heads"], z["attn_head_dim"]) == (32, 2, 128)
    assert c["shapes"]["dtype"] == "bfloat16"
    toy = ref.sizes({**c, "shapes": {**c["shapes"], **c["rehearse"]}})
    starts = np.cumsum((0,) + toy["lens"][:-1])
    assert any(s % toy["chunk"] for s in starts[1:])  # inside a chunk
    assert any(s % toy["chunk"] == 0 for s in starts[1:])  # on an edge
