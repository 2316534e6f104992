"""Plain reference of one decode step of one period of Kimi-Linear (three Kimi
Delta Attention layers on a recurrent state, then one latent-attention layer
without positional encoding over a paged latent cache), and the data of a
run.

Imports nothing of the program.  ``tenzing_tpu/models/
delta_attention_reference.py`` states the KDA layer's published recurrence
a whole sequence from a zero state, and ``tests/test_kda_decode.py`` holds
the program's step to it; this file is its own copy of one step.

**A KDA layer**, sequence b, head h of ``H`` (``d`` channels; the inputs
as the layer's projections would hand them over):

    convolution  [q ; k ; v] = silu(sum_{i<taps} Wc[i] . row_i),  rows =
                 the window Cv[b] (taps - 1 rows) and the new row x[b];
                 Cvnew[b] = the rows moved on by one
    norms        q <- q / sqrt(|q|^2 + 1e-6) . d^(-1/2);  k likewise, no d
    decay        alpha = exp(-exp(A_log[h]) softplus(f + dt_bias))   (d,)
    beta         beta = sigmoid(b)
    state        Snew = (I - beta k k^T) Diag(alpha) S + beta k v^T
    read-out     o = Snew^T q;  o <- RMSNorm_w(o) . sigmoid(go)

float32 throughout, every contraction an elementwise product and a sum (no
matrix unit, so no rounding to bfloat16 anywhere), a block of sequences at
a time (``lax.map``: "computed in blocks").  **The latent-attention
layer** is ``references/mla_paged_decode.py``'s, copied: the absorbed order
of sums, a page of keys at a time with a running maximum, the cache read
through the block table, here a block of 16 neighbouring sequences at a
time over the pages the block's longest has (the lengths span 1k to 131k);
``scale = 192^(-1/2)`` (``rope_scaling`` null: no ``mscale``);
``mla_use_nope``: the 64 ``k_rope`` columns arrive unrotated, which changes
nothing after the inputs.

**Departure the harness forces**: the step reads ``S.<l>``, ``Cv.<l>`` and
writes ``Snew.<l>``, ``Cvnew.<l>``, and the lengths do not advance, so an
iteration is idempotent (``timed_fence_gap``).  The bytes are those of an
update in place.

What is compared (:func:`check`), the worst layer reported:

* ``kda_state_rms_gap``: root of the summed squares of ``Snew - ref`` over
  that of ``ref``.  Float32 against float32: sound runs read rounding; a
  state carried in bfloat16 reads 2^-9 a value (the control).
* ``kda_o_rms_gap`` and ``kda_o_widest_row_gap``: ``o`` (stored in
  bfloat16) against the reference's float32, the rms over a layer and the
  largest over (sequence, head) of ``|o - ref| / max(|ref|, median
  |ref|)``.  See a wrong gate, norm or convolution tap in any one head.
* ``kda_conv_mismatched_rows``: rows of ``Cvnew`` that differ from the
  reference's (limit 0: the window is copied).
* ``mla_o_rms_gap``, ``mla_o_widest_row_gap``,
  ``mla_append_mismatched_rows``: the latent layer's, as
  ``mla_paged_decode.py`` defines them.
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

#: the output buffers of one iteration that :func:`check` compares: every
#: layer's ``o``, a KDA layer's ``Snew`` and ``Cvnew``, the latent layer's
#: open pages
OUTPUT = "o"
STATE = "Snew"
WINDOW = "Cvnew"
OPEN = "Copen"
#: limits of the comparison (PERF.md section 2: each from the largest sound
#: reading and the control's at the cell's own size on the chip,
#: ``tests/kda_step1_on_chip.py`` on three seeds and seven whole runs, PR
#: 42).  The state, float32 against float32: sound 4.29e-8 to 4.41e-8 (both
#: engines, to the last digit alike), the state carried in bfloat16 2.269e-3
#: to 2.271e-3: 227 times over the one, 227 under the other.
STATE_RMS_LIMIT = 1e-5
#: ``o`` is stored in bfloat16, and sound reads that rounding: rms 1.6571e-3
#: to 1.6635e-3 (a mean over 524 288 values: it moves in the fourth digit),
#: the control 2.742e-3 to 2.753e-3: 1.32 times over the one, 1.25 under the
#: other
O_RMS_LIMIT = 0.0022
#: the widest row cannot tell the control from a sound run (a bfloat16
#: rounding is at most 2^-8 = 3.9e-3 of a row's norm: sound 2.41e-3 to
#: 2.76e-3, control 3.86e-3 to 4.07e-3); it is here for a fault of place or
#: of a gate in one head, which reads 0.05 and more (``tests/test_kda.py``):
#: 2.6 times over that bound
O_ROW_LIMIT = 0.01
#: the latent layer's, ``mla_paged_decode.py``'s own: sound reads 3.305e-3 to
#: 3.348e-3 and 6.15e-3 to 7.79e-3 here (32 heads; 4.2e-3 and 1.4e-2 there
#: at 128), the cache read as float8 3.81e-2 to 3.84e-2 and 0.110 to 0.114
MLA_RMS_LIMIT = 0.015
MLA_ROW_LIMIT = 0.05
BFLOAT16 = (8, 7)     # exponent and mantissa bits of the control's state
FLOAT8_E4M3 = (4, 3)  # of the latent cache's control (step 1's readings)
NEG = -1e30
L2_EPS = 1e-6
DECAY_RANGE = (0.2, 0.999)
KDA_DRAWN = ("x", "Cv", "Wc", "f", "dt_bias", "A_log", "b", "go", "w_norm",
             "S")
MLA_DRAWN = ("C", "Copen", "c_new", "kr_new", "q_nope", "q_rope", "W_UK",
             "W_UV")
SEQ_BLOCK = 16  # sequences a block of either reference


def sizes(config: dict) -> dict:
    """The step's sizes: the published widths (top-level keys, as the
    model's ``config.json`` names them) and the run's (``shapes``); a
    rehearsal's ``toy`` group stands in for the widths."""
    s = config["shapes"]
    src = {**config, **s.get("toy", {})}
    lin = src["linear_attn_config"]
    nope, rope = int(src["qk_nope_head_dim"]), int(src["qk_rope_head_dim"])
    if src.get("rope_scaling") is not None:
        raise ValueError("this model's softmax scale has no mscale")
    pattern = tuple(config["pattern"])
    if len(pattern) != int(config["layers"]):
        raise ValueError(f"{config['layers']} layers, pattern {pattern}")
    return {"lens": tuple(sorted(int(n) for n in s["lens"])),
            "pattern": pattern,
            "kda_heads": int(lin["num_heads"]), "d": int(lin["head_dim"]),
            "taps": int(lin["short_conv_kernel_size"]),
            "eps": float(src["rms_norm_eps"]),
            "kda_groups": int(s["kda_groups"]),
            "heads": int(src["num_attention_heads"]),
            "rank": int(src["kv_lora_rank"]), "rope": rope, "nope": nope,
            "v_dim": int(src["v_head_dim"]),
            "scale": (nope + rope) ** -0.5,
            "page": int(s["page_tokens"]), "groups": int(s["groups"]),
            "fold_pages": int(s["fold_pages"]),
            "table_seed": int(s.get("table_seed", 0)),
            "dtype": s["dtype"]}


def tags(config: dict) -> list:
    """``[(kind, tag)]`` of the period's layers, in order."""
    return [(kind, f"L{i}") for i, kind in enumerate(config["pattern"])]


def block_table(z: dict) -> np.ndarray:
    """``(batch, max_pages)``: the sealed pages of all sequences, in the
    batch's order, are a random permutation of the pool; a slot past a
    sequence's sealed pages holds 0."""
    sealed = [n // z["page"] for n in z["lens"]]
    perm = np.random.default_rng(z["table_seed"]).permutation(
        max(1, sum(sealed)))
    table = np.zeros((len(sealed), max(sealed) + 1), np.int32)
    at = 0
    for b, n in enumerate(sealed):
        table[b, :n] = perm[at:at + n]
        at += n
    return table


def kda_shapes(z: dict) -> dict:
    """``{name: (shape, dtype)}`` of one KDA layer's drawn tensors."""
    b, h, d, t = len(z["lens"]), z["kda_heads"], z["d"], z["taps"]
    dt, f32 = z["dtype"], "float32"
    return {"x": ((b, 3, h, d), dt), "Cv": ((b, t - 1, 3, h, d), dt),
            "Wc": ((t, 3, h, d), dt), "f": ((b, h, d), dt),
            "dt_bias": ((h, d), f32), "A_log": ((h, 1), f32),
            "b": ((b, h, 1), dt), "go": ((b, h, d), dt),
            "w_norm": ((1, d), f32), "S": ((b, h, d, d), f32)}


def mla_shapes(z: dict) -> dict:
    """``{name: shape}`` of the latent layer's drawn tensors."""
    b, h, w = len(z["lens"]), z["heads"], z["rank"] + z["rope"]
    pages = max(1, sum(n // z["page"] for n in z["lens"]))
    return {"C": (pages, w, z["page"]), "Copen": (b, w, z["page"]),
            "c_new": (b, z["rank"]), "kr_new": (b, z["rope"]),
            "q_nope": (b, h, z["nope"]), "q_rope": (b, h, z["rope"]),
            "W_UK": (h, z["nope"], z["rank"]),
            "W_UV": (h, z["rank"], z["v_dim"])}


# -- a KDA layer --------------------------------------------------------------

def _read(x, via):
    """``x`` as it reads through a format of ``via`` (exponent, mantissa)
    bits: ``lax.reduce_precision`` (a cast there and back the TPU's
    compiler takes out)."""
    return x if via is None else lax.reduce_precision(x, *via)


def kda_reference(z: dict, t: dict, via=None):
    """``(o, Snew, Cvnew)`` of one KDA layer from its tensors ``t`` (named
    without the layer's tag): ``o`` and ``Snew`` float32.  ``via`` (the
    control): the state is carried in that format, read rounded to it and
    written rounded to it."""
    f32 = jnp.float32
    d, eps = z["d"], z["eps"]
    wc = t["Wc"].astype(f32)
    dt_bias, a_log, w_norm = t["dt_bias"], t["A_log"], t["w_norm"]

    def block(args):
        x, cv, f, b, go, s = args
        rows = jnp.concatenate([cv, x[:, None]], axis=1)
        y = jnp.sum(wc[None] * rows.astype(f32), axis=1)
        y = y * jax.nn.sigmoid(y)
        q, k, v = y[:, 0], y[:, 1], y[:, 2]
        q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + L2_EPS) \
            * d ** -0.5
        k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + L2_EPS)
        alpha = jnp.exp(-jnp.exp(a_log) * jax.nn.softplus(
            f.astype(f32) + dt_bias))
        beta = jax.nn.sigmoid(b.astype(f32))
        decayed = alpha[..., :, None] * _read(s, via)
        erased = jnp.sum(k[..., :, None] * decayed, axis=-2)
        snew = _read(decayed + k[..., :, None] * (
            beta * (v - erased))[..., None, :], via)
        o = jnp.sum(snew * q[..., :, None], axis=-2)
        o = o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True) + eps) * w_norm
        return o * jax.nn.sigmoid(go.astype(f32)), snew, rows[:, 1:]

    batch = t["x"].shape[0]
    n = SEQ_BLOCK if batch % SEQ_BLOCK == 0 else batch
    cut = [t[k].reshape((batch // n, n) + t[k].shape[1:])
           for k in ("x", "Cv", "f", "b", "go", "S")]
    return tuple(r.reshape((batch,) + r.shape[2:])
                 for r in lax.map(block, tuple(cut)))


# -- the latent-attention layer (references/mla_paged_decode.py's) ------------

def expected_open(t: dict, page: int):
    """The open pages after the append: column ``L_b % page`` of sequence
    b's becomes ``[c_new[b] ; k_rope_new[b]]``."""
    new = jnp.concatenate([t["c_new"], t["kr_new"]], axis=1)
    batch = new.shape[0]
    return t["Copen"].at[jnp.arange(batch), :, (t["lens"] - 1) % page].set(
        new.astype(t["Copen"].dtype))


def mla_reference(z: dict, t: dict, via=None):
    """``o`` float32 ``(batch, heads, v_dim)`` of the latent layer, a block
    of neighbouring sequences and a page of keys at a time.  ``via``: the
    cache is read rounded to that format."""
    f32 = jnp.float32
    page, rank = z["page"], z["rank"]
    table = t["table"]
    batch = t["lens"].shape[0]
    n = SEQ_BLOCK if batch % SEQ_BLOCK == 0 else batch
    with jax.default_matmul_precision("highest"):
        qt = jnp.concatenate(
            [jnp.einsum("bhd,hdc->bhc", t["q_nope"].astype(f32),
                        t["W_UK"].astype(f32)), t["q_rope"].astype(f32)],
            axis=2)
        opened_all = expected_open(t, page)

        def block(i):
            at = i * n
            vis = lax.dynamic_slice_in_dim(t["lens"], at, n)
            rows = lax.dynamic_slice_in_dim(table, at, n)
            q = lax.dynamic_slice_in_dim(qt, at, n)
            opened = lax.dynamic_slice_in_dim(opened_all, at, n).astype(f32)
            open_tile = (vis - 1) // page

            def one_tile(j, carry):
                acc, m, l = carry
                sealed = t["C"][rows[:, jnp.minimum(j, rows.shape[1] - 1)]]
                kt = _read(jnp.where((j == open_tile)[:, None, None], opened,
                                     sealed.astype(f32)), via)
                seen = (j * page + jnp.arange(page))[None, :] < vis[:, None]
                s = z["scale"] * jnp.einsum("bhw,bwk->bhk", q, kt)
                s = jnp.where(seen[:, None, :], s, NEG)
                m_new = jnp.maximum(m, jnp.max(s, axis=2, keepdims=True))
                alpha = jnp.exp(m - m_new)
                p = jnp.where(seen[:, None, :], jnp.exp(s - m_new), 0.0)
                return (acc * alpha + jnp.einsum("bhk,bck->bhc", p,
                                                 kt[:, :rank]),
                        m_new, l * alpha + jnp.sum(p, axis=2, keepdims=True))

            shape = (n, q.shape[1])
            acc, _, l = lax.fori_loop(
                0, jnp.max(open_tile) + 1, one_tile,
                (jnp.zeros(shape + (rank,), f32),
                 jnp.full(shape + (1,), NEG, f32),
                 jnp.zeros(shape + (1,), f32)))
            return acc / l

        o_lat = lax.map(block, jnp.arange(batch // n))
        o_lat = o_lat.reshape((batch,) + o_lat.shape[2:])
        return jnp.einsum("bhc,hcd->bhd", o_lat, t["W_UV"].astype(f32))


# -- the numbers compared -----------------------------------------------------

def _median(x):
    """``jnp.median`` of non-negative float32 values by bisection on their
    bit patterns (``references/mla_paged_decode.py``: a sort costs the
    TPU's compiler far more)."""
    bits = lax.bitcast_convert_type(x.ravel(), jnp.int32)

    def kth(k):
        def halve(_, span):
            lo, hi = span
            mid = lo + (hi - lo) // 2
            enough = jnp.sum(bits <= mid) > k
            return jnp.where(enough, lo, mid + 1), jnp.where(enough, mid, hi)

        _, hi = lax.fori_loop(0, 31, halve,
                              (jnp.int32(0), jnp.int32(0x7F800000)))
        return lax.bitcast_convert_type(hi, jnp.float32)

    return 0.5 * (kth((bits.size - 1) // 2) + kth(bits.size // 2))


def _o_gaps(out, ref):
    """``[rms gap, widest row gap]`` of a layer's ``o``."""
    err = out.astype(jnp.float32) - ref
    err2, ref2 = jnp.sum(err * err, axis=2), jnp.sum(ref * ref, axis=2)
    floor = _median(jnp.sqrt(ref2))
    return [jnp.sqrt(jnp.sum(err2) / jnp.sum(ref2)),
            jnp.max(jnp.sqrt(err2) / jnp.maximum(jnp.sqrt(ref2), floor))]


def _frozen(z: dict) -> tuple:
    return tuple(sorted(z.items()))


@lru_cache(maxsize=None)
def _programs(frozen: tuple):
    """``(draw_kda, draw_mla, kda_gaps, mla_ref, mla_gaps, kda_ref)``: one
    layer's tensors from a key; a KDA layer's four numbers from its tensors
    and outputs (``via`` static; the reference's ``Snew`` lives only
    inside); the latent layer's reference ``o`` and expected open pages,
    and its three numbers; a KDA layer's reference outputs themselves (the
    control's and the tests')."""
    z = dict(frozen)
    dt = jnp.dtype(z["dtype"])
    d, taps = z["d"], z["taps"]
    lo, hi = (-np.log(x) for x in reversed(DECAY_RANGE))

    def key_of(seed, layer):
        # the device's own generator (``rbg``: the default ``threefry``
        # costs a checkout's first run most of a minute of compiling)
        return jax.random.fold_in(jax.random.key(seed, impl="rbg"), layer)

    @jax.jit
    def draw_kda(seed, layer):
        key = key_of(seed, layer)
        shapes = kda_shapes(z)
        k = {name: jax.random.fold_in(key, i)
             for i, name in enumerate(sorted(shapes))}

        def normal(name, scale=1.0):
            shape, dtype = shapes[name]
            return (jax.random.normal(k[name], shape, jnp.float32)
                    * scale).astype(jnp.dtype(dtype))

        # the decay a step and key channel: -g = exp(A_log) softplus(f +
        # dt_bias) log-uniform between -ln 0.999 and -ln 0.2 at f = 0
        a_log = jax.random.uniform(k["A_log"], shapes["A_log"][0],
                                   jnp.float32, -0.5, 0.5)
        rate = jnp.exp(jax.random.uniform(
            k["dt_bias"], shapes["dt_bias"][0], jnp.float32,
            np.log(lo), np.log(hi)))
        return {"x": normal("x"), "Cv": normal("Cv"),
                "Wc": normal("Wc", taps ** -0.5), "f": normal("f", 0.5),
                "dt_bias": jnp.log(jnp.expm1(rate / jnp.exp(a_log))),
                "A_log": a_log, "b": normal("b"), "go": normal("go"),
                "w_norm": 1.0 + 0.1 * jax.random.normal(
                    k["w_norm"], shapes["w_norm"][0], jnp.float32),
                # a state after many tokens, not zeros: rows of the size a
                # unit-norm key's rank-one updates leave
                "S": normal("S", d ** -0.5)}

    scaled = {"W_UK": z["nope"] ** -0.5, "W_UV": z["rank"] ** -0.5}

    @jax.jit
    def draw_mla(seed, layer):
        key = key_of(seed, layer)
        return {name: (jax.random.normal(jax.random.fold_in(key, i), shape,
                                         jnp.float32)
                       * scaled.get(name, 1.0)).astype(dt)
                for i, (name, shape) in enumerate(
                    sorted(mla_shapes(z).items()))}

    @partial(jax.jit, static_argnums=4)
    def kda_gaps(t, o, snew, cvnew, via):
        ref_o, ref_s, ref_w = kda_reference(z, t, via)
        err = snew - ref_s
        return jnp.stack(
            [jnp.sqrt(jnp.sum(err * err) / jnp.sum(ref_s * ref_s))]
            + _o_gaps(o, ref_o)
            + [jnp.sum(jnp.any(
                (cvnew != ref_w).reshape(cvnew.shape[0], -1),
                axis=1)).astype(jnp.float32)])

    @partial(jax.jit, static_argnums=1)
    def mla_ref(t, via):
        return mla_reference(z, t, via), expected_open(t, z["page"])

    @jax.jit
    def mla_gaps(out, opened, ref, ref_open):
        return jnp.stack(_o_gaps(out, ref) + [jnp.sum(jnp.any(
            opened != ref_open, axis=1)).astype(jnp.float32)])

    kda_ref = jax.jit(partial(kda_reference, z), static_argnums=1)
    return draw_kda, draw_mla, kda_gaps, mla_ref, mla_gaps, kda_ref


def _seed(seed: int):
    return jnp.uint32(seed & 0xFFFFFFFF)


#: the run's data, kept for the reference to read (one seed at a time: the
#: states are 0.8 GB and the pools 1.2, and the executor holds the same
#: arrays)
_DATA = {}
#: ``{sizes: (kda_gaps, mla_ref, mla_gaps)}`` compiled by :func:`precompile`
_COMPILED = {}


def make_data(config: dict, seed: int) -> dict:
    """Every input of the step: ``<name>.L<i>`` for the drawn tensors of
    every layer, ``lens`` (visible keys, ``L_b + 1``) and ``table``."""
    z = sizes(config)
    key = (_frozen(z), seed)
    if key not in _DATA:
        _DATA.clear()
        draw_kda, draw_mla = _programs(key[0])[:2]
        data = {"lens": jnp.asarray([n + 1 for n in z["lens"]], jnp.int32),
                "table": jnp.asarray(block_table(z))}
        for i, kind in enumerate(z["pattern"]):
            draw = draw_kda if kind == "kda" else draw_mla
            data.update({f"{name}.L{i}": x
                         for name, x in draw(_seed(seed), i).items()})
        _DATA[key] = data
    return dict(_DATA[key])


def layer_tensors(data: dict, kind: str, tag: str) -> dict:
    """One layer's tensors of ``data`` under their plain names."""
    if kind == "kda":
        return {name: data[f"{name}.{tag}"] for name in KDA_DRAWN}
    t = {name: data[f"{name}.{tag}"] for name in MLA_DRAWN}
    t.update(lens=data["lens"], table=data["table"])
    return t


@lru_cache(maxsize=1)
def _mla_reference_of(frozen: tuple, seed: int, tag: str):
    """The latent layer's ``(o_ref, expected open pages)`` for one seed,
    computed once a run (each schedule compared reads the same).  A KDA
    layer's reference is computed anew a schedule: its ``Snew`` is the size
    of the state, and three of them kept would be a fourth set of states
    on the chip."""
    data = _DATA[(frozen, seed)]
    run = (_COMPILED[frozen][1] if frozen in _COMPILED
           else partial(_programs(frozen)[3], via=None))
    return run(layer_tensors(data, "mla", tag))


def precompile(config: dict, like: dict) -> None:
    """Compile the references and the comparisons for one layer of each
    kind, shaped as ``like`` (set-up: the persistent cache keeps them, and
    no run of them is counted as set-up)."""
    z = sizes(config)
    frozen = _frozen(z)
    kda_gaps, mla_ref, mla_gaps = _programs(frozen)[2:5]

    def spec(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype)

    first = {kind: tag for kind, tag in reversed(tags(config))}
    t = {k: spec(v) for k, v in layer_tensors(like, "kda",
                                              first["kda"]).items()}
    out = [spec(like[f"{name}.{first['kda']}"])
           for name in (OUTPUT, STATE, WINDOW)]
    compiled = [kda_gaps.lower(t, *out, None).compile()]
    t = {k: spec(v) for k, v in layer_tensors(like, "mla",
                                              first["mla"]).items()}
    o = spec(like[f"{OUTPUT}.{first['mla']}"])
    ref = jax.ShapeDtypeStruct(o.shape, jnp.float32)
    compiled += [mla_ref.lower(t, None).compile(),
                 mla_gaps.lower(o, t["Copen"], ref, t["Copen"]).compile()]
    _COMPILED[frozen] = tuple(compiled)


def _numbers(config: dict, seed: int, outputs_: dict) -> dict:
    """``{name: worst layer's value}`` of everything compared."""
    z = sizes(config)
    frozen = _frozen(z)
    data = make_data(config, seed)
    if frozen in _COMPILED:
        kda_gaps, _, mla_gaps = _COMPILED[frozen]
    else:
        progs = _programs(frozen)
        kda_gaps, mla_gaps = partial(progs[2], via=None), progs[4]
    kda, mla = [], []
    for kind, tag in tags(config):
        if kind == "kda":
            kda.append(kda_gaps(
                layer_tensors(data, kind, tag),
                *(outputs_[f"{name}.{tag}"]
                  for name in (OUTPUT, STATE, WINDOW))))
        else:
            ref, opened = _mla_reference_of(frozen, seed, tag)
            mla.append(mla_gaps(outputs_[f"{OUTPUT}.{tag}"],
                                outputs_[f"{OPEN}.{tag}"], ref, opened))
    kda, mla = jax.device_get([kda, mla])
    return {"kda_state_rms_gap": float(max(g[0] for g in kda)),
            "kda_o_rms_gap": float(max(g[1] for g in kda)),
            "kda_o_widest_row_gap": float(max(g[2] for g in kda)),
            "kda_conv_mismatched_rows": int(sum(g[3] for g in kda)),
            "mla_o_rms_gap": float(max(g[0] for g in mla)),
            "mla_o_widest_row_gap": float(max(g[1] for g in mla)),
            "mla_append_mismatched_rows": int(sum(g[2] for g in mla))}


LIMITS = {"kda_state_rms_gap": STATE_RMS_LIMIT, "kda_o_rms_gap": O_RMS_LIMIT,
          "kda_o_widest_row_gap": O_ROW_LIMIT, "kda_conv_mismatched_rows": 0,
          "mla_o_rms_gap": MLA_RMS_LIMIT,
          "mla_o_widest_row_gap": MLA_ROW_LIMIT,
          "mla_append_mismatched_rows": 0}


def check(config: dict, seed: int, outputs_: dict) -> list:
    """The seven numbers of the module's docstring, each beside its
    limit."""
    got = _numbers(config, seed, outputs_)
    return [{"name": name, "value": got[name], "limit": limit}
            for name, limit in LIMITS.items()]


def _as_outputs(config: dict, seed: int, kda_via, mla_via=None) -> dict:
    z = sizes(config)
    data = make_data(config, seed)
    dt = jnp.dtype(z["dtype"])
    progs = _programs(_frozen(z))
    mla_ref, kda = progs[3], progs[5]
    out = {}
    for kind, tag in tags(config):
        t = layer_tensors(data, kind, tag)
        if kind == "kda":
            o, snew, cvnew = kda(t, kda_via)
            out.update({f"{OUTPUT}.{tag}": o.astype(dt),
                        f"{STATE}.{tag}": snew, f"{WINDOW}.{tag}": cvnew})
        else:
            o, opened = mla_ref(t, mla_via)
            out.update({f"{OUTPUT}.{tag}": o.astype(dt),
                        f"{OPEN}.{tag}": opened})
    return out


def control(config: dict, seed: int) -> dict:
    """The reference in the program's place, one precision down: the
    recurrent state carried in bfloat16 where the configuration states
    float32.  :func:`check` has to refuse it."""
    return _as_outputs(config, seed, BFLOAT16)


def cache_control(config: dict, seed: int) -> dict:
    """The latent cache read as float8 (``mla_paged_decode.py``'s control;
    step 1's readings for the latent layer's limits)."""
    return _as_outputs(config, seed, None, FLOAT8_E4M3)


def sound(config: dict, seed: int) -> dict:
    """The reference's own float32 layers, ``o`` rounded once to the
    configuration's dtype (tests: :func:`check` passes it)."""
    return _as_outputs(config, seed, None)
