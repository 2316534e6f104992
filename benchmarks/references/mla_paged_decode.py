"""Plain reference of one decode step of latent attention (DeepSeek-V3's MLA)
over a paged latent cache, and the data of a run.

Imports nothing of the program.  Per layer, sequence b with ``L_b`` cached
tokens, head h (``tenzing_tpu/models/latent_attention_reference.py`` states
the published form these are reordered from; ``tests/test_mla_decode.py``
holds this file to it):

    append:   row L_b of b's cache becomes [c_new[b] ; k_rope_new[b]]
    absorb:   qt[b,h] = [q_nope[b,h] W_UK[h] ; q_rope[b,h]]        (576)
    scores:   s[b,h,j] = scale qt[b,h] . C[b,j,:],   j = 0 .. L_b
    softmax:  p = softmax_j(s);   o_lat[b,h] = sum_j p[b,h,j] C[b,j,:512]
    up:       o[b,h] = o_lat[b,h] W_UV[h]                           (128)

float32 throughout at ``jax.default_matmul_precision("highest")``.
**Departure, written down**: this is the *absorbed* order of sums, a page of
keys at a time with a running maximum, and not the published form (per-head
``k_nope = W_UK c`` and ``v = c W_UV`` for every cached token, then plain
attention): that one is 35 TFLOP a layer of float32 products at the cell's
size, most of an epilogue.  :func:`published_layer` computes it in blocks
for ``tests/mla_step1_on_chip.py``, which reads the gap between the two
once at the cell's own size (PERF.md section 2).  Other departures: ``qt``,
P and ``o_lat`` stay float32 where the system stores ``qt`` and ``o_lat``
in bfloat16 and rounds P before the second product; the limits carry them.

**The data's layout** is part of what is handed over, so the reference reads
it too: a layer's cache is a pool of sealed pages ``C`` ``(pages, 576,
page)`` and one open page a sequence ``Copen`` ``(batch, 576, page)``; a
page holds its keys as *columns*; token j of sequence b is column ``j %
page`` of ``C[table[b, j // page]]`` while ``j // page < L_b // page`` and of
``Copen[b]`` from there on.  ``lens`` holds the visible keys ``L_b + 1``.
Lengths and table come from the configuration (``shapes.lens``,
``shapes.table_seed``), everything else from the seed, drawn on the device.

What is compared (:func:`check`), the worst layer reported:

* ``mla_o_rms_gap``: root of the summed squares of ``o - o_ref`` over that
  of ``o_ref``.  Sees a lower precision anywhere.
* ``mla_o_widest_row_gap``: the largest, over (sequence, head), of ``|o -
  o_ref| / max(|o_ref|, median |o_ref|)`` (Euclidean norms over the head's
  width).  A sequence read through a wrong table row reads 0.1 to 1 (a
  page of 2048 wrong keys is 1.6% of the longest sequence's and a quarter
  of the shortest's).  One key more or less (a limit off by one, the new
  row unread) reads 0.1 and more at the toy's dozens of keys
  (``tests/test_mla.py``, ``tests/test_mla_decode.py``: that is where the
  kernel's limit arithmetic is held to the key) and cannot be seen by any
  tolerance in a mean over 8k to 131k keys.
* ``mla_append_mismatched_rows``: columns of the open pages that differ
  from the reference's after its own append, every layer (limit 0: an
  append copies).
"""

from __future__ import annotations

import math
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

#: the output buffers of one iteration that :func:`check` compares are
#: ``OUTPUT.<layer>`` and ``OPEN.<layer>``, one a layer
OUTPUT = "o"
OPEN = "Copen"
#: limits of the comparison (PERF.md, section 2: each between the largest
#: sound reading and the control's smallest at the cell's own size on the
#: chip, three seeds each, PR 35).  rms: sound 0.00414 to 0.00420 (five
#: bfloat16 roundings: qt, P, o_lat, o and the cache's own), the float8
#: control 0.0622 to 0.0642: 3.6 times over the one, 4.1 under the other.
#: Widest row: sound 0.0121 to 0.0142 (a maximum over 2048 to 4096 rows),
#: control 0.208 to 0.232: 3.5 times over, 4.2 under
RMS_LIMIT = 0.015
ROW_LIMIT = 0.05
FLOAT8_E4M3 = (4, 3)  # exponent and mantissa bits of the control's cache
NEG = -1e30           # the empty row maximum: finite, so no NaN from exp
DRAWN = ("C", "Copen", "c_new", "kr_new", "q_nope", "q_rope", "W_UK", "W_UV")


def sizes(config: dict) -> dict:
    """The step's sizes: the published widths (top-level keys, as the
    model's ``config.json`` names them) and the run's (``shapes``); a
    rehearsal's ``toy`` group stands in for the widths."""
    s = config["shapes"]
    src = {**config, **s.get("toy", {})}
    nope, rope = int(src["qk_nope_head_dim"]), int(src["qk_rope_head_dim"])
    yarn = config["rope_scaling"]
    mscale = 0.1 * float(yarn["mscale_all_dim"]) * math.log(
        float(yarn["factor"])) + 1.0
    return {"lens": tuple(sorted(int(n) for n in s["lens"])),
            "heads": int(src["num_attention_heads"]),
            "rank": int(src["kv_lora_rank"]), "rope": rope, "nope": nope,
            "v_dim": int(src["v_head_dim"]),
            "scale": (nope + rope) ** -0.5 * mscale * mscale,
            "page": int(s["page_tokens"]), "groups": int(s["groups"]),
            "fold_pages": int(s["fold_pages"]),
            "table_seed": int(s.get("table_seed", 0)),
            "layers": int(config["layers"]), "dtype": s["dtype"]}


def tags(config: dict) -> list:
    return [f"L{i}" for i in range(int(config["layers"]))]


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


def shapes(z: dict) -> dict:
    """``{name: shape}`` of one layer's drawn tensors."""
    b, h, w = len(z["lens"]), z["heads"], z["rank"] + z["rope"]
    pages = max(1, sum(n // z["page"] for n in z["lens"]))
    return {"C": (pages, w, z["page"]), "Copen": (b, w, z["page"]),
            "c_new": (b, z["rank"]), "kr_new": (b, z["rope"]),
            "q_nope": (b, h, z["nope"]), "q_rope": (b, h, z["rope"]),
            "W_UK": (h, z["nope"], z["rank"]),
            "W_UV": (h, z["rank"], z["v_dim"])}


def layer_reference(z: dict, t: dict, via=None):
    """``o`` float32 ``(batch, heads, v_dim)`` of one layer from its tensors
    ``t`` (named without the layer's tag, ``lens`` and ``table`` among
    them), a page of keys at a time.  ``via`` (the control; exponent and
    mantissa bits): the cache is read rounded to that format by
    ``lax.reduce_precision`` (a cast there and back the TPU compiler takes
    out, PERF.md section 2)."""
    f32 = jnp.float32
    page, rank = z["page"], z["rank"]
    vis, table = t["lens"], t["table"]
    batch = vis.shape[0]
    with jax.default_matmul_precision("highest"):
        qt = jnp.concatenate(
            [jnp.einsum("bhd,hdc->bhc", t["q_nope"].astype(f32),
                        t["W_UK"].astype(f32)), t["q_rope"].astype(f32)],
            axis=2)
        opened = expected_open(t, page).astype(f32)
        open_tile = (vis - 1) // page

        def read(x):
            return x if via is None else lax.reduce_precision(x, *via)

        def one_tile(j, carry):
            acc, m, l = carry
            sealed = t["C"][table[:, jnp.minimum(j, table.shape[1] - 1)]]
            kt = read(jnp.where((j == open_tile)[:, None, None], opened,
                                sealed.astype(f32)))  # (batch, width, page)
            seen = (j * page + jnp.arange(page))[None, :] < vis[:, None]
            s = z["scale"] * jnp.einsum("bhw,bwk->bhk", qt, kt)
            s = jnp.where(seen[:, None, :], s, NEG)
            m_new = jnp.maximum(m, jnp.max(s, axis=2, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.where(seen[:, None, :], jnp.exp(s - m_new), 0.0)
            return (acc * alpha + jnp.einsum("bhk,bck->bhc", p,
                                             kt[:, :rank]),
                    m_new, l * alpha + jnp.sum(p, axis=2, keepdims=True))

        shape = (batch, qt.shape[1])
        acc, _, l = lax.fori_loop(
            0, table.shape[1], one_tile,
            (jnp.zeros(shape + (rank,), f32), jnp.full(shape + (1,), NEG, f32),
             jnp.zeros(shape + (1,), f32)))
        return jnp.einsum("bhc,hcd->bhd", acc / l, t["W_UV"].astype(f32))


def published_layer(z: dict, t: dict, keys: int = 2048):
    """``o`` of one layer by the *published* form, ``keys`` tokens of one
    sequence at a time (per-head ``k_nope`` and ``v`` from the latent, then
    plain attention with a running maximum).  For the step-1 script: 35
    TFLOP of float32 products a layer at the cell's size."""
    f32 = jnp.float32
    page, rank = z["page"], z["rank"]
    if keys % page:
        raise ValueError(f"blocks of whole pages: {keys} % {page}")
    per = keys // page
    w_uk, w_uv = t["W_UK"].astype(f32), t["W_UV"].astype(f32)
    opened = expected_open(t, page).astype(f32)
    table = t["table"]

    def one_sequence(b):
        vis = t["lens"][b]
        q_nope, q_rope = t["q_nope"][b].astype(f32), t["q_rope"][b].astype(f32)
        open_tile = (vis - 1) // page

        def one_block(i, carry):
            acc, m, l = carry
            tiles = i * per + jnp.arange(per)
            sealed = t["C"][table[b, jnp.minimum(tiles, table.shape[1] - 1)]]
            kt = jnp.where((tiles == open_tile)[:, None, None], opened[b],
                           sealed.astype(f32))        # (per, width, page)
            c = jnp.moveaxis(kt, 1, 2).reshape(keys, -1)  # (keys, width)
            seen = i * keys + jnp.arange(keys) < vis
            k_nope = jnp.einsum("hdc,jc->jhd", w_uk, c[:, :rank])
            v = jnp.einsum("jc,hcd->jhd", c[:, :rank], w_uv)
            s = z["scale"] * (jnp.einsum("hd,jhd->hj", q_nope, k_nope)
                              + jnp.einsum("hr,jr->hj", q_rope, c[:, rank:]))
            s = jnp.where(seen[None, :], s, NEG)
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.where(seen[None, :], jnp.exp(s - m_new), 0.0)
            return (acc * alpha + jnp.einsum("hj,jhd->hd", p, v), m_new,
                    l * alpha + jnp.sum(p, axis=1, keepdims=True))

        h = q_nope.shape[0]
        acc, _, l = lax.fori_loop(
            0, (vis + keys - 1) // keys, one_block,
            (jnp.zeros((h, z["v_dim"]), f32), jnp.full((h, 1), NEG, f32),
             jnp.zeros((h, 1), f32)))
        return acc / l

    with jax.default_matmul_precision("highest"):
        return lax.map(one_sequence, jnp.arange(t["lens"].shape[0]))


def expected_open(t: dict, page: int):
    """The open pages after the append: column ``L_b % page`` of sequence
    b's becomes ``[c_new[b] ; k_rope_new[b]]``."""
    new = jnp.concatenate([t["c_new"], t["kr_new"]], axis=1)
    batch = new.shape[0]
    return t["Copen"].at[jnp.arange(batch), :, (t["lens"] - 1) % page].set(
        new.astype(t["Copen"].dtype))


def _median(x):
    """``jnp.median`` of non-negative float32 values (an even count: the mean
    of the two middle ones), by bisection on their bit patterns, which order
    as the values do (``references/attn_window_gqa.py``: a sort costs the
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


def _frozen(z: dict) -> tuple:
    return tuple(sorted(z.items()))


@lru_cache(maxsize=None)
def _programs(frozen: tuple):
    """``(draw, reference, gaps)``: one layer's tensors from a key; one
    layer's reference ``o`` and expected open pages (``via`` static); the
    three numbers of one layer's outputs against them."""
    z = dict(frozen)
    dt = jnp.dtype(z["dtype"])
    scaled = {"W_UK": z["nope"] ** -0.5, "W_UV": z["rank"] ** -0.5}

    @jax.jit
    def draw(seed, layer):
        # the device's own generator (``rbg``: the default ``threefry``
        # costs a checkout's first run most of a minute of compiling)
        key = jax.random.fold_in(jax.random.key(seed, impl="rbg"), layer)
        return {name: (jax.random.normal(jax.random.fold_in(key, i), shape,
                                         jnp.float32)
                       * scaled.get(name, 1.0)).astype(dt)
                for i, (name, shape) in enumerate(sorted(shapes(z).items()))}

    @partial(jax.jit, static_argnums=1)
    def reference(t, via):
        return layer_reference(z, t, via), expected_open(t, z["page"])

    @jax.jit
    def gaps(out, opened, ref, ref_open):
        err = out.astype(jnp.float32) - ref
        err2, ref2 = jnp.sum(err * err, axis=2), jnp.sum(ref * ref, axis=2)
        floor = _median(jnp.sqrt(ref2))
        return jnp.stack([
            jnp.sqrt(jnp.sum(err2) / jnp.sum(ref2)),
            jnp.max(jnp.sqrt(err2) / jnp.maximum(jnp.sqrt(ref2), floor)),
            jnp.sum(jnp.any(opened != ref_open, axis=1)).astype(jnp.float32)])

    return draw, reference, gaps


def _seed(seed: int):
    return jnp.uint32(seed & 0xFFFFFFFF)


#: the run's data, kept for the reference to read (one seed at a time: the
#: pools are 0.6 GB a layer and the executor holds the same arrays)
_DATA = {}
#: ``{sizes: (reference, gaps)}`` compiled by :func:`precompile` (the
#: epilogue runs with the persistent cache off)
_COMPILED = {}


def make_data(config: dict, seed: int) -> dict:
    """Every input of the step: ``<name>.L<i>`` for the drawn tensors of
    every layer, ``lens`` (visible keys, ``L_b + 1``) and ``table``."""
    z = sizes(config)
    key = (_frozen(z), seed)
    if key not in _DATA:
        _DATA.clear()
        draw = _programs(key[0])[0]
        data = {"lens": jnp.asarray([n + 1 for n in z["lens"]], jnp.int32),
                "table": jnp.asarray(block_table(z))}
        for i in range(z["layers"]):
            data.update({f"{name}.L{i}": x
                         for name, x in draw(_seed(seed), i).items()})
        _DATA[key] = data
    return dict(_DATA[key])


def layer_tensors(data: dict, i: int) -> dict:
    """One layer's tensors of ``data`` under their plain names."""
    t = {name: data[f"{name}.L{i}"] for name in DRAWN}
    t.update(lens=data["lens"], table=data["table"])
    return t


@lru_cache(maxsize=1)
def _reference_of(frozen: tuple, seed: int):
    """Every layer's ``(o_ref, expected open pages)`` for one seed, computed
    once a run (each schedule compared reads the same)."""
    data = _DATA[(frozen, seed)]
    if frozen in _COMPILED:
        run = _COMPILED[frozen][0]
    else:
        run = partial(_programs(frozen)[1], via=None)
    return [run(layer_tensors(data, i))
            for i in range(dict(frozen)["layers"])]


def precompile(config: dict, like: dict) -> None:
    """Compile the reference and the comparison for one layer's tensors and
    outputs shaped as ``like`` (set-up: the persistent cache keeps them, and
    no run of them is counted as set-up)."""
    z = sizes(config)
    frozen = _frozen(z)
    _, reference, gaps = _programs(frozen)

    def spec(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype)

    t = {k: spec(v) for k, v in layer_tensors(like, 0).items()}
    o = spec(like[f"{OUTPUT}.L0"])
    ref = jax.ShapeDtypeStruct(o.shape, jnp.float32)
    _COMPILED[frozen] = (
        reference.lower(t, None).compile(),
        gaps.lower(o, t["Copen"], ref, t["Copen"]).compile())


def check(config: dict, seed: int, outputs_: dict) -> list:
    """The three numbers of the module's docstring."""
    z = sizes(config)
    frozen = _frozen(z)
    make_data(config, seed)
    refs = _reference_of(frozen, seed)
    gaps = _COMPILED[frozen][1] if frozen in _COMPILED else _programs(
        frozen)[2]
    got = jax.device_get([
        gaps(outputs_[f"{OUTPUT}.{t}"], outputs_[f"{OPEN}.{t}"], ref, opened)
        for t, (ref, opened) in zip(tags(config), refs)])
    return [{"name": "mla_o_rms_gap",
             "value": float(max(g[0] for g in got)), "limit": RMS_LIMIT},
            {"name": "mla_o_widest_row_gap",
             "value": float(max(g[1] for g in got)), "limit": ROW_LIMIT},
            {"name": "mla_append_mismatched_rows",
             "value": int(sum(g[2] for g in got)), "limit": 0}]


def _as_outputs(config: dict, seed: int, via) -> dict:
    z = sizes(config)
    data = make_data(config, seed)
    run = _programs(_frozen(z))[1]
    out = {}
    for i, t in enumerate(tags(config)):
        o, opened = run(layer_tensors(data, i), via)
        out[f"{OUTPUT}.{t}"] = o.astype(jnp.dtype(z["dtype"]))
        out[f"{OPEN}.{t}"] = opened
    return out


def control(config: dict, seed: int) -> dict:
    """The reference in the program's place, one precision down: the cache
    read as float8 where the configuration states bfloat16.  :func:`check`
    has to refuse it."""
    return _as_outputs(config, seed, FLOAT8_E4M3)


def sound(config: dict, seed: int) -> dict:
    """The reference's own float32 layers, rounded once to the
    configuration's dtype (tests: :func:`check` passes it)."""
    return _as_outputs(config, seed, None)
