"""Plain reference of one decode step of learned sparse attention
(DeepSeek-V3.2's DSA) over two paged caches, and the data of a run.

Imports nothing of the program.  Per layer, sequence b with ``L_b`` cached
tokens (``tenzing_tpu/models/latent_attention_reference.py`` states the
published form; ``tests/test_dsa_decode.py`` holds this file to it):

    append:  row L_b of b's latent cache becomes [c_new[b] ; k_rope_new[b]],
             row L_b of its index-key cache kI_new[b]
    index:   I[b,j] = sum_h wI[b,h] relu(qI[b,h] . KI[b,j]),   j = 0 .. L_b
    select:  S_b = positions of the min(topk, L_b + 1) largest I[b,.],
             equal scores to the lower position
    attend:  qt[b,h] = [q_nope[b,h] W_UK[h] ; q_rope[b,h]];
             p = softmax over j in S_b of scale qt[b,h] . C[b,j,:];
             o_lat[b,h] = sum_{j in S_b} p C[b,j,:512];  o = o_lat W_UV[h]

float32 throughout at ``jax.default_matmul_precision("highest")``, the index
a page of keys at a time so that it fits.  **Departures, written down**: the
attention is the *absorbed* order of sums over the selected rows gathered
(the published form is the dense scores with -inf outside ``S_b``: the same
sums; ``tests/test_dsa_decode.py`` ties the two at toy size); ``qt``, P and
``o_lat`` stay float32 where the system stores ``qt`` and ``o_lat`` in
bfloat16 and rounds P before the second product; the limits carry them.

**The data's layout** is part of what is handed over, so the reference reads
it too.  Both caches are paged through one table and one vector of lengths
(``lens`` holds the visible keys ``L_b + 1``), sealed pages in a pool and one
open page a sequence: index keys ``KI`` ``(pages, 128, page)`` / ``KIopen``
``(batch, 128, page)``, a page's keys as *columns*; latents ``C`` ``(pages,
page, 640)`` / ``Copen`` ``(batch, page, 640)``, **a token a row**, its 576
numbers then 64 zeros (whole lanes: the configuration's
``assumed.cache_layout``).  Token j of sequence b lies in page ``table[b, j
// page]`` of a pool while ``j // page < L_b // page`` and in b's open page
from there on, at ``j % page``.  Lengths and table come from the
configuration, everything else from the seed, drawn on the device.

What is compared (:func:`check`), summed or the worst over the layers:

* *the selection, against this file's float32 scores*.
  ``dsa_select_malformed_rows``: sequences whose row of ``sel`` does not
  hold exactly ``min(topk, L_b + 1)`` distinct visible positions in its
  first slots (limit 0).  ``dsa_selection_outside_margin``: selected
  positions whose reference score lies under the reference's ``topk``-th
  largest by more than :data:`MARGIN`, and visible unselected ones over it
  by more (limit 0).  A near-tie the two programs' roundings decide
  differently moves neither; a wrong table row, a length off by one or an
  index cache read in a lower precision does.
  ``dsa_selection_differs``: positions selected that the reference's own
  exact selection leaves out; reported, its limit is what it cannot pass.
* *the attention, against the float32 reference over the program's own
  selection*: ``dsa_o_rms_gap`` (root of the summed squares of ``o -
  o_ref`` over that of ``o_ref``: a lower precision anywhere) and
  ``dsa_o_widest_row_gap`` (largest over (sequence, head) of ``|o - o_ref|
  / max(|o_ref|, median |o_ref|)``: a row gathered from a wrong place, a
  key limit off by one; over 2048 keys one wrong key shows where over 131k
  it cannot).
* ``dsa_append_mismatched_rows``: rows of the open latent pages and columns
  of the open index pages that differ from the reference's after its own
  appends, every layer (limit 0: an append copies).
"""

from __future__ import annotations

import math
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

#: the output buffers of one iteration that :func:`check` compares, one a
#: layer: ``o.<layer>``, ``sel.<layer>``, ``Copen.<layer>``, ``KIopen.<layer>``
OUTPUT = "o"
SELECTED = "sel"
OPEN = "Copen"
OPEN_KEYS = "KIopen"
SCORES = "I"  # the last layer's scores (shared by the layers): readings only
#: limits of the comparison (PERF.md, section 2: each between the largest
#: sound reading and the control's smallest at the cell's own size on the
#: chip, three seeds each, PR 40)
MARGIN = 1e-3
RMS_LIMIT = 0.015
ROW_LIMIT = 0.05
FLOAT8_E4M3 = (4, 3)  # exponent and mantissa bits of the control's caches
NEG = -1e30           # a score past a sequence's length
LANES = 128
DRAWN = ("C", "Copen", "KI", "KIopen", "c_new", "kr_new", "kI_new", "qI",
         "wI", "q_nope", "q_rope", "W_UK", "W_UV")


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
    if "toy" not in s and int(s["index_topk"]) != int(config["index_topk"]):
        raise ValueError("index_topk is never cut: shapes.index_topk "
                         f"{s['index_topk']} != {config['index_topk']}")
    return {"lens": tuple(sorted(int(n) for n in s["lens"])),
            "heads": int(src["num_attention_heads"]),
            "rank": int(src["kv_lora_rank"]), "rope": rope, "nope": nope,
            "v_dim": int(src["v_head_dim"]),
            "index_heads": int(src["index_n_heads"]),
            "index_dim": int(src["index_head_dim"]),
            "topk": int(s["index_topk"]),
            "scale": (nope + rope) ** -0.5 * mscale * mscale,
            "page": int(s["page_tokens"]), "groups": int(s["groups"]),
            "table_seed": int(s.get("table_seed", 0)),
            "layers": int(config["layers"]), "dtype": s["dtype"]}


def tags(config: dict) -> list:
    return [f"L{i}" for i in range(int(config["layers"]))]


def row_width(z: dict) -> int:
    """A stored latent row: its ``rank + rope`` numbers in whole lanes."""
    return -(-(z["rank"] + z["rope"]) // LANES) * LANES


def picked(z: dict) -> list:
    """Keys a sequence attends over: ``min(topk, L_b + 1)``."""
    return [min(z["topk"], n + 1) for n in z["lens"]]


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
    b, h, row = len(z["lens"]), z["heads"], row_width(z)
    ih, idim, page = z["index_heads"], z["index_dim"], z["page"]
    pages = max(1, sum(n // page for n in z["lens"]))
    return {"C": (pages, page, row), "Copen": (b, page, row),
            "KI": (pages, idim, page), "KIopen": (b, idim, page),
            "c_new": (b, z["rank"]), "kr_new": (b, z["rope"]),
            "kI_new": (b, idim), "qI": (b, ih, idim), "wI": (b, ih),
            "q_nope": (b, h, z["nope"]), "q_rope": (b, h, z["rope"]),
            "W_UK": (h, z["nope"], z["rank"]),
            "W_UV": (h, z["rank"], z["v_dim"])}


def expected_open(t: dict, page: int):
    """``(latent, index)`` open pages after the appends: row ``L_b % page``
    of sequence b's latent page becomes ``[c_new[b] ; k_rope_new[b] ; 0]``,
    column ``L_b % page`` of its index page ``kI_new[b]``."""
    new = jnp.concatenate([t["c_new"], t["kr_new"]], axis=1)
    new = jnp.pad(new, ((0, 0), (0, t["Copen"].shape[2] - new.shape[1])))
    rows = jnp.arange(new.shape[0])
    at = (t["lens"] - 1) % page
    return (t["Copen"].at[rows, at, :].set(new.astype(t["Copen"].dtype)),
            t["KIopen"].at[rows, :, at].set(
                t["kI_new"].astype(t["KIopen"].dtype)))


def _read(x, via):
    """``x`` in float32, rounded to the control's format first where there
    is one (``lax.reduce_precision``: a cast there and back the TPU compiler
    takes out, PERF.md section 2)."""
    x = x.astype(jnp.float32)
    return x if via is None else lax.reduce_precision(x, *via)


def index_reference(z: dict, t: dict, via=None):
    """``I`` float32 ``(batch, max_pages * page)`` of one layer, a page of
    keys at a time, ``NEG`` past a sequence's length."""
    page = z["page"]
    vis, table = t["lens"], t["table"]
    q, w = t["qI"].astype(jnp.float32), t["wI"].astype(jnp.float32)
    opened = expected_open(t, page)[1]
    open_tile = (vis - 1) // page

    def one_tile(j):
        sealed = t["KI"][table[:, j]]
        kt = _read(jnp.where((j == open_tile)[:, None, None], opened, sealed),
                   via)  # (batch, dim, page)
        s = jnp.einsum("bhd,bdk->bhk", q, kt)
        got = jnp.sum(jnp.maximum(s, 0.0) * w[:, :, None], axis=1)
        seen = (j * page + jnp.arange(page))[None, :] < vis[:, None]
        return jnp.where(seen, got, NEG)

    with jax.default_matmul_precision("highest"):
        tiles = lax.map(one_tile, jnp.arange(table.shape[1]))
    return jnp.moveaxis(tiles, 0, 1).reshape(vis.shape[0], -1)


def exact_selection(z: dict, scores):
    """``(positions (batch, topk), the topk-th largest score (batch,))`` of
    the reference's own selection (``lax.top_k``: of equal scores the lower
    position first); a sequence with fewer visible keys than ``topk`` has
    ``NEG`` for its ``topk``-th."""
    k = z["topk"]
    if scores.shape[1] < k:
        scores = jnp.pad(scores, ((0, 0), (0, k - scores.shape[1])),
                         constant_values=NEG)
    top, at = lax.top_k(scores, k)
    return at.astype(jnp.int32), top[:, -1]


def ordered_keys(scores):
    """uint32 keys that order as the float32 ``scores`` do (a negative
    score's bits turned over, the others' sign bit set)."""
    bits = lax.bitcast_convert_type(scores, jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def members_without_a_sort(z: dict, scores):
    """``(member (batch, n) bool, topk-th largest score (batch,))`` of the
    same selection as :func:`exact_selection`'s, found as
    ``references/attn_window_gqa.py`` finds its median (a sort costs the
    TPU's compiler a quarter of a minute an instance): the ``topk``-th
    largest key bit by bit from the top, the scores above it, and of those
    equal to it the first."""
    k = z["topk"]
    if scores.shape[1] < k:
        scores = jnp.pad(scores, ((0, 0), (0, k - scores.shape[1])),
                         constant_values=NEG)
    keys = ordered_keys(scores)

    def one_bit(i, kth):
        higher = kth | (jnp.uint32(1 << 31) >> i.astype(jnp.uint32))
        enough = jnp.sum(keys >= higher[:, None], axis=1) >= k
        return jnp.where(enough, higher, kth)

    kth = lax.fori_loop(0, 32, one_bit,
                        jnp.zeros((scores.shape[0],), jnp.uint32))
    above, equal = keys > kth[:, None], keys == kth[:, None]
    wanted = k - jnp.sum(above, axis=1)
    member = above | (equal & (jnp.cumsum(equal, axis=1) <= wanted[:, None]))
    value = jnp.max(jnp.where(equal, scores, NEG), axis=1)
    return member, value


def gathered(z: dict, t: dict, sel, via=None):
    """``(batch, topk, rank + rope)`` float32: the latent rows at positions
    ``sel`` of each sequence after the append, read through the table."""
    page = z["page"]
    opened = expected_open(t, page)[0]
    slot, at = sel // page, sel % page
    page_id = jnp.take_along_axis(
        t["table"], jnp.clip(slot, 0, t["table"].shape[1] - 1), axis=1)
    sealed = t["C"][page_id, at]
    in_open = jnp.take_along_axis(opened, at[:, :, None], axis=1)
    is_open = slot == ((t["lens"] - 1) // page)[:, None]
    got = jnp.where(is_open[:, :, None], in_open, sealed)
    return _read(got[:, :, :z["rank"] + z["rope"]], via)


def attention_reference(z: dict, t: dict, sel, via=None):
    """``o`` float32 ``(batch, heads, v_dim)`` of one layer over the
    selection ``sel`` ``(batch, topk)``, whose first ``min(topk, L_b + 1)``
    slots count."""
    f32 = jnp.float32
    rank = z["rank"]
    limit = jnp.minimum(z["topk"], t["lens"])
    rows = gathered(z, t, sel, via)
    with jax.default_matmul_precision("highest"):
        qt = jnp.concatenate(
            [jnp.einsum("bhd,hdc->bhc", t["q_nope"].astype(f32),
                        t["W_UK"].astype(f32)), t["q_rope"].astype(f32)],
            axis=2)
        s = z["scale"] * jnp.einsum("bhw,bkw->bhk", qt, rows)
        counts = jnp.arange(sel.shape[1])[None, :] < limit[:, None]
        p = jax.nn.softmax(jnp.where(counts[:, None, :], s, -jnp.inf), axis=2)
        o_lat = jnp.einsum("bhk,bkc->bhc", p, rows[:, :, :rank])
        return jnp.einsum("bhc,hcd->bhd", o_lat, t["W_UV"].astype(f32))


def layer_reference(z: dict, t: dict, via=None):
    """One layer by the reference alone: ``(o, selection, scores)``."""
    scores = index_reference(z, t, via)
    sel, _ = exact_selection(z, scores)
    return attention_reference(z, t, sel, via), sel, scores


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


def membership(sel, counts, width: int):
    """``(batch, width)`` int32: how often each position is among the
    slots of ``sel`` that count; a position outside ``0 .. width`` is
    dropped (and so missed by the count of its row)."""
    rows = jnp.broadcast_to(jnp.arange(sel.shape[0])[:, None], sel.shape)
    inside = counts & (sel >= 0) & (sel < width)
    return jnp.zeros((sel.shape[0], width), jnp.int32).at[
        rows, jnp.where(inside, sel, 0)].add(inside.astype(jnp.int32))


def selection_numbers(z: dict, t: dict, sel, scores, margin: float):
    """``[malformed rows, outside the margin, differs, widest excess]`` of a
    selection against the reference's ``scores``: the last is the margin
    that would leave nothing outside (a reading, not compared)."""
    vis = t["lens"]
    limit = jnp.minimum(z["topk"], vis)
    width = scores.shape[1]
    counts = jnp.arange(sel.shape[1])[None, :] < limit[:, None]
    member = membership(sel, counts, width)
    visible = jnp.arange(width)[None, :] < vis[:, None]
    good = jnp.sum(jnp.where(visible, member == 1, False), axis=1) == limit
    malformed = jnp.sum(~good | jnp.any(member > 1, axis=1))
    theirs, kth = members_without_a_sort(z, scores)
    picked_ = member > 0
    below = jnp.where(picked_ & visible, kth[:, None] - scores, -jnp.inf)
    above = jnp.where(~picked_ & visible, scores - kth[:, None], -jnp.inf)
    outside = jnp.sum(below > margin) + jnp.sum(above > margin)
    differs = jnp.sum(picked_ & ~theirs[:, :width] & visible)
    excess = jnp.maximum(jnp.max(below), jnp.max(above))
    return jnp.stack([malformed.astype(jnp.float32),
                      outside.astype(jnp.float32),
                      differs.astype(jnp.float32),
                      jnp.maximum(excess, 0.0)])


def _frozen(z: dict) -> tuple:
    return tuple(sorted(z.items()))


@lru_cache(maxsize=None)
def _programs(frozen: tuple):
    """``(draw, reference, gaps, whole)``: one layer's tensors from a key; one
    layer's reference scores and expected open pages (``via`` static); the
    numbers of one layer's outputs against them; one layer by the reference
    alone with its open pages (the control's and the sound layers')."""
    z = dict(frozen)
    dt = jnp.dtype(z["dtype"])
    width = z["rank"] + z["rope"]
    scaled = {"W_UK": z["nope"] ** -0.5, "W_UV": z["rank"] ** -0.5,
              "wI": (z["index_heads"] * z["index_dim"]) ** -0.5}

    @jax.jit
    def draw(seed, layer):
        # the device's own generator (``rbg``: the default ``threefry``
        # costs a checkout's first run most of a minute of compiling)
        key = jax.random.fold_in(jax.random.key(seed, impl="rbg"), layer)
        out = {}
        for i, (name, shape) in enumerate(sorted(shapes(z).items())):
            x = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32) * scaled.get(name, 1.0)
            if name in ("C", "Copen"):  # a row's tail past its width: zero
                x = jnp.where(jnp.arange(shape[2]) < width, x, 0.0)
            out[name] = x if name == "wI" else x.astype(dt)
        return out

    @partial(jax.jit, static_argnums=1)
    def reference(t, via):
        return (index_reference(z, t, via),) + expected_open(t, z["page"])

    @jax.jit
    def gaps(t, out, sel, opened, opened_keys, scores, ref_open,
             ref_open_keys):
        ref = attention_reference(z, t, sel)
        err = out.astype(jnp.float32) - ref
        err2, ref2 = jnp.sum(err * err, axis=2), jnp.sum(ref * ref, axis=2)
        floor = _median(jnp.sqrt(ref2))
        moved = (jnp.sum(jnp.any(opened != ref_open, axis=2))
                 + jnp.sum(jnp.any(opened_keys != ref_open_keys, axis=1)))
        return jnp.concatenate([
            selection_numbers(z, t, sel, scores, MARGIN),
            jnp.stack([
                jnp.sqrt(jnp.sum(err2) / jnp.sum(ref2)),
                jnp.max(jnp.sqrt(err2) / jnp.maximum(jnp.sqrt(ref2), floor)),
                moved.astype(jnp.float32)])])

    @partial(jax.jit, static_argnums=1)
    def whole(t, via):
        return layer_reference(z, t, via) + expected_open(t, z["page"])

    return draw, reference, gaps, whole


def _seed(seed: int):
    return jnp.uint32(seed & 0xFFFFFFFF)


#: the run's data, kept for the reference to read (one seed at a time: the
#: pools are 0.9 GB a layer and the executor holds the same arrays)
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
    """Every layer's ``(scores, expected open latent pages, expected open
    index pages)`` for one seed, computed once a run (each schedule
    compared reads the same)."""
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
    _, reference, gaps, _ = _programs(frozen)

    def spec(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype)

    t = {k: spec(v) for k, v in layer_tensors(like, 0).items()}
    o, sel = spec(like[f"{OUTPUT}.L0"]), spec(like[f"{SELECTED}.L0"])
    scores = jax.ShapeDtypeStruct(
        (t["lens"].shape[0], t["table"].shape[1] * z["page"]), jnp.float32)
    _COMPILED[frozen] = (
        reference.lower(t, None).compile(),
        gaps.lower(t, o, sel, t["Copen"], t["KIopen"], scores, t["Copen"],
                   t["KIopen"]).compile())


def _numbers(config: dict, seed: int, outputs_: dict) -> list:
    """Per layer the seven numbers of ``gaps``."""
    z = sizes(config)
    frozen = _frozen(z)
    data = make_data(config, seed)
    refs = _reference_of(frozen, seed)
    gaps = _COMPILED[frozen][1] if frozen in _COMPILED else _programs(
        frozen)[2]
    return jax.device_get([
        gaps(layer_tensors(data, i), outputs_[f"{OUTPUT}.{t}"],
             outputs_[f"{SELECTED}.{t}"], outputs_[f"{OPEN}.{t}"],
             outputs_[f"{OPEN_KEYS}.{t}"], *ref)
        for i, (t, ref) in enumerate(zip(tags(config), refs))])


def check(config: dict, seed: int, outputs_: dict) -> list:
    """The six numbers of the module's docstring."""
    z = sizes(config)
    got = _numbers(config, seed, outputs_)
    slots = z["layers"] * sum(picked(z))

    def total(i):
        return int(sum(g[i] for g in got))

    return [{"name": "dsa_select_malformed_rows", "value": total(0),
             "limit": 0},
            {"name": "dsa_selection_outside_margin", "value": total(1),
             "limit": 0},
            {"name": "dsa_selection_differs", "value": total(2),
             "limit": slots},
            {"name": "dsa_o_rms_gap",
             "value": float(max(g[4] for g in got)), "limit": RMS_LIMIT},
            {"name": "dsa_o_widest_row_gap",
             "value": float(max(g[5] for g in got)), "limit": ROW_LIMIT},
            {"name": "dsa_append_mismatched_rows", "value": total(6),
             "limit": 0}]


def readings(config: dict, seed: int, outputs_: dict) -> dict:
    """What the limits were set from (``tests/dsa_step1_on_chip.py``): the
    margin that would leave no selected or unselected position outside it
    (the worst layer), and, where ``outputs_`` holds the last layer's scores
    (``I``), the widest gap between them and the reference's over the
    visible keys."""
    z = sizes(config)
    got = _numbers(config, seed, outputs_)
    out = {"selection_widest_excess": float(max(g[3] for g in got))}
    if SCORES in outputs_:
        ref = _reference_of(_frozen(z), seed)[-1][0]
        mine = jnp.reshape(outputs_[SCORES], (ref.shape[0], -1))
        seen = jnp.arange(ref.shape[1])[None, :] < make_data(
            config, seed)["lens"][:, None]
        out["score_widest_gap"] = float(jnp.max(jnp.where(
            seen, jnp.abs(mine[:, :ref.shape[1]] - ref), 0.0)))
        out["score_rms"] = float(jnp.sqrt(
            jnp.sum(jnp.where(seen, ref * ref, 0.0)) / jnp.sum(seen)))
    return out


def _as_outputs(config: dict, seed: int, via) -> dict:
    z = sizes(config)
    data = make_data(config, seed)
    whole = _programs(_frozen(z))[3]
    out = {}
    for i, t in enumerate(tags(config)):
        o, sel, scores, opened, opened_keys = whole(layer_tensors(data, i),
                                                    via)
        out[f"{OUTPUT}.{t}"] = o.astype(jnp.dtype(z["dtype"]))
        out[f"{SELECTED}.{t}"] = sel
        out[f"{OPEN}.{t}"], out[f"{OPEN_KEYS}.{t}"] = opened, opened_keys
        out[SCORES] = scores
    return out


def control(config: dict, seed: int) -> dict:
    """The reference in the program's place, one precision down: both
    caches read as float8 where the configuration states bfloat16 (the
    selection made from the float8 index keys' scores, the attention over
    it from the float8 latents).  :func:`check` has to refuse it."""
    return _as_outputs(config, seed, FLOAT8_E4M3)


def sound(config: dict, seed: int) -> dict:
    """The reference's own float32 layers, rounded once to the
    configuration's dtype (tests: :func:`check` passes it)."""
    return _as_outputs(config, seed, None)
