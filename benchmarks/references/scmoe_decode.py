"""Plain reference of one decode step of LongCat-Flash-Lite's shortcut-connected
blocks, and the data of a run: sequences by chip, experts by chip, every
other weight on every chip.

Imports nothing of the program (its own copy of ``tenzing_tpu/models/
shortcut_moe_reference.py``'s equations; ``benchmarks/tests/test_scmoe.py``
holds the two together).  Float32 throughout at
``jax.default_matmul_precision("highest")``; no kernel, no slot, no
capacity.  Per token (``h`` the residual stream; block ``l``, sublayer ``i``)::

    for i in 0, 1:
        a  = RMSNorm_in[l,i](h)
        h  = h + o_proj[l,i]( MLA[l,i](a) )
        m  = RMSNorm_post[l,i](h)
        if i == 0:  s = MoE[l](m)
        h  = h + W_down[l,i]( silu(W_gate[l,i] m) * (W_up[l,i] m) )
    h = h + s

``MLA(a)``: ``cq = RMSNorm(a W_qa)``; ``[q_nope ; q_rope] = cq W_qb`` a head,
times ``(hidden / q_lora_rank)^0.5``; ``[c ; k_rope] = a W_kva``, ``c =
RMSNorm(c) (hidden / kv_lora_rank)^0.5``; rotary (interleaved pairs, yarn)
on ``q_rope`` and ``k_rope`` at position ``L_b``; the row ``[c ; k_rope]``
becomes row ``L_b`` of the cache; ``qt = [q_nope W_UK ; q_rope]``;
``softmax(scale qt C^T)`` over the ``L_b + 1`` rows; ``o = (p C[:, :rank])
W_UV``; ``o_proj``.  The cache is read through the chip's block table a page
of keys at a time with a running maximum, a block of 16 neighbouring
sequences at a time (``references/mla_paged_decode.py``'s order of sums).

``MoE(m)``: ``p = softmax(m W_r)`` over 256 + 128 outputs; the 12 largest of
``p + bias`` (equal scores to the lower index); ``w = 6 p`` of the picked,
not renormalised; ``s = sum over real picks of w FFN_e(m) + (sum over zero
picks of w) m``.  The experts are computed where they live: every chip
gathers the host's tokens (256 rows), runs each of its 64 experts over all
of them weighted by a mask, and the chips' sums are scattered back: no
expert weight crosses a chip (a block's are 4.8 GB).

**The data** (:func:`make_data`): every weight matrix normal over the square
root of its fan-in, but ``W_UK``, which is drawn that much smaller again by
the product of the three published factors on a latent score (the q-lora
scale 1.414, the kv-lora scale 2.449 and ``mscale^2`` 1.513: 5.24).  Drawn
without, a score's deviation is 8.7 where the other latent cells read 1.7:
the softmax then sees two or three keys, every rounding before it is
amplified, and step 1 on the chip read the program 7% from this reference
and the caches as float8 43% (PR 46); a trained model's ``kv_b_proj`` has
the factors absorbed.  With it the deviation is 2.0.

What is compared (:func:`check`), the worst block or layer reported:

* ``scmoe_h_rms_gap`` / ``scmoe_h_widest_token_gap``: every block's output
  ``h`` (the last is the step's) against the reference's: root of the
  summed squares of the gap over that of the reference, and the largest
  over tokens of ``|h_t - ref_t| / max(|ref_t|, median |ref|)``.  A token
  that went to a wrong expert, took a wrong weight or came back to a wrong
  place reads near 1 in the second.
* ``scmoe_s_rms_gap``: each expert block's output ``s`` against the
  reference's.  A missing zero term (the second control) reads here first.
* ``scmoe_expert_rms_gap``: the real experts' part of ``s``, which is ``s``
  less the step's own zero term (its ``zero_w`` times its ``m``), against
  the reference's sum over the real picks.  The identity experts' term is
  most of ``s`` (a third of the picks at full weight against experts'
  outputs that partly cancel), so slots carried as float8 (the control)
  move ``s`` by a percent and this number by several.
* ``scmoe_row_rms_gap``: the appended rows ``[c_new ; kr_new]`` against the
  reference's, every attention layer.
* ``scmoe_append_mismatched_rows``: sequences whose open page differs from
  what it was with the step's own row at column ``L_b`` (limit 0: the
  append copies).
* ``scmoe_tokens_left_out``: tokens whose 12th and 13th scores lie closer
  than ``TIE`` in some block: rounding decides which of the two such a
  token goes to, so they are left out of the numbers above, and the limit
  on their count keeps the comparison from passing by leaving out.
* ``chips_without_a_shard``: chips whose part of the output lies elsewhere.
"""

from __future__ import annotations

import math
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

#: the step's output is ``h.B<blocks>``: what ``chips_without_a_shard`` reads
OUTPUT = "h"
AXIS = "ep"
TIE = 1e-7
NEG = -1e30
SEQ_BLOCK = 16
#: limits of the comparison (PERF.md section 2; PR 46).  Each lies between
#: the largest sound reading and the control's smallest, with room on both
#: sides where the control moves the number at all.  Sound, at the cell's
#: own size on the chip (my chip run, PR 46: three whole runs on three
#: seeds, naive and eight finalists): h 0.00731-0.00739, widest token
#: 0.00957-0.01026, s 0.00737-0.00765, experts 0.01202-0.01240, rows
#: 0.00678-0.00689.  Before them, what the limits were set from: the
#: program against this reference at published attention widths, four heads
#: and 16 sequences a chip in bfloat16 (*CPU*, two seeds): h 0.0071-0.0072,
#: widest token 0.0093-0.0113, s 0.0080-0.0083, experts 0.0113-0.0118, rows
#: 0.0068-0.0070; the control there (slots as float8, scores from bfloat16)
#: h 0.021-0.023, token 0.059-0.089, s 0.038-0.051, experts 0.072-0.078,
#: rows 0.015-0.017, and on the chip at the cell's size (step 1, three
#: seeds) experts 0.078-0.098, s 0.042-0.052, token 0.061-0.089, h
#: 0.011-0.015.  So the control is refused by the experts', the token's and
#: s's limits and not by h's or the rows'; those two are for the caches read
#: as float8 (h 0.43, rows 0.31 on the chip) and a fault upstream of both.
H_RMS_LIMIT = 0.018
H_TOKEN_LIMIT = 0.035
S_RMS_LIMIT = 0.022
EXPERT_RMS_LIMIT = 0.035
ROW_RMS_LIMIT = 0.018
LEFT_OUT_SHARE = 0.02
FLOAT8_E4M3 = (4, 3)
BFLOAT16 = (8, 7)

ATTN_W = ("Wn_in", "Wqa", "Wqn", "Wqb", "Wkva", "Wkvn", "W_UK", "W_UV", "Wo")
FFN_W = ("Wn_post", "Wgate", "Wup", "Wdown")
MOE_W = ("Wg", "W1", "W3", "W2")


def sizes(config: dict) -> dict:
    """The step's sizes: the published widths (top-level keys, as the
    model's ``config.json`` names them) and the run's (``shapes``); a
    rehearsal's ``toy`` group stands in for the widths."""
    s = config["shapes"]
    src = {**config, **s.get("toy", {})}
    rs = config["rope_scaling"]
    nope, rope = int(src["qk_nope_head_dim"]), int(src["qk_rope_head_dim"])
    ranks, held = int(s["ranks"]), int(s["experts_per_shard"])
    if ranks * held != int(src["n_routed_experts"]):
        raise ValueError(f"{src['n_routed_experts']} experts over {ranks} "
                         f"ranks of {held}")
    mscale = 0.1 * float(rs["mscale_all_dim"]) * math.log(
        float(rs["factor"])) + 1.0
    return {"lens": tuple(sorted(int(n) for n in s["lens"])),
            "blocks": int(config["layers"]), "ranks": ranks,
            "d": int(src["hidden_size"]), "ffn": int(src["ffn_hidden_size"]),
            "f": int(src["expert_ffn_hidden_size"]),
            "experts": int(src["n_routed_experts"]), "held": held,
            "zero": int(src["zero_expert_num"]), "top_k": int(src["moe_topk"]),
            "route_scale": float(config["routed_scaling_factor"]),
            "heads": int(src["num_attention_heads"]),
            "rank": int(src["kv_lora_rank"]),
            "q_rank": int(src["q_lora_rank"]), "rope": rope, "nope": nope,
            "v_dim": int(src["v_head_dim"]),
            "eps": float(config["rms_norm_eps"]),
            "scale": (nope + rope) ** -0.5 * mscale * mscale,
            "theta": float(config["rope_theta"]),
            "factor": float(rs["factor"]),
            "original": int(rs["original_max_position_embeddings"]),
            "beta_fast": float(rs["beta_fast"]),
            "beta_slow": float(rs["beta_slow"]),
            "page": int(s["page_tokens"]), "groups": int(s["groups"]),
            "fold_pages": int(s["fold_pages"]),
            "capacity_factor": float(s["capacity_factor"]),
            "synth": bool(s.get("synth", False)),
            "table_seed": int(s.get("table_seed", 0)), "dtype": s["dtype"]}


def mesh_of(config: dict) -> Mesh:
    """The reference's own ranks: the first ``ranks`` devices JAX has."""
    return Mesh(np.array(jax.devices()[:int(config["shapes"]["ranks"])]),
                (AXIS,))


def attn_tags(z: dict) -> list:
    return [f"B{l}.a{i}" for l in range(z["blocks"]) for i in (0, 1)]


def frequencies(z: dict) -> np.ndarray:
    """``(rope / 2,)``: the angle a position turns pair ``j`` by (yarn)."""
    rope, theta = z["rope"], z["theta"]
    j = np.arange(rope // 2, dtype=np.float64)
    f = theta ** (-2.0 * j / rope)

    def c(beta):
        return rope * math.log(z["original"] / (2 * math.pi * beta)) / (
            2 * math.log(theta))

    lo = min(max(math.floor(c(z["beta_fast"])), 0), rope - 1)
    hi = min(max(math.ceil(c(z["beta_slow"])), 0), rope - 1)
    r = np.clip((j - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return (f * ((1.0 - r) + r / z["factor"])).astype(np.float32)


def block_table(z: dict) -> np.ndarray:
    """``(ranks * batch, max_pages)``: a chip's sealed pages, in its batch's
    order, are a random permutation of its own pool (another a chip); a slot
    past a sequence's sealed pages holds 0."""
    sealed = [n // z["page"] for n in z["lens"]]
    tables = []
    for chip in range(z["ranks"]):
        perm = np.random.default_rng(z["table_seed"] + chip).permutation(
            max(1, sum(sealed)))
        table = np.zeros((len(sealed), max(sealed) + 1), np.int32)
        at = 0
        for b, n in enumerate(sealed):
            table[b, :n] = perm[at:at + n]
            at += n
        tables.append(table)
    return np.concatenate(tables)


def local_shapes(z: dict) -> dict:
    """``{group: {kind: (one chip's shape, dtype, cut by chip?)}}`` of the
    drawn tensors: an attention layer's, a dense FFN's, an expert block's."""
    b, h, d, dt = len(z["lens"]), z["heads"], z["d"], z["dtype"]
    w, f32 = z["rank"] + z["rope"], "float32"
    pages = max(1, sum(n // z["page"] for n in z["lens"]))
    n_r = z["experts"] + z["zero"]
    return {
        "attn": {"C": ((pages, w, z["page"]), dt, True),
                 "Copen": ((b, w, z["page"]), dt, True),
                 "Wn_in": ((d,), f32, False),
                 "Wqa": ((d, z["q_rank"]), dt, False),
                 "Wqn": ((z["q_rank"],), f32, False),
                 "Wqb": ((z["q_rank"], h * (z["nope"] + z["rope"])), dt,
                         False),
                 "Wkva": ((d, w), dt, False),
                 "Wkvn": ((z["rank"],), f32, False),
                 "W_UK": ((h, z["nope"], z["rank"]), dt, False),
                 "W_UV": ((h, z["rank"], z["v_dim"]), dt, False),
                 "Wo": ((h * z["v_dim"], d), dt, False)},
        "ffn": {"Wn_post": ((d,), f32, False),
                "Wgate": ((d, z["ffn"]), dt, False),
                "Wup": ((d, z["ffn"]), dt, False),
                "Wdown": ((z["ffn"], d), dt, False)},
        "moe": {"Wg": ((d, n_r), f32, False),
                "W1": ((z["held"], d, z["f"]), dt, True),
                "W3": ((z["held"], d, z["f"]), dt, True),
                "W2": ((z["held"], z["f"], d), dt, True)}}


def _spec(shape, cut: bool):
    return P(AXIS, *([None] * (len(shape) - 1))) if cut else P(
        *([None] * len(shape)))


def _read(x, via):
    """``x`` as it reads through a format of ``via`` (exponent, mantissa)
    bits: ``lax.reduce_precision`` (a cast there and back the TPU's
    compiler takes out)."""
    return x if via is None else lax.reduce_precision(x, *via)


def _norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rotate(x, pos, freq):
    """Pairs ``(2j, 2j+1)`` of ``x (batch, ..., rope)`` turned by ``pos[b]
    freq[j]``."""
    angle = pos[:, None] * freq[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (freq.shape[0],)
    cos, sin = jnp.cos(angle).reshape(shape), jnp.sin(angle).reshape(shape)
    pairs = x.reshape(x.shape[:-1] + (freq.shape[0], 2))
    even, odd = pairs[..., 0], pairs[..., 1]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


# -- one chip's part of a block -------------------------------------------------

def _attention(z: dict, t: dict, a, lens, table, cache_via):
    """``(o W_o (batch, hidden), new rows (batch, width))`` of one layer for
    one chip's normed inputs ``a``; ``t`` the layer's tensors under their
    plain names, ``lens`` the visible keys ``L_b + 1``."""
    f32 = jnp.float32
    page, rank, nope = z["page"], z["rank"], z["nope"]
    g = lambda k: t[k].astype(f32)
    batch = a.shape[0]
    freq = jnp.asarray(frequencies(z))
    pos = (lens - 1).astype(f32)
    cq = _norm(a @ g("Wqa"), g("Wqn"), z["eps"])
    q = (cq @ g("Wqb")).reshape(batch, z["heads"], nope + z["rope"]) * (
        z["d"] / z["q_rank"]) ** 0.5
    row = a @ g("Wkva")
    c = _norm(row[:, :rank], g("Wkvn"), z["eps"]) * (z["d"] / rank) ** 0.5
    new = jnp.concatenate([c, _rotate(row[:, rank:], pos, freq)], axis=1)
    qt = jnp.concatenate(
        [jnp.einsum("bhd,hdc->bhc", q[..., :nope], g("W_UK")),
         _rotate(q[..., nope:], pos, freq)], axis=2)
    # the open pages with the reference's own new row in: what a float32
    # step would attend over
    opened_all = t["Copen"].astype(f32).at[
        jnp.arange(batch), :, (lens - 1) % page].set(new)
    n = SEQ_BLOCK if batch % SEQ_BLOCK == 0 else batch

    def block(i):
        at = i * n
        vis = lax.dynamic_slice_in_dim(lens, at, n)
        rows = lax.dynamic_slice_in_dim(table, at, n)
        qb = lax.dynamic_slice_in_dim(qt, at, n)
        opened = lax.dynamic_slice_in_dim(opened_all, at, n)
        open_tile = (vis - 1) // page

        def one_tile(j, carry):
            acc, m, l = carry
            sealed = t["C"][rows[:, jnp.minimum(j, rows.shape[1] - 1)]]
            kt = _read(jnp.where((j == open_tile)[:, None, None], opened,
                                 sealed.astype(f32)), cache_via)
            seen = (j * page + jnp.arange(page))[None, :] < vis[:, None]
            s = z["scale"] * jnp.einsum("bhw,bwk->bhk", qb, kt)
            s = jnp.where(seen[:, None, :], s, NEG)
            m_new = jnp.maximum(m, jnp.max(s, axis=2, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.where(seen[:, None, :], jnp.exp(s - m_new), 0.0)
            return (acc * alpha + jnp.einsum("bhk,bck->bhc", p, kt[:, :rank]),
                    m_new, l * alpha + jnp.sum(p, axis=2, keepdims=True))

        shape = (n, qb.shape[1])
        acc, _, l = lax.fori_loop(
            0, jnp.max(open_tile) + 1, one_tile,
            (jnp.zeros(shape + (rank,), f32),
             jnp.full(shape + (1,), NEG, f32),
             jnp.zeros(shape + (1,), f32)))
        return acc / l

    o_lat = lax.map(block, jnp.arange(batch // n))
    o_lat = o_lat.reshape((batch,) + o_lat.shape[2:])
    o = jnp.einsum("bhc,hcd->bhd", o_lat, g("W_UV"))
    return o.reshape(batch, -1) @ g("Wo"), new


def _mlp(x, w1, w3, w2):
    f32 = jnp.float32
    return (jax.nn.silu(x @ w1.astype(f32)) * (x @ w3.astype(f32))) @ \
        w2.astype(f32)


def _moe(z: dict, t: dict, m, slot_via, score_via, zero_term: bool):
    """``(s (batch, hidden), the real experts' part of it, the sum of the
    zero picks' weights (batch, 1), clear (batch,))`` of one chip's tokens
    ``m``:
    the chip's experts over the host's tokens, the chips' sums scattered
    back.  ``slot_via``: tokens reach the real experts, and their outputs
    come back, rounded to that format; ``score_via``: the router's product
    reads both its sides through that format."""
    k, n_e = z["top_k"], z["experts"]
    wr = t["Wg"].astype(jnp.float32)
    p = jax.nn.softmax(_read(m, score_via) @ _read(wr, score_via), axis=1)
    top, sel = lax.top_k(p, k + 1)  # e_score_correction_bias: zeros
    clear = top[:, k - 1] - top[:, k] >= TIE
    sel = sel[:, :k]
    wts = z["route_scale"] * top[:, :k]
    zero_w = jnp.sum(jnp.where(sel >= n_e, wts, 0.0), axis=1, keepdims=True)
    m_all, sel_all, w_all = (lax.all_gather(x, AXIS, axis=0, tiled=True)
                             for x in (m, sel, wts))
    mine = lax.axis_index(AXIS) * z["held"] + jnp.arange(z["held"])
    # (held, tokens): each of the chip's experts' weight for every token
    mask = jnp.sum(jnp.where(sel_all[None] == mine[:, None, None],
                             w_all[None], 0.0), axis=2)
    sent = _read(m_all, slot_via)

    def add_expert(y, e):
        we, w1e, w3e, w2e = e
        return y + we[:, None] * _read(_mlp(sent, w1e, w3e, w2e),
                                       slot_via), None

    y, _ = lax.scan(add_expert, jnp.zeros_like(m_all),
                    (mask, t["W1"], t["W3"], t["W2"]))
    s = lax.psum_scatter(y, AXIS, scatter_dimension=0, tiled=True)
    return (s + zero_w * m if zero_term else s), s, zero_w, clear


def _block(z: dict, via: tuple, h, lens, table, attn0, ffn0, moe, attn1,
           ffn1):
    """One chip's part of a block from its input ``h`` (float32): ``(h out,
    m0, s, the real experts' part of s, the zero picks' weight, rows0,
    rows1, clear)``."""
    slot_via, score_via, cache_via, zero_term = via
    rows, s, real, zero_w, m0, clear = [], None, None, None, None, None
    with jax.default_matmul_precision("highest"):
        for i, (at, ft) in enumerate(((attn0, ffn0), (attn1, ffn1))):
            a = _norm(h, at["Wn_in"], z["eps"])
            o, new = _attention(z, at, a, lens, table, cache_via)
            rows.append(new)
            h = h + o
            m = _norm(h, ft["Wn_post"], z["eps"])
            if i == 0:
                m0 = m
                s, real, zero_w, clear = _moe(z, moe, m, slot_via, score_via,
                                              zero_term)
            h = h + _mlp(m, ft["Wgate"], ft["Wup"], ft["Wdown"])
        return h + s, m0, s, real, zero_w, rows[0], rows[1], clear


# -- the programs ---------------------------------------------------------------

def _frozen(z: dict) -> tuple:
    return tuple(sorted(z.items()))


SOUND = (None, None, None, True)


@lru_cache(maxsize=None)
def _programs(mesh: Mesh, frozen: tuple):
    """``(draw, block, gaps, pages)``: one group's tensors from a key, every
    chip drawing its own part; one block of the reference over the whole
    mesh (``via`` static); a compared tensor's numbers; the open pages'
    check."""
    z = dict(frozen)
    shapes = local_shapes(z)
    by_seq = P(AXIS, None)
    # W_UK: drawn at its fan-in, times the inverse of the three published
    # factors on the latent score (q-lora scale, kv-lora scale, mscale^2): a
    # trained model's kv_b absorbs them; drawn without, the scores' deviation
    # is 8.7 and the softmax sees two or three keys (module docstring, data)
    softmax_gain = ((z["d"] / z["q_rank"]) ** 0.5 * (z["d"] / z["rank"]) ** 0.5
                    * z["scale"] * (z["nope"] + z["rope"]) ** 0.5)
    scaled = {"Wqa": z["d"], "Wqb": z["q_rank"], "Wkva": z["d"],
              "W_UK": z["nope"] * softmax_gain ** 2, "W_UV": z["rank"],
              "Wo": z["heads"] * z["v_dim"], "Wgate": z["d"], "Wup": z["d"],
              "Wdown": z["ffn"], "W1": z["d"], "W3": z["d"], "W2": z["f"]}
    kv_scale = (z["d"] / z["rank"]) ** 0.5

    def draw_local(group, seed, index):
        # the device's own generator (``rbg``: the default ``threefry``
        # costs a checkout's first run most of a minute of compiling)
        key = jax.random.fold_in(jax.random.key(seed, impl="rbg"), index)
        out = {}
        for i, (kind, (shape, dtype, cut)) in enumerate(
                sorted(shapes[group].items())):
            k = jax.random.fold_in(key, i)
            if cut:  # a chip's own part: its own numbers
                k = jax.random.fold_in(k, 1 + lax.axis_index(AXIS))
            flat = (int(np.prod(shape[:-1])), shape[-1])
            x = jax.random.normal(k, flat, jnp.float32)
            if kind in ("Wn_in", "Wn_post", "Wqn", "Wkvn"):
                x = 1.0 + 0.1 * x
            elif kind == "Wg":  # a balanced router: columns of unit length
                x = x / jnp.linalg.norm(x, axis=0, keepdims=True)
            elif kind in ("C", "Copen"):
                # rows as they lie there: columns, the latent part normed
                # and scaled, the rope part rotated (a normal draw stays one)
                x = x.reshape(shape)
                x = jnp.concatenate([x[:, :z["rank"]] * kv_scale,
                                     x[:, z["rank"]:]], axis=1)
            else:
                x = x / np.sqrt(scaled[kind])
            out[kind] = x.reshape(shape).astype(jnp.dtype(dtype))
        return out

    def draw_of(group):
        specs = {kind: _spec(shape, cut)
                 for kind, (shape, _, cut) in shapes[group].items()}
        return jax.jit(jax.shard_map(
            partial(draw_local, group), mesh=mesh, in_specs=(P(), P()),
            out_specs=specs, check_vma=False))

    draw = {group: draw_of(group) for group in shapes}

    @jax.jit
    def draw_h(seed):
        def local(seed):
            key = jax.random.fold_in(jax.random.key(seed, impl="rbg"),
                                     1000 + lax.axis_index(AXIS))
            return jax.random.normal(
                key, (len(z["lens"]), z["d"]), jnp.float32).astype(
                    jnp.dtype(z["dtype"]))
        return jax.shard_map(local, mesh=mesh, in_specs=(P(),),
                             out_specs=by_seq, check_vma=False)(seed)

    group_specs = {group: {kind: _spec(shape, cut)
                           for kind, (shape, _, cut) in shapes[group].items()}
                   for group in shapes}

    @partial(jax.jit, static_argnums=0)
    def block(via, h, lens, table, attn0, ffn0, moe, attn1, ffn1):
        return jax.shard_map(
            partial(_block, z, via), mesh=mesh,
            in_specs=(by_seq, P(AXIS), by_seq, group_specs["attn"],
                      group_specs["ffn"], group_specs["moe"],
                      group_specs["attn"], group_specs["ffn"]),
            out_specs=(by_seq,) * 7 + (P(AXIS),),
            check_vma=False)(h, lens, table, attn0, ffn0, moe, attn1, ffn1)

    @jax.jit
    def gaps(out, ref, clear):
        """``[rms gap, widest row gap]`` of rows ``out`` against ``ref``
        over the clear rows."""
        keep = clear[:, None]
        err = jnp.where(keep, out.astype(jnp.float32) - ref, 0.0)
        ref = jnp.where(keep, ref, 0.0)
        err2, ref2 = jnp.sum(err * err, axis=1), jnp.sum(ref * ref, axis=1)
        floor = jnp.median(jnp.sqrt(ref2))
        return jnp.stack([
            jnp.sqrt(jnp.sum(err2) / jnp.sum(ref2)),
            jnp.max(jnp.sqrt(err2) / jnp.maximum(jnp.sqrt(ref2), floor))])

    @jax.jit
    def pages(opened, before, c_new, kr_new, lens):
        """Sequences whose open page is not what it was with the step's own
        new row at column ``L_b``."""
        new = jnp.concatenate([c_new, kr_new], axis=1).astype(before.dtype)
        want = before.at[jnp.arange(before.shape[0]), :,
                         (lens - 1) % z["page"]].set(new)
        return jnp.sum(jnp.any((opened != want).reshape(
            opened.shape[0], -1), axis=1)).astype(jnp.float32)

    return draw, draw_h, block, gaps, pages


def _of(config: dict):
    z = sizes(config)
    return z, _programs(mesh_of(config), _frozen(z))


def _seed(seed: int):
    return jnp.uint32(seed & 0xFFFFFFFF)


#: the run's data, kept for the reference to read (one seed at a time: the
#: executor holds the same arrays)
_DATA = {}
#: the reference's forward of the run's data, computed once a run
_FORWARD = {}


def make_data(config: dict, seed: int) -> dict:
    """Every input of the step under the program's names: ``h.B0``, ``lens``
    (visible keys, ``L_b + 1``), ``table``, an attention layer's
    ``<kind>.B<l>.a<i>``, a dense FFN's ``<kind>.B<l>.f<i>``, an expert
    block's ``B<l>.moe.<kind>``; each chip's part drawn on that chip."""
    z, (draw, draw_h, *_) = _of(config)
    key = (_frozen(z), seed)
    if key not in _DATA:
        _DATA.clear()
        _FORWARD.clear()
        mesh = mesh_of(config)
        by_seq = NamedSharding(mesh, P(AXIS))
        lens = np.tile(np.asarray([n + 1 for n in z["lens"]], np.int32),
                       z["ranks"])
        data = {"h.B0": draw_h(_seed(seed)),
                "lens": jax.device_put(lens, by_seq),
                "table": jax.device_put(
                    block_table(z), NamedSharding(mesh, P(AXIS, None)))}
        at = 0
        for l in range(z["blocks"]):
            for i in (0, 1):
                for group, tag in (("attn", f"B{l}.a{i}"),
                                   ("ffn", f"B{l}.f{i}")):
                    made = draw[group](_seed(seed), jnp.int32(at))
                    data.update({f"{k}.{tag}": v for k, v in made.items()})
                    at += 1
            made = draw["moe"](_seed(seed), jnp.int32(at))
            data.update({f"B{l}.moe.{k}": v for k, v in made.items()})
            at += 1
        _DATA[key] = data
    return dict(_DATA[key])


def _group(data: dict, kinds, tag: str, prefix: bool = False) -> dict:
    return {k: data[f"{tag}.{k}" if prefix else f"{k}.{tag}"] for k in kinds}


def forward(config: dict, seed: int, via: tuple = SOUND) -> dict:
    """The reference's step on the run's data: ``{"h": [every block's
    output], "m0": [every block's router input], "s": [...], "experts":
    [the real experts' part of each s], "rows": {tag: appended rows},
    "clear": tokens no near-tie touched in any block}``,
    float32, rows by chip.  The sound one is kept for the run."""
    z, (_, _, block, *_) = _of(config)
    key = (_frozen(z), seed, via)
    if key in _FORWARD:
        return _FORWARD[key]
    data = make_data(config, seed)
    out = {"h": [], "m0": [], "s": [], "experts": [], "zero_w": [],
           "rows": {}, "clear": None}
    h = data["h.B0"].astype(jnp.float32)
    for l in range(z["blocks"]):
        parts = []
        for i in (0, 1):
            parts += [_group(data, ATTN_W + ("C", "Copen"), f"B{l}.a{i}"),
                      _group(data, FFN_W, f"B{l}.f{i}")]
        moe = _group(data, MOE_W, f"B{l}.moe", prefix=True)
        h, m0, s, real, zero_w, r0, r1, clear = block(
            via, h, data["lens"], data["table"], parts[0], parts[1], moe,
            parts[2], parts[3])
        out["h"].append(h)
        out["m0"].append(m0)
        out["s"].append(s)
        out["experts"].append(real)
        out["zero_w"].append(zero_w)
        out["rows"][f"B{l}.a0"], out["rows"][f"B{l}.a1"] = r0, r1
        out["clear"] = clear if out["clear"] is None else out["clear"] & clear
    if via == SOUND:
        _FORWARD[key] = out
    return out


def router_inputs(config: dict, seed: int) -> list:
    """Every block's router input of the float32 forward (rows by chip):
    what the program's set-up negotiation takes its selection from."""
    return forward(config, seed)["m0"]


def precompile(config: dict, like: dict) -> None:
    """The comparison's programs are small and the forward has run at
    set-up (:func:`router_inputs`): compile the comparisons for outputs
    placed as ``like`` by running them once on it."""
    _numbers(config, None, like, against=like)


def _numbers(config: dict, seed, outputs: dict, against=None) -> dict:
    z, (_, _, _, gaps, pages) = _of(config)
    if against is None:
        ref = forward(config, seed)
        data = make_data(config, seed)
    else:  # shapes only: the outputs against themselves
        ref = {"h": [against[f"h.B{l + 1}"].astype(jnp.float32)
                     for l in range(z["blocks"])],
               "s": [against[f"s.B{l}"].astype(jnp.float32)
                     for l in range(z["blocks"])],
               "experts": [against[f"s.B{l}"].astype(jnp.float32)
                           for l in range(z["blocks"])],
               "rows": {t: jnp.concatenate(
                   [against[f"c_new.{t}"], against[f"kr_new.{t}"]],
                   axis=1).astype(jnp.float32) for t in attn_tags(z)},
               "clear": against["lens"] > 0}
        data = against
    clear = ref["clear"]
    by_seq = NamedSharding(mesh_of(config), P(AXIS, None))

    def where_it_should_lie(x):
        # an output that lies elsewhere is compared where it should have
        # lain (chips_without_a_shard says that it did not)
        if x.sharding.is_equivalent_to(by_seq, x.ndim):
            return x
        return jax.device_put(x, by_seq)

    h = [gaps(where_it_should_lie(outputs[f"h.B{l + 1}"]), ref["h"][l], clear)
         for l in range(z["blocks"])]
    s = [gaps(outputs[f"s.B{l}"], ref["s"][l], clear)
         for l in range(z["blocks"])]
    real = [gaps(_experts_part(outputs, l), ref["experts"][l], clear)
            for l in range(z["blocks"])]
    rows = [gaps(jnp.concatenate([outputs[f"c_new.{t}"],
                                  outputs[f"kr_new.{t}"]], axis=1),
                 ref["rows"][t], clear) for t in attn_tags(z)]
    moved = [pages(outputs[f"Copen.{t}"], data[f"Copen.{t}"],
                   outputs[f"c_new.{t}"], outputs[f"kr_new.{t}"],
                   data["lens"]) for t in attn_tags(z)]
    h, s, real, rows, moved, left = jax.device_get(
        [h, s, real, rows, moved, jnp.sum(~clear)])
    return {"scmoe_h_rms_gap": float(max(g[0] for g in h)),
            "scmoe_h_widest_token_gap": float(max(g[1] for g in h)),
            "scmoe_s_rms_gap": float(max(g[0] for g in s)),
            "scmoe_expert_rms_gap": float(max(g[0] for g in real)),
            "scmoe_row_rms_gap": float(max(g[0] for g in rows)),
            "scmoe_append_mismatched_rows": int(sum(moved)),
            "scmoe_tokens_left_out": int(left)}


@jax.jit
def _less_zero_term(s, zero_w, m):
    return s.astype(jnp.float32) - zero_w * m.astype(jnp.float32)


def _experts_part(outputs: dict, block: int):
    """The real experts' part of a step's ``s``: ``s`` less the step's own
    zero term, the sum of its zero picks' weights times its router input."""
    return _less_zero_term(outputs[f"s.B{block}"],
                           outputs[f"B{block}.moe.zero_w_0"],
                           outputs[f"m.B{block}.f0"])


def check(config: dict, seed: int, outputs: dict) -> list:
    """The numbers of the module's docstring, each beside its limit."""
    z = sizes(config)
    got = _numbers(config, seed, outputs)
    out = outputs[f"h.B{z['blocks']}"]
    owners = {s.device for s in out.addressable_shards}
    n_tokens = z["ranks"] * len(z["lens"])
    limits = {"scmoe_h_rms_gap": H_RMS_LIMIT,
              "scmoe_h_widest_token_gap": H_TOKEN_LIMIT,
              "scmoe_s_rms_gap": S_RMS_LIMIT,
              "scmoe_expert_rms_gap": EXPERT_RMS_LIMIT,
              "scmoe_row_rms_gap": ROW_RMS_LIMIT,
              "scmoe_append_mismatched_rows": 0,
              "scmoe_tokens_left_out": int(np.ceil(LEFT_OUT_SHARE * n_tokens))}
    return [{"name": name, "value": got[name], "limit": limit}
            for name, limit in limits.items()] + [
        {"name": "chips_without_a_shard",
         "value": z["ranks"] - len(owners), "limit": 0}]


def _as_outputs(config: dict, seed: int, via: tuple) -> dict:
    """The reference in the program's place: its forward's tensors under
    the program's names, rounded once to the configuration's dtype; the
    open pages with its own rows in."""
    z = sizes(config)
    dt = jnp.dtype(z["dtype"])
    data = make_data(config, seed)
    ref = forward(config, seed, via)
    out = {}
    for l in range(z["blocks"]):
        out[f"h.B{l + 1}"] = ref["h"][l].astype(dt)
        out[f"s.B{l}"] = ref["s"][l].astype(dt)
        # the zero term as the step would hold it: its weight and its token
        m = ref["m0"][l].astype(dt)
        out[f"m.B{l}.f0"] = m
        out[f"B{l}.moe.zero_w_0"] = ref["zero_w"][l]
    for t in attn_tags(z):
        new = ref["rows"][t].astype(dt)
        out[f"c_new.{t}"], out[f"kr_new.{t}"] = (new[:, :z["rank"]],
                                                 new[:, z["rank"]:])
        before = data[f"Copen.{t}"]
        out[f"Copen.{t}"] = before.at[
            jnp.arange(before.shape[0]), :,
            (data["lens"] - 1) % z["page"]].set(new)
    return out


def control(config: dict, seed: int) -> dict:
    """The reference in the program's place, one precision down: the expert
    slots travel as float8 (tokens to the real experts, their outputs back)
    where the configuration states bfloat16, and the router's scores are
    made from bfloat16 where it states float32.  :func:`check` has to
    refuse it."""
    return _as_outputs(config, seed, (FLOAT8_E4M3, BFLOAT16, None, True))


def zero_control(config: dict, seed: int) -> dict:
    """The reference without the zero picks' term: :func:`check` has to
    refuse it."""
    return _as_outputs(config, seed, (None, None, None, False))


def cache_control(config: dict, seed: int) -> dict:
    """The latent caches read as float8 (``mla_paged_decode.py``'s control)."""
    return _as_outputs(config, seed, (None, None, FLOAT8_E4M3, True))


def sound(config: dict, seed: int) -> dict:
    """The reference's own float32 step, rounded once to the
    configuration's dtype (tests: :func:`check` passes it)."""
    return _as_outputs(config, seed, SOUND)
