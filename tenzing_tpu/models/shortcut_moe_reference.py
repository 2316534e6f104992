"""Plain reference of one decode step of shortcut-connected expert blocks
(``models/shortcut_moe.py``): ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")``, no kernel, no page, no slot, no
capacity, no mesh; a sequence's attention over its dense cache one sequence
at a time, a token's experts by a dense one-hot product over all of them.

It follows the published block line by line (per token; ``h`` the residual
stream; block ``l``, sublayer ``i`` in 0, 1)::

    for i in 0, 1:
        a  = RMSNorm_in[l,i](h)
        h  = h + o_proj[l,i]( MLA[l,i](a) )
        m  = RMSNorm_post[l,i](h)
        if i == 0:  s = MoE[l](m)
        h  = h + W_down[l,i]( silu(W_gate[l,i] m) * (W_up[l,i] m) )
    h = h + s

``MLA(a)``, one decode step: ``cq = RMSNorm(a W_qa)``; ``[q_nope ; q_rope]
= cq W_qb`` a head, times ``(hidden / q_lora_rank)^0.5``; ``[c ; k_rope] = a
W_kva``, ``c = RMSNorm(c) (hidden / kv_lora_rank)^0.5``; rotary on ``q_rope``
and ``k_rope`` at position ``L_b`` (interleaved pairs, yarn frequencies);
the row ``[c ; k_rope]`` becomes row ``L_b`` of the cache; then the
*published*, unabsorbed order of sums: a cached row's key a head is ``[c
W_UK^T ; k_rope]`` and its value ``c W_UV``; ``softmax(scale . q . k)`` over
the ``L_b + 1`` rows; ``o`` the weighted values; ``o_proj``.

``MoE(m)``: ``p = softmax(m W_r)`` over the ``n_experts + zero_experts``
outputs; the ``top_k`` largest of ``p + bias`` (equal scores to the lower
index); weights ``w = routed_scale . p`` of the picked, **not**
renormalised; ``s = sum over real picks of w . FFN_e(m) + (sum over zero
picks of w) . m``.

**Departures**, each the program's too: the cached rows are data as they
would lie there; the lengths do not advance; ``W_UK`` / ``W_UV`` are given
a head as they are absorbed, not as one ``kv_b_proj``.
"""

from __future__ import annotations

import numpy as np

from tenzing_tpu.models.shortcut_moe import (
    ScMoEArgs,
    attn_tag,
    ffn_tag,
    moe_names,
    rope_frequencies,
)


def _norm(x, w, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rotate(x, pos, freq):
    """Pairs ``(2j, 2j+1)`` of ``x (..., rope)`` turned by ``pos freq[j]``."""
    import jax.numpy as jnp

    out = []
    for j in range(freq.shape[0]):
        c, s = jnp.cos(pos * freq[j]), jnp.sin(pos * freq[j])
        even, odd = x[..., 2 * j], x[..., 2 * j + 1]
        out += [even * c - odd * s, odd * c + even * s]
    return jnp.stack(out, axis=-1)


def attention(args: ScMoEArgs, w: dict, tag: str, a, caches, lens):
    """``(o (batch, heads * v_dim), new rows (batch, width))`` of one layer
    for the normed inputs ``a (batch, hidden)``; ``caches[b]`` the ``(L_b,
    width)`` cached rows of sequence b."""
    import jax
    import jax.numpy as jnp

    f32, m = jnp.float32, args.mla
    freq = jnp.asarray(rope_frequencies(
        m.rope, args.rope_theta, args.rope_factor, args.rope_original,
        args.beta_fast, args.beta_slow))
    g = lambda k: jnp.asarray(w[f"{k}.{tag}"], f32)
    outs, rows = [], []
    for b, length in enumerate(lens):
        pos = jnp.float32(length)
        cq = _norm(a[b] @ g("Wqa"), g("Wqn"), args.eps)
        q = (cq @ g("Wqb")).reshape(m.heads, m.nope + m.rope) * args.q_scale
        q_nope, q_rope = q[:, :m.nope], _rotate(q[:, m.nope:], pos, freq)
        row = a[b] @ g("Wkva")
        c = _norm(row[:m.rank], g("Wkvn"), args.eps) * args.kv_scale
        new = jnp.concatenate([c, _rotate(row[m.rank:], pos, freq)])
        rows.append(new)
        seen = jnp.concatenate([jnp.asarray(caches[b], f32), new[None]])
        lat, k_rope = seen[:, :m.rank], seen[:, m.rank:]
        k_nope = jnp.einsum("jc,hdc->jhd", lat, g("W_UK"))
        v = jnp.einsum("jc,hcd->jhd", lat, g("W_UV"))
        s = m.scale * (jnp.einsum("hd,jhd->hj", q_nope, k_nope)
                       + q_rope @ k_rope.T)
        p = jax.nn.softmax(s, axis=1)
        outs.append(jnp.einsum("hj,jhd->hd", p, v).reshape(-1))
    return jnp.stack(outs), jnp.stack(rows)


def select(args: ScMoEArgs, w: dict, block: int, m0, bias=None):
    """``(picks (T, top_k), their weights)`` of the block's router for its
    inputs ``m0``: the largest ``p + bias``, equal scores to the lower
    index; weights ``routed_scale . p``, not renormalised."""
    import jax
    import jax.numpy as jnp

    mo = args.moe
    p = jax.nn.softmax(m0 @ jnp.asarray(w[moe_names(block).buf("Wg")],
                                        jnp.float32), axis=1)
    score = p if bias is None else p + bias[None, :]
    sel = jnp.argsort(-score, axis=1, stable=True)[:, :mo.top_k]
    return sel, mo.routed_scale * jnp.take_along_axis(p, sel, axis=1)


def experts(args: ScMoEArgs, w: dict, block: int, m0, sel, wts):
    """``s``: every real expert over every token, weighted by a one-hot
    mask of the picks; the zero picks' weights times the token itself."""
    import jax
    import jax.numpy as jnp

    f32, mo, b = jnp.float32, args.moe, moe_names(block).buf
    s = jnp.sum(jnp.where(sel >= mo.n_experts, wts, 0.0), axis=1,
                keepdims=True) * m0
    for e in range(mo.n_experts):
        we = jnp.sum(jnp.where(sel == e, wts, 0.0), axis=1, keepdims=True)
        w1, w3, w2 = (jnp.asarray(w[b(k)][e], f32) for k in ("W1", "W3", "W2"))
        s = s + we * ((jax.nn.silu(m0 @ w1) * (m0 @ w3)) @ w2)
    return s


def forward(args: ScMoEArgs, w: dict, h, caches: dict, lens) -> dict:
    """The step for ``h (T, hidden)``, one token a sequence, ``caches[tag]``
    the sequences' dense caches of attention layer ``tag``.  Returns ``{"h":
    [every block's input and the step's output], "m0": [every block's
    router input], "sel": [the picks], "s": [the shortcuts], "rows": {tag:
    the appended rows}}``, float32."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    out = {"h": [jnp.asarray(h, f32)], "m0": [], "sel": [], "s": [],
           "rows": {}}
    g = lambda name: jnp.asarray(w[name], f32)
    with jax.default_matmul_precision("highest"):
        h = out["h"][0]
        for l in range(args.blocks):
            s = None
            for i in (0, 1):
                at, ft = attn_tag(l, i), ffn_tag(l, i)
                a = _norm(h, g(f"Wn_in.{at}"), args.eps)
                o, out["rows"][at] = attention(args, w, at, a, caches[at],
                                               lens)
                h = h + o @ g(f"Wo.{at}")
                m = _norm(h, g(f"Wn_post.{ft}"), args.eps)
                if i == 0:
                    sel, wts = select(args, w, l, m)
                    s = experts(args, w, l, m, sel, wts)
                    out["m0"].append(m)
                    out["sel"].append(sel)
                    out["s"].append(s)
                h = h + (jax.nn.silu(m @ g(f"Wgate.{ft}"))
                         * (m @ g(f"Wup.{ft}"))) @ g(f"Wdown.{ft}")
            h = h + s
            out["h"].append(h)
    return out


def make_data(args: ScMoEArgs, seed: int = 0, table_seed: int = 0) -> dict:
    """Host arrays of a step at a small size (tests): the global buffers of
    ``shortcut_moe.data_layout`` that are inputs: ``h.B0`` standard normal,
    every weight matrix normal over the square root of its fan-in, norm
    weights near 1, the router's columns of unit length (float32), the
    caches standard normal (their latent part times the kv-lora scale, as
    normed rows lie there), tables and lengths
    ``latent_attention.make_decode_buffers``'s."""
    import jax.numpy as jnp

    from tenzing_tpu.models import latent_attention, shortcut_moe

    rng = np.random.default_rng(seed)
    m, mo = args.mla, args.moe
    tags = shortcut_moe.attn_tags(args)
    latent = latent_attention.make_decode_buffers(
        m, tags, seed, table_seed, args.shards)
    data = {k: latent[k] for k in ("lens", "table")}
    fan_in = {"Wqa": args.hidden, "Wqb": args.q_rank, "Wkva": args.hidden,
              "Wo": m.heads * m.v_dim, "Wgate": args.hidden,
              "Wup": args.hidden, "Wdown": args.ffn, "W1": mo.d_model,
              "W3": mo.d_model, "W2": mo.d_ff}
    for name, (shape, dtype, _) in shortcut_moe.data_layout(args).items():
        parts = name.split(".")
        kind = parts[-1] if parts[-1] in fan_in or parts[-1] == "Wg" \
            else parts[0]
        if kind in ("C", "Copen", "W_UK", "W_UV"):
            x = latent[name].astype(np.float32)
            if kind in ("C", "Copen"):
                x[:, :m.rank] *= args.kv_scale
        elif name == "h.B0":
            x = rng.standard_normal(shape)
        elif kind in ("Wn_in", "Wn_post", "Wqn", "Wkvn"):
            x = 1.0 + 0.1 * rng.standard_normal(shape)
        elif kind == "Wg":
            x = rng.standard_normal(shape)
            x /= np.linalg.norm(x, axis=0, keepdims=True)
        elif kind in fan_in:
            x = rng.standard_normal(shape) / np.sqrt(fan_in[kind])
        else:
            continue
        data[name] = np.asarray(x).astype(jnp.dtype(dtype))
    return data
