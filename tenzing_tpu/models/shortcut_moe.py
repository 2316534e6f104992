"""One decode step of shortcut-connected expert blocks (ScMoE, the layer of
LongCat-Flash) as a searchable op DAG: a hidden state that flows through
latent attention, dense FFNs and an expert block in one graph, expert
parallel over a mesh.

A block ``l`` (per token; ``h`` the residual stream; sublayer ``i`` in 0, 1)::

    for i in 0, 1:
        a  = RMSNorm_in[l,i](h)
        h  = h + o_proj[l,i]( MLA[l,i](a) )
        m  = RMSNorm_post[l,i](h)
        if i == 0:  s = MoE[l](m)              # the shortcut: joins at the end
        h  = h + W_down[l,i]( silu(W_gate[l,i] m) * (W_up[l,i] m) )
    h = h + s

The expert block's gate, dispatch, experts and combine (``models/moe.py``
:class:`~tenzing_tpu.models.moe.MoELayer`, one layer of several by its
:class:`~tenzing_tpu.models.moe.LayerNames`) run *beside* the first dense
FFN, the second attention and the second dense FFN: the only edges between
the two branches are ``m`` of sublayer 0 and the join.  The architecture
gives the exchange a partner; the graph hands that freedom to the search.

``MLA(a)`` is ``models/latent_attention.py``'s decode step (``add_layer``:
append, absorb, a group's engine menu, up-project) with the query and the
appended row *produced by vertices* here: the low-rank query path
(:class:`QueryPath`: ``q_a``, norm, ``q_b``, the q-lora scale, rotary) and
the latent path (:class:`KvPath`: ``kv_a``, norm, the kv-lora scale, rotary
on the shared rope key).  Rotary is on interleaved pairs ``(2j, 2j+1)`` at
position ``L_b`` with yarn frequencies (:func:`rope_frequencies`).

On the mesh every per-sequence buffer is cut by sequence over ``ep``
(attention and dense FFNs data-parallel, their weights on every shard), the
experts by expert; the lengths are the same list on every shard.  Whatever
a vertex writes is a buffer of its own (``h`` is never updated in place),
so an iteration is the same step again.

Names carry the block and the part: vertices ``B0.a0.*`` (attention 0 with
its input norm and output projection), ``B0.m0.norm``, ``B0.moe.*``,
``B0.f0.ffn``, ``B0.a1.*``, ``B0.m1.norm``, ``B0.f1.ffn``, ``B0.join``;
buffers ``<kind>.B0.a0`` (attention), ``<kind>.B0.f0`` (post-norm and dense
FFN), ``B0.moe.<kind>`` (the expert block), ``h.B0`` a block's input and
``h.B<blocks>`` the step's output.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List, Sequence as Seq

import numpy as np

from tenzing_tpu.core.graph import Graph
from tenzing_tpu.core.operation import DeviceOp
from tenzing_tpu.models import latent_attention
from tenzing_tpu.models.latent_attention import LatentDecodeArgs
from tenzing_tpu.models.moe import (
    AXIS,
    LayerNames,
    MoEArgs,
    MoELayer,
    mesh_moe_buffers,
    moe_synth_plans,
)


@dataclass(frozen=True)
class ScMoEArgs:
    """``blocks`` shortcut-connected blocks on ``moe.n_ep`` shards: ``mla``
    one shard's sequences and the attention's widths, ``moe`` the expert
    block (``tokens_per_shard`` the shard's sequences, ``d_model`` the
    hidden size).  The defaults are LongCat-Flash-Lite's."""

    mla: LatentDecodeArgs
    moe: MoEArgs
    blocks: int = 2
    q_rank: int = 1536          # q_lora_rank
    ffn: int = 6144             # ffn_hidden_size
    eps: float = 1e-5           # rms_norm_eps
    scale_q_lora: bool = True   # mla_scale_q_lora
    scale_kv_lora: bool = True  # mla_scale_kv_lora
    rope_theta: float = 5e6
    rope_factor: float = 10.0   # rope_scaling.factor (yarn); 1: plain rotary
    rope_original: int = 32768  # original_max_position_embeddings
    beta_fast: float = 32.0
    beta_slow: float = 1.0

    def __post_init__(self):
        if self.moe.tokens_per_shard != self.mla.batch:
            raise ValueError("a shard's tokens are its sequences' one query "
                             "position each")
        if self.mla.dtype != self.moe.dtype:
            raise ValueError("one dtype a step")

    @property
    def hidden(self) -> int:
        return self.moe.d_model

    @property
    def shards(self) -> int:
        return self.moe.n_ep

    @property
    def q_scale(self) -> float:
        return (self.hidden / self.q_rank) ** 0.5 if self.scale_q_lora else 1.

    @property
    def kv_scale(self) -> float:
        return ((self.hidden / self.mla.rank) ** 0.5 if self.scale_kv_lora
                else 1.)


def attn_tag(block: int, i: int) -> str:
    return f"B{block}.a{i}"


def ffn_tag(block: int, i: int) -> str:
    return f"B{block}.f{i}"


def moe_names(block: int) -> LayerNames:
    """The expert block of ``block``: its input the post-norm of sublayer
    0, its output the shortcut ``s``."""
    return LayerNames(f"B{block}.moe", x=f"m.{ffn_tag(block, 0)}",
                      y=f"s.B{block}")


def rope_frequencies(rope: int, theta: float, factor: float, original: int,
                     beta_fast: float, beta_slow: float) -> np.ndarray:
    """``(rope / 2,)`` float32: the angle a position of pair ``j`` turns by,
    yarn's: ``f_j = theta^(-2j/rope)``; ``r_j = clip((j - lo) / (hi - lo),
    0, 1)`` with ``lo = floor(c(beta_fast))``, ``hi = ceil(c(beta_slow))``,
    ``c(beta) = rope ln(original / (2 pi beta)) / (2 ln theta)``, both
    clipped to ``0 .. rope - 1``; the frequency is ``f_j ((1 - r_j) + r_j /
    factor)``: fast pairs turn as trained, slow ones ``factor`` times
    slower."""
    j = np.arange(rope // 2, dtype=np.float64)
    f = theta ** (-2.0 * j / rope)
    if factor == 1.0:
        return f.astype(np.float32)

    def c(beta):
        return rope * math.log(original / (2 * math.pi * beta)) / (
            2 * math.log(theta))

    lo = min(max(math.floor(c(beta_fast)), 0), rope - 1)
    hi = min(max(math.ceil(c(beta_slow)), 0), rope - 1)
    r = np.clip((j - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return (f * ((1.0 - r) + r / factor)).astype(np.float32)


def rotate_pairs(x, pos, freq):
    """Rotary on interleaved pairs: ``x`` ``(batch, ..., rope)`` float32,
    ``pos`` ``(batch,)`` float32, ``freq`` ``(rope / 2,)``; pair ``(2j,
    2j+1)`` of sequence b turns by ``pos[b] freq[j]``."""
    import jax.numpy as jnp

    angle = pos[:, None] * freq[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (freq.shape[0],)
    cos, sin = jnp.cos(angle).reshape(shape), jnp.sin(angle).reshape(shape)
    pairs = x.reshape(x.shape[:-1] + (freq.shape[0], 2))
    even, odd = pairs[..., 0], pairs[..., 1]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def rms_norm(x, w, eps: float):
    """``x / sqrt(mean x^2 + eps) . w`` in float32."""
    import jax.numpy as jnp
    from jax import lax

    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rotary(args: "ScMoEArgs", bufs):
    """``(positions (batch,) float32, frequencies (rope / 2,))`` of a step:
    sequence b's new token stands at ``L_b``, one before its visible keys."""
    import jax.numpy as jnp

    return (bufs["lens"] - 1).astype(jnp.float32), jnp.asarray(
        rope_frequencies(args.mla.rope, args.rope_theta, args.rope_factor,
                         args.rope_original, args.beta_fast, args.beta_slow))


def _dot(x, w):
    """A product in the step's precision: operands as stored, float32
    accumulation."""
    import jax.numpy as jnp

    return jnp.dot(x, w, preferred_element_type=jnp.float32)


class RmsNorm(DeviceOp):
    """``dst = RMSNorm(src) . w``."""

    def __init__(self, name: str, src: str, w: str, dst: str, eps: float):
        super().__init__(name)
        self._src, self._w, self._dst, self._eps = src, w, dst, eps

    def reads(self):
        return [self._src, self._w]

    def writes(self):
        return [self._dst]

    def apply(self, bufs, ctx):
        out = rms_norm(bufs[self._src], bufs[self._w], self._eps)
        return {self._dst: out.astype(bufs[self._dst].dtype)}


class QueryPath(DeviceOp):
    """The low-rank query of an attention layer from its normed input
    ``a``: ``cq = RMSNorm(a W_qa)``; ``[q_nope ; q_rope] = cq W_qb`` a head,
    both times the q-lora scale; rotary on ``q_rope`` at position ``L_b``.
    Writes the layer's ``q_nope`` and ``q_rope``, which the absorb reads."""

    def __init__(self, name: str, args: ScMoEArgs, tag: str):
        super().__init__(name)
        self._args, self._t = args, tag

    def reads(self):
        return [f"{k}.{self._t}" for k in ("a", "Wqa", "Wqn", "Wqb")] + [
            "lens"]

    def writes(self):
        return [f"q_nope.{self._t}", f"q_rope.{self._t}"]

    def apply(self, bufs, ctx):
        a, m, t = self._args, self._args.mla, self._t
        x = bufs[f"a.{t}"]
        cq = rms_norm(_dot(x, bufs[f"Wqa.{t}"]), bufs[f"Wqn.{t}"], a.eps)
        q = _dot(cq.astype(x.dtype), bufs[f"Wqb.{t}"]) * a.q_scale
        q = q.reshape(x.shape[0], m.heads, m.nope + m.rope)
        rope = rotate_pairs(q[..., m.nope:], *_rotary(a, bufs))
        return {f"q_nope.{t}": q[..., :m.nope].astype(x.dtype),
                f"q_rope.{t}": rope.astype(x.dtype)}


class KvPath(DeviceOp):
    """The new latent row of an attention layer from its normed input:
    ``[c ; k_rope] = a W_kva``; ``c = RMSNorm(c)`` times the kv-lora scale
    (``k_rope`` is not scaled); rotary on ``k_rope`` at position ``L_b``.
    Writes ``c_new`` and ``kr_new``: the row the append appends."""

    def __init__(self, name: str, args: ScMoEArgs, tag: str):
        super().__init__(name)
        self._args, self._t = args, tag

    def reads(self):
        return [f"{k}.{self._t}" for k in ("a", "Wkva", "Wkvn")] + ["lens"]

    def writes(self):
        return [f"c_new.{self._t}", f"kr_new.{self._t}"]

    def apply(self, bufs, ctx):
        a, m, t = self._args, self._args.mla, self._t
        x = bufs[f"a.{t}"]
        row = _dot(x, bufs[f"Wkva.{t}"])
        c = rms_norm(row[:, :m.rank], bufs[f"Wkvn.{t}"], a.eps) * a.kv_scale
        kr = rotate_pairs(row[:, m.rank:], *_rotary(a, bufs))
        return {f"c_new.{t}": c.astype(x.dtype),
                f"kr_new.{t}": kr.astype(x.dtype)}


class OutProject(DeviceOp):
    """``dst = h + o W_o``: the heads' outputs back to the hidden size, and
    the residual add."""

    def __init__(self, name: str, tag: str, h: str, dst: str):
        super().__init__(name)
        self._t, self._h, self._dst = tag, h, dst

    def reads(self):
        return [f"o.{self._t}", f"Wo.{self._t}", self._h]

    def writes(self):
        return [self._dst]

    def apply(self, bufs, ctx):
        import jax.numpy as jnp

        o = bufs[f"o.{self._t}"]
        h = bufs[self._h]
        out = h.astype(jnp.float32) + _dot(o.reshape(o.shape[0], -1),
                                           bufs[f"Wo.{self._t}"])
        return {self._dst: out.astype(h.dtype)}


class DenseFFN(DeviceOp):
    """``dst = h + (silu(m W_gate) * (m W_up)) W_down``: the gated dense
    FFN on the post-normed ``m``, and the residual add."""

    def __init__(self, name: str, tag: str, h: str, dst: str):
        super().__init__(name)
        self._t, self._h, self._dst = tag, h, dst

    def reads(self):
        return [f"{k}.{self._t}" for k in ("m", "Wgate", "Wup", "Wdown")] + [
            self._h]

    def writes(self):
        return [self._dst]

    def apply(self, bufs, ctx):
        import jax
        import jax.numpy as jnp

        t = self._t
        m, h = bufs[f"m.{t}"], bufs[self._h]
        act = jax.nn.silu(_dot(m, bufs[f"Wgate.{t}"])) * _dot(
            m, bufs[f"Wup.{t}"])
        out = h.astype(jnp.float32) + _dot(act.astype(m.dtype),
                                           bufs[f"Wdown.{t}"])
        return {self._dst: out.astype(h.dtype)}


class ShortcutJoin(DeviceOp):
    """``dst = h + s``: the expert block's output joins the residual stream
    at the block's end."""

    def __init__(self, name: str, h: str, s: str, dst: str):
        super().__init__(name)
        self._h, self._s, self._dst = h, s, dst

    def reads(self):
        return [self._h, self._s]

    def writes(self):
        return [self._dst]

    def apply(self, bufs, ctx):
        import jax.numpy as jnp

        h = bufs[self._h]
        return {self._dst: (h.astype(jnp.float32)
                            + bufs[self._s].astype(jnp.float32)
                            ).astype(h.dtype)}


def scmoe_decode_graph(args: ScMoEArgs, synth: bool = False,
                       synth_relax: bool = False,
                       impl_choice: bool = False) -> Graph:
    """The step's blocks one after another; inside a block the two branches
    of the module's docstring, joined by ``m`` of sublayer 0 and the join
    alone.  ``synth``: each exchange is a menu of XLA's all-to-all and
    ``collectives/synth.py``'s ring of permutes."""
    plan = latent_attention.decode_plan(args.mla)
    g = Graph()
    last = None
    for l in range(args.blocks):
        h = f"h.B{l}"
        moe = None
        for i in (0, 1):
            at, ft = attn_tag(l, i), ffn_tag(l, i)
            norm = RmsNorm(f"{at}.norm", h, f"Wn_in.{at}", f"a.{at}",
                           args.eps)
            if last is None:
                g.start_then(norm)
            else:
                g.then(last, norm)
            q = QueryPath(f"{at}.q_path", args, at)
            kv = KvPath(f"{at}.kv_path", args, at)
            g.then(norm, q)
            g.then(norm, kv)
            up = latent_attention.add_layer(
                g, args.mla, plan, at, {"append": [kv], "absorb": [q]},
                impl_choice)
            proj = OutProject(f"{at}.out_proj", at, h, f"hA.{at}")
            g.then(up, proj)
            post = RmsNorm(f"B{l}.m{i}.norm", f"hA.{at}", f"Wn_post.{ft}",
                           f"m.{ft}", args.eps)
            g.then(proj, post)
            if i == 0:
                moe = MoELayer(args.moe, f"B{l}.moe", synth=synth,
                               synth_relax=synth_relax, names=moe_names(l))
                g.then(post, moe)
            ffn = DenseFFN(f"{ft}.ffn", ft, f"hA.{at}", f"hF.{ft}")
            g.then(post, ffn)
            h, last = f"hF.{ft}", ffn
        join = ShortcutJoin(f"B{l}.join", h, f"s.B{l}", f"h.B{l + 1}")
        g.then(last, join)
        g.then(moe, join)
        last = join
    g.then_finish(last)
    return g


#: the order of the residual stream as written: the whole expert branch
#: where ``i == 0`` computes it (naive's phase list, one block)
WRITTEN = ("a0.", "m0.", "moe.", "f0.", "a1.", "m1.", "f1.", "join")
#: the shortcut discipline: the dense branch placed between each post and
#: its await as far as the edges allow (the start point's, one block)
SHORTCUT = ("a0.", "m0.", "moe.gate", "moe.pack", "moe.a2a_disp", "f0.",
            "moe.await_disp", "moe.ffn", "moe.a2a_comb", "a1.", "m1.", "f1.",
            "moe.await_comb", "moe.combine", "moe.moe_concat", "join")


def phases(args: ScMoEArgs, order: Seq[str]) -> List[str]:
    """``order`` (one block's prefixes) for every block, as
    ``solve/local.py`` ``phase_policy`` takes them."""
    return [f"B{l}.{p}" for l in range(args.blocks) for p in order]


def attn_tags(args: ScMoEArgs) -> List[str]:
    return [attn_tag(l, i) for l in range(args.blocks) for i in (0, 1)]


def buffer_layout(args: ScMoEArgs) -> Dict[str, tuple]:
    """``{name: (global shape, dtype, partition spec)}`` of the buffers
    this module's vertices add to the latent layers' and the expert
    blocks': the residual stream's stations by sequence, the weights on
    every shard (norm weights float32)."""
    from jax.sharding import PartitionSpec as P

    m, dt, d = args.mla, args.mla.dtype, args.hidden
    rows = (args.shards * m.batch, d)
    by_seq, whole1, whole2 = P(AXIS, None), P(None), P(None, None)
    out = {"h.B0": (rows, dt, by_seq)}
    for l in range(args.blocks):
        out[f"s.B{l}"] = out[f"h.B{l + 1}"] = (rows, dt, by_seq)
        for i in (0, 1):
            at, ft = attn_tag(l, i), ffn_tag(l, i)
            out.update({
                f"a.{at}": (rows, dt, by_seq),
                f"hA.{at}": (rows, dt, by_seq),
                f"m.{ft}": (rows, dt, by_seq),
                f"hF.{ft}": (rows, dt, by_seq),
                f"Wn_in.{at}": ((d,), "float32", whole1),
                f"Wqa.{at}": ((d, args.q_rank), dt, whole2),
                f"Wqn.{at}": ((args.q_rank,), "float32", whole1),
                f"Wqb.{at}": ((args.q_rank, m.heads * (m.nope + m.rope)), dt,
                              whole2),
                f"Wkva.{at}": ((d, m.width), dt, whole2),
                f"Wkvn.{at}": ((m.rank,), "float32", whole1),
                f"Wo.{at}": ((m.heads * m.v_dim, d), dt, whole2),
                f"Wn_post.{ft}": ((d,), "float32", whole1),
                f"Wgate.{ft}": ((d, args.ffn), dt, whole2),
                f"Wup.{ft}": ((d, args.ffn), dt, whole2),
                f"Wdown.{ft}": ((args.ffn, d), dt, whole2)})
    return out


def data_layout(args: ScMoEArgs, capacity: int = 1) -> Dict[str, tuple]:
    """``{name: (global shape, dtype, partition spec)}`` of every buffer of
    the step on the mesh: this module's, the latent layers' and the expert
    blocks' (at ``capacity`` slots)."""
    from tenzing_tpu.models import moe as moe_mod

    tags = attn_tags(args)
    out = dict(buffer_layout(args))
    specs = latent_attention.mesh_specs(args.mla, tags, AXIS)
    for name, (shape, dtype) in latent_attention.buffer_shapes(
            args.mla, tags, args.shards).items():
        out[name] = (tuple(shape), dtype, specs[name])
    for l in range(args.blocks):
        out.update(moe_mod.buffer_layout(args.moe, capacity, moe_names(l),
                                         router_dtype="float32"))
    return out


def ring_staging_layout(args: ScMoEArgs, capacity: int) -> Dict[str, tuple]:
    """``{name: (global shape, dtype, partition spec)}`` of the staging
    buffers the ring exchanges of every expert block need beside
    :func:`data_layout` (``scmoe_decode_graph(synth=True)``)."""
    from jax.sharding import PartitionSpec as P

    out = {}
    for l in range(args.blocks):
        for site in ("disp", "comb"):
            for plan in moe_synth_plans(args.moe, 0, site, cap=capacity,
                                        names=moe_names(l)):
                for decl in plan.buffers:
                    shape = ((args.shards * decl.shape[0],)
                             + tuple(decl.shape[1:]))
                    out[decl.name] = (shape, args.moe.dtype, P(
                        AXIS, *([None] * (len(shape) - 1))))
    return out


def scmoe_buffers(args: ScMoEArgs, mesh, data: Dict[str, object],
                  route_on: Seq[object], synth: bool = False):
    """``(buffers, specs)`` of the step on ``mesh`` from ``data`` that lie
    there already (:func:`data_layout`: the first block's ``h``, ``lens``,
    ``table``, the caches, every weight): the expert blocks' set-up
    negotiation (``models/moe.py`` ``mesh_moe_buffers``), block ``l``'s
    selection taken from ``route_on[l]``, the float32 forward's input of
    that block's router (sublayer 0's post-norm, rows by shard); what the
    iteration writes starts at zero on its own shard.  ``synth``: the ring
    exchanges' staging buffers too."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from tenzing_tpu.obs.metrics import get_metrics
    from tenzing_tpu.obs.tracer import get_tracer

    cap = args.moe.fixed_capacity()
    with get_tracer().span("scmoe.plan", blocks=args.blocks,
                           groups=args.mla.groups, capacity=cap,
                           page_tokens=args.mla.page):
        bufs, specs = {}, {}
        for l in range(args.blocks):
            made, sp = mesh_moe_buffers(args.moe, mesh, data, moe_names(l),
                                        route_on=route_on[l])
            bufs.update(made)
            specs.update(sp)
        layout = data_layout(args, cap)
        if synth:
            layout.update(ring_staging_layout(args, cap))
        for name, (shape, dtype, spec) in layout.items():
            specs[name] = spec
            if name in bufs:
                continue
            if name in data:
                bufs[name] = data[name]
            else:  # what the iteration writes
                bufs[name] = jnp.zeros(shape, dtype,
                                       device=NamedSharding(mesh, spec))
    reg = get_metrics()

    def shard_bytes(name):
        """What one shard holds of a buffer."""
        v = bufs[name]
        cut = args.shards if AXIS in tuple(specs[name]) else 1
        return int(np.prod(v.shape)) * v.dtype.itemsize // cut

    reg.counter("scmoe.weight_bytes").inc(
        sum(shard_bytes(k) for k in bufs if _is_weight(k)))
    reg.counter("scmoe.cache_bytes").inc(
        sum(shard_bytes(f"{k}.{t}") for t in attn_tags(args)
            for k in ("C", "Copen")))
    return bufs, specs


def _is_weight(name: str) -> bool:
    """A buffer that holds parameters: this module's and the latent layers'
    ``W...`` kinds, an expert block's experts and router."""
    return name.split(".")[0].startswith("W") or name.split(".")[-1] in (
        "W1", "W2", "W3", "Wg")
