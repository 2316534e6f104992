"""Mixture-of-Experts layer: expert parallelism as a searchable op DAG.

The reference has no ML layers (SURVEY.md §2.5: TP/PP/EP absent; the op-DAG
must nonetheless *express* such programs).  This model is the expert-parallel
(EP) member of that family, the structural sibling of the irregular SpMV
exchange (models/spmv_irregular.py): tokens are routed to experts that live on
other shards, so the layer is dispatch (all-to-all) -> expert FFN -> combine
(all-to-all back) — the reference's ``Ialltoallv`` pattern
(ops_mpi.hpp:82-119) with MXU compute between the two exchanges.

Design:

* **Which experts a token goes to is negotiated at set-up** (the analog of
  ``RowPartSpmv``'s send/recv negotiation, row_part_spmv.cuh:259-423): the
  ``top_k`` experts of every token are selected once, when buffers are
  built, producing static per-(shard, expert) slot tables — ``disp_idx``
  (which local token fills each capacity slot), ``comb_idx`` (which slots a
  token's experts answer in) and ``disp_w`` (a slot's combine weight; 0
  marks padding).  Raggedness is handled by padding every (src, expert)
  pair to the common capacity, exactly like the irregular SpMV's
  width-padded lists — there is no ragged all-to-all on ICI.  A shard holds
  ``experts_per_shard`` experts (a grouped product over the experts held).
* **Two scoring rules, one layer** (:class:`MoEArgs`): ``scoring`` says how
  a score is made, ``gate_in_iteration`` whether the timed iteration makes
  it.  ``"softmax"``: a selected expert's weight is its softmax probability
  over all the router's outputs times ``routed_scale``, not renormalised
  (the top-1 default, fixed at set-up; LongCat-Flash's rule with
  ``routed_scale=6`` in the iteration).  ``"sigmoid"`` (the deepseek_v3
  rule, ``scoring_func: sigmoid`` / ``norm_topk_prob`` /
  ``routed_scaling_factor``): scores ``sigmoid(x W_g)``, the selected ones
  normalised to sum 1 and scaled.  In the iteration ``disp_w`` is computed
  by ``gate_c``, a chain that depends on neither all-to-all, like the shared
  expert ``shared_c`` (``shared_ff``).
* **Zero-compute experts** (``zero_experts``, LongCat-Flash's
  ``zero_expert_num`` of type identity): the router has ``n_experts +
  zero_experts`` outputs and the selection runs over all of them; a pick at
  or above ``n_experts`` is an identity expert: it holds no slot, crosses no
  chip and adds ``w . x`` on the token's own shard (``gate_c`` sums such
  picks' weights into ``zero_w_c``, the combine adds the term).  A token so
  has between 0 and ``top_k`` slots.
* **A layer among several** (:class:`LayerNames`): a tag before every
  buffer's and vertex's name, and the names of the input and output
  buffers, which are then another layer's.
* **The data plane is schedulable.**  Tokens are split into ``n_chunks``
  microbatch chunks; each chunk is an independent chain

      pack_c -> a2a_disp_c(post) -> await -> ffn_c -> a2a_comb_c(post)
             -> await -> combine_c        (gate_c, shared_c -> combine_c)

  so the solver can pipeline chunks: expert compute of chunk 0 overlaps the
  dispatch of chunk 1 (the schedule MoE systems hand-tune; here it is
  *searched*).  The reference hard-codes its overlap discipline with
  post-all-before-wait-any edges (ops_halo_exchange.cu:249-256); this graph
  deliberately leaves that freedom to the search.
* The expert FFN (a gelu MLP, or the gated SwiGLU MLP with ``gated``; the
  MXU hot spot) has an implementation ChoiceOp for the gelu form: XLA
  einsums vs the Pallas tiled-matmul kernel (ops/ffn_pallas.py).

Numerics are checked against a dense host evaluation of the routed layer
(tests/test_moe.py; ``dryrun_multichip`` covers the full sharded path).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

from tenzing_tpu.core.graph import Graph
from tenzing_tpu.core.operation import ChoiceOp, CompoundOp, DeviceOp, OpBase
from tenzing_tpu.ops.comm_ops import AllToAllStart, AwaitTransfer

AXIS = "ep"

#: op-name prefixes of the post-all-before-await-any discipline (the
#: reference's hard-coded overlap, ops_halo_exchange.cu:249-256), for
#: ``solve/local.py`` ``phase_policy`` / ``greedy_phase_order``: every
#: dispatch is posted before anything waits, the chains that cross no chip
#: (gate, shared expert) run while it is in flight
PHASES = ("start", "pack", "a2a_disp", "gate", "shared", "await_disp", "ffn",
          "a2a_comb", "await_comb", "combine", "moe_concat", "finish")


class LayerNames:
    """Names of one layer's buffers and vertices where a graph holds
    several: ``tag`` goes before each (``"B0.moe"``: ``B0.moe.W1``,
    ``B0.moe.pack_0``), and the layer's input and output are the buffers
    ``x`` and ``y``, whatever vertex of the graph writes or reads them.  The
    defaults are the layer alone: ``X``, ``Y``, no tag."""

    def __init__(self, tag: str = "", x: str = "X", y: str = "Y"):
        self.tag, self.x, self.y = tag, x, y
        self._pre = f"{tag}." if tag else ""

    def buf(self, base: str) -> str:
        return {"X": self.x, "Y": self.y}.get(base, self._pre + base)

    def op(self, base: str) -> str:
        return self._pre + base


ALONE = LayerNames()


@dataclass(frozen=True)
class MoEArgs:
    """One expert-parallel layer.  The defaults are the one-expert-a-shard,
    top-1 softmax, gelu layer; ``experts_per_shard=16, top_k=6, gated=True,
    shared_ff=2816, scoring="sigmoid", routed_scale=2.446,
    capacity_factor=1.5`` is Moonlight-16B-A3B's (deepseek_v3) over four
    shards; ``experts_per_shard=64, top_k=12, gated=True, scoring="softmax",
    gate_in_iteration=True, routed_scale=6, zero_experts=128`` is
    LongCat-Flash-Lite's.

    A **zero expert** (``zero_experts`` of them, the router's outputs at and
    above ``n_experts``) computes nothing: selected, it returns the token
    itself times the pick's weight.  It is selected like any other, holds
    no slot in the exchange and lives on no shard."""

    n_ep: int  # expert-parallel shards
    tokens_per_shard: int = 16
    d_model: int = 8
    d_ff: int = 16
    n_chunks: int = 2  # microbatch chunks (the pipelining freedom)
    dtype: str = "float32"
    experts_per_shard: int = 1  # resident experts a shard
    top_k: int = 1  # experts a token is sent to
    gated: bool = False  # (silu(x W1) * (x W3)) W2 instead of gelu(x W1) W2
    shared_ff: int = 0  # width of the shared expert (0: none)
    # slots per (source shard, expert, chunk) = ceil(factor * mean load);
    # 0: the largest load routed (the data decide the shapes)
    capacity_factor: float = 0.0
    # how a selected expert's weight is made.  "softmax": its probability
    # over all the router's outputs, times routed_scale, not renormalised;
    # "sigmoid": sigmoid scores, the selected normalised to sum 1, scaled
    scoring: str = "softmax"
    routed_scale: float = 1.0
    zero_experts: int = 0  # identity experts behind the n_experts real ones
    # whether the timed iteration makes the weights (gate_c) or set-up fixes
    # them; None: the iteration for "sigmoid", set-up for "softmax"
    gate_in_iteration: Optional[bool] = None

    def __post_init__(self):
        if self.scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown scoring {self.scoring!r}")
        if not 1 <= self.top_k <= self.n_router:
            raise ValueError(f"top_k {self.top_k} of {self.n_router} experts")
        if self.gate_in_iteration is None:
            object.__setattr__(self, "gate_in_iteration",
                               self.scoring == "sigmoid")
        if self.zero_experts and not self.gate_in_iteration:
            raise ValueError("zero experts' weights are made in the "
                             "iteration (gate_in_iteration)")

    @property
    def chunk_tokens(self) -> int:
        assert self.tokens_per_shard % self.n_chunks == 0
        return self.tokens_per_shard // self.n_chunks

    @property
    def n_experts(self) -> int:
        return self.n_ep * self.experts_per_shard

    @property
    def n_router(self) -> int:
        """Outputs of the router: the real experts, then the zero ones."""
        return self.n_experts + self.zero_experts

    def fixed_capacity(self):
        """Slots per (source shard, expert, chunk) where the configuration
        fixes them (``capacity_factor``), else ``None``.  The mean load is
        the real picks': a balanced router sends ``n_experts`` of
        ``n_router`` picks to a real expert."""
        if not self.capacity_factor:
            return None
        mean = self.chunk_tokens * self.top_k / self.n_router
        return int(np.ceil(self.capacity_factor * mean))


from tenzing_tpu.utils.numeric import gelu_tanh as _gelu


def top1_route(x: np.ndarray, wg: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Top-1 gating in float64: (expert index, softmax gate weight) per token
    — the single source of the routing rule for every MoE buffer builder
    (multi-chip here, single-chip models/moe_pipeline.py) and its expected-Y
    host references."""
    logits = x.astype(np.float64) @ wg.astype(np.float64)  # (T, E)
    expert = np.argmax(logits, axis=1)
    pz = np.exp(logits - logits.max(axis=1, keepdims=True))
    pz /= pz.sum(axis=1, keepdims=True)
    gate = pz[np.arange(len(x)), expert]
    return expert, gate


def select_experts(args: MoEArgs, x, wg, bias):
    """The set-up half of the router: ``(T, top_k)`` int32 expert ids, the
    ``top_k`` largest of ``score(x wg) + bias`` in float32 at the highest
    matmul precision (a rounding must not send a token elsewhere than a
    reference does).  Softmax and sigmoid are monotone, so the selection
    is the logits' except for the bias, which is added to the score."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        logits = jnp.dot(x.astype(jnp.float32), wg.astype(jnp.float32))
    score = (jax.nn.sigmoid(logits) if args.scoring == "sigmoid"
             else jax.nn.softmax(logits, axis=1))
    _, sel = jax.lax.top_k(score + bias.astype(jnp.float32)[None, :],
                           args.top_k)
    return sel.astype(jnp.int32)


def gate_weights(args: MoEArgs, xc, wg, topk):
    """The iteration half of the router: ``(Tc, top_k)`` float32 combine
    weights of the selected experts ``topk``.  Sigmoid scoring: their scores
    ``sigmoid(xc wg)``, normalised to sum 1 (``norm_topk_prob``; the
    model's ``+ 1e-20``) and scaled (``routed_scaling_factor``).  Softmax
    scoring: their probabilities over all the router's outputs, scaled and
    not renormalised; a float32 router's product is taken at the highest
    precision (the default would round both sides to bfloat16)."""
    import jax
    import jax.numpy as jnp

    if args.scoring == "softmax":
        exact = wg.dtype == jnp.float32
        logits = jnp.dot(xc.astype(wg.dtype), wg,
                         precision=jax.lax.Precision.HIGHEST if exact
                         else None, preferred_element_type=jnp.float32)
        return args.routed_scale * jnp.take_along_axis(
            jax.nn.softmax(logits, axis=1), topk, axis=1)
    s = jax.nn.sigmoid(jnp.dot(xc, wg, preferred_element_type=jnp.float32))
    picked = jnp.take_along_axis(s, topk, axis=1)
    return args.routed_scale * picked / (
        picked.sum(axis=1, keepdims=True) + 1e-20)


def expert_loads(sel, args: MoEArgs):
    """``(n_chunks, n_experts)`` int32: tokens one shard's chunk sends to
    each expert, from its ``(T, top_k)`` selection."""
    import jax.numpy as jnp

    e = sel.reshape(args.n_chunks, -1)
    ids = jnp.arange(args.n_experts, dtype=sel.dtype)
    return jnp.sum(e[:, :, None] == ids[None, None, :], axis=1,
                   dtype=jnp.int32)


def slot_tables(sel, args: MoEArgs, cap: int) -> Dict[str, object]:
    """One shard's static slot tables from its ``(T, top_k)`` selection, as
    the device ops index them (each with the shard's leading axis of 1
    where the global buffer stacks shards):

    * ``disp_idx_c`` (1, n_ep, E_l*cap): the chunk-local token in each slot
      (0 in padding), slot ``e_l*cap + j`` of peer ``p`` the ``j``-th token,
      in token order, selected for expert ``p*E_l + e_l``;
    * ``slot_tk_c`` (1, n_ep, E_l*cap): the flat ``token*top_k + k`` whose
      weight the slot carries, -1 in padding;
    * ``comb_idx_c`` (Tc, top_k): the flat slot each selected expert of a
      token answers in (the all-to-all back keeps the slot's place);
    * ``topk_c`` (Tc, top_k): the selection itself.

    A selection beyond ``cap`` finds no slot (counted by the caller from
    :func:`expert_loads`; the builders refuse it).  Neither does a zero
    expert's pick (an id at or above ``n_experts``): its ``comb_idx`` points
    at the table's last slot and the combine masks it by ``topk_c``."""
    import jax.numpy as jnp

    k, tc, n_e = args.top_k, args.chunk_tokens, args.n_experts
    a = jnp.arange(tc * k, dtype=jnp.int32)
    out = {}
    for c in range(args.n_chunks):
        topk = sel[c * tc:(c + 1) * tc]
        e = topk.reshape(-1)
        onehot = (e[:, None] == jnp.arange(n_e, dtype=e.dtype)[None, :])
        held = jnp.minimum(e, n_e - 1) if args.zero_experts else e
        rank = jnp.take_along_axis(
            jnp.cumsum(onehot.astype(jnp.int32), axis=0), held[:, None],
            axis=1)[:, 0] - 1
        # beyond capacity: an index past the table, which the scatter drops
        slot = jnp.where(rank < cap, e * cap + rank, n_e * cap)
        if args.zero_experts:
            slot = jnp.where(e < n_e, slot, n_e * cap)
        shape = (1, args.n_ep, args.experts_per_shard * cap)
        out[f"disp_idx_{c}"] = jnp.zeros((n_e * cap,), jnp.int32).at[
            slot].set(a // k, mode="drop").reshape(shape)
        out[f"slot_tk_{c}"] = jnp.full((n_e * cap,), -1, jnp.int32).at[
            slot].set(a, mode="drop").reshape(shape)
        out[f"comb_idx_{c}"] = jnp.minimum(slot, n_e * cap - 1).reshape(tc, k)
        out[f"topk_{c}"] = topk
    return out


def slot_weights(w_tk, slot_tk):
    """Per-slot combine weights from per-(token, k) weights ``w_tk``
    (Tc, top_k): the weight each slot's token gives the slot's expert, 0 in
    padding."""
    import jax.numpy as jnp

    w = w_tk.reshape(-1)[jnp.maximum(slot_tk, 0)]
    return jnp.where(slot_tk >= 0, w, jnp.zeros((), w.dtype))


def _mlp(args: MoEArgs, x, w1, w3, w2, grouped: bool = False):
    """The expert MLP in the layer's precision: inputs and weights in
    ``args.dtype``, float32 accumulation, the hidden activation rounded to
    ``args.dtype`` between the products.  Plain: ``x`` (t, d) through one
    expert; ``grouped``: ``x`` (p, e, c, d) through expert ``e``'s weights
    for each ``e`` (a grouped product over the experts held)."""
    import jax
    import jax.numpy as jnp

    up, down = (("pecd,edf->pecf", "pecf,efd->pecd") if grouped
                else ("td,df->tf", "tf,fd->td"))
    f32 = jnp.float32
    g = jnp.einsum(up, x, w1, preferred_element_type=f32)
    if args.gated:
        h = jax.nn.silu(g) * jnp.einsum(up, x, w3, preferred_element_type=f32)
    else:
        h = jax.nn.gelu(g)
    return jnp.einsum(down, h.astype(x.dtype), w2, preferred_element_type=f32)


class _ChunkOp(DeviceOp):
    """A device op of chunk ``c``'s chain."""

    def __init__(self, name: str, c: int, args: MoEArgs,
                 names: LayerNames = ALONE):
        super().__init__(name)
        self._c = c
        self._args = args
        self._names = names
        self._b = names.buf

    def _tokens(self, bufs):
        """The chunk's local tokens ``(Tc, d)``."""
        tc_ = self._args.chunk_tokens
        return bufs[self._b("X")][self._c * tc_ : (self._c + 1) * tc_]


class DispatchPack(_ChunkOp):
    """Fill chunk ``c``'s capacity-padded send buffer from the local tokens the
    router assigned to each expert (the gather the reference's Scatter op does
    for the Ialltoallv send buffer, ops_spmv.cuh:194-215)."""

    def reads(self):
        return [self._b("X"), self._b(f"disp_idx_{self._c}")]

    def writes(self):
        return [self._b(f"send_disp_{self._c}")]

    def apply(self, bufs, ctx):
        xc = self._tokens(bufs)  # (Tc, d)
        idx = bufs[self._b(f"disp_idx_{self._c}")][0]  # (n_ep, E_l*C)
        return {self._b(f"send_disp_{self._c}"): xc[idx]}  # (n_ep, E_l*C, d)


class GateWeights(_ChunkOp):
    """Chunk ``c``'s combine weights, computed in the iteration: the score
    product ``x W_g`` over all the router's outputs, the selected experts'
    weights (:func:`gate_weights`) laid out slot by slot and, where the
    layer has zero experts, each token's sum over its zero picks
    (``zero_w_c``, float32: the combine adds that much of the token
    itself).  Depends on neither all-to-all: the search may run it while a
    dispatch is in flight."""

    def reads(self):
        b = self._b
        return [b("X"), b("Wg"), b(f"topk_{self._c}"),
                b(f"slot_tk_{self._c}")]

    def writes(self):
        return [self._b(f"disp_w_{self._c}")] + (
            [self._b(f"zero_w_{self._c}")] if self._args.zero_experts else [])

    def apply(self, bufs, ctx):
        import jax.numpy as jnp

        b, a = self._b, self._args
        topk = bufs[b(f"topk_{self._c}")]
        w_tk = gate_weights(a, self._tokens(bufs), bufs[b("Wg")], topk)
        out = {b(f"disp_w_{self._c}"): slot_weights(
            w_tk, bufs[b(f"slot_tk_{self._c}")])}
        if a.zero_experts:
            out[b(f"zero_w_{self._c}")] = jnp.sum(
                jnp.where(topk >= a.n_experts, w_tk, 0.0), axis=1,
                keepdims=True)
        return out


class SharedExpert(_ChunkOp):
    """Chunk ``c`` through the shared expert (every token, no routing, no
    exchange): the MLP of the experts' form at width ``shared_ff``."""

    def reads(self):
        b = self._b
        return [b("X"), b("Ws1"), b("Ws2")] + (
            [b("Ws3")] if self._args.gated else [])

    def writes(self):
        return [self._b(f"shared_out_{self._c}")]

    def apply(self, bufs, ctx):
        b = self._b
        xc = self._tokens(bufs)
        y = _mlp(self._args, xc, bufs[b("Ws1")], bufs.get(b("Ws3")),
                 bufs[b("Ws2")])
        return {b(f"shared_out_{self._c}"): y.astype(xc.dtype)}


class ExpertFFN(_ChunkOp):
    """Run the resident experts' MLP over every received token (the MXU
    compute between the two exchanges): a grouped product over the experts
    held.  Padding slots carry real numbers but combine gives them weight
    0."""

    def reads(self):
        b = self._b
        return [b(f"recv_disp_{self._c}"), b("W1"), b("W2")] + (
            [b("W3")] if self._args.gated else [])

    def writes(self):
        return [self._b(f"ffn_out_{self._c}")]

    def _experts(self, x, w1, w3, w2):
        """``x`` (rows, E_l, cap, d) through expert ``e``'s weights for each
        ``e`` of the second axis."""
        return _mlp(self._args, x, w1, w3, w2, grouped=True)

    def _ffn(self, bufs, x):
        """``x`` (rows, E_l*cap, d), rows by source shard, to the same shape."""
        rows, slots, d = x.shape
        b = self._b
        e_l = bufs[b("W1")].shape[0]  # this shard's experts
        y = self._experts(x.reshape(rows, e_l, slots // e_l, d),
                          bufs[b("W1")], bufs.get(b("W3")), bufs[b("W2")])
        return y.astype(x.dtype).reshape(rows, slots, d)

    def apply(self, bufs, ctx):
        x = bufs[self._b(f"recv_disp_{self._c}")]  # (n_ep, E_l*C, d)
        return {self._b(f"ffn_out_{self._c}"): self._ffn(bufs, x)}

    # -- op-chunking protocol (core/chunking.py, T3): the expert MLP splits
    # over the source-shard rows of the received slot table (the token
    # axis), each partial folding its row slice into the output — so the
    # combine all-to-all (or another chunk's dispatch) can post against the
    # tail partials instead of waiting for the whole FFN.  XLA only: the
    # Pallas subclass owns its internal blocking.
    def chunkable(self) -> bool:
        return True

    def chunk_counts(self) -> List[int]:
        from tenzing_tpu.core.chunking import pow2_counts

        return pow2_counts(self._args.n_ep)

    def split(self, n: int) -> List["ExpertFFNPartial"]:
        e = self._args.n_ep
        if n < 1 or e % n:
            raise ValueError(f"{e} slot-table rows do not split {n} ways")
        return [ExpertFFNPartial(f"{self.name()}.c{n}p{j}", self._c,
                                 self._args, j, n, self._names)
                for j in range(n)]


class ExpertFFNPartial(ExpertFFN):
    """Partial ``j`` of an ``n``-way token split of :class:`ExpertFFN`:
    the MLP over its source-shard row slice, folded into the output buffer
    by an accumulating slice update (read-modify-write — the combine is
    the update chain, so other ops interleave between the partials)."""

    def __init__(self, name: str, c: int, args: MoEArgs, part: int,
                 n_parts: int, names: LayerNames = ALONE):
        super().__init__(name, c, args, names)
        self._part, self._n_parts = part, n_parts

    def chunkable(self) -> bool:
        return False  # a partial never re-splits

    def reads(self):
        return super().reads() + [self._b(f"ffn_out_{self._c}")]

    def apply(self, bufs, ctx):
        from jax import lax

        out = self._b(f"ffn_out_{self._c}")
        x = bufs[self._b(f"recv_disp_{self._c}")]  # (n_ep, E_l*C, d)
        n = x.shape[0]
        if n % self._n_parts:
            # chunk validity was checked against the build-time n_ep —
            # fail at trace time rather than slice partial rows silently
            raise ValueError(
                f"{self.name()}: {n} slot-table rows do not split "
                f"{self._n_parts} ways")
        lo = self._part * (n // self._n_parts)
        y = self._ffn(bufs, x[lo : lo + n // self._n_parts])
        return {out: lax.dynamic_update_slice_in_dim(bufs[out], y, lo, 0)}


class ExpertFFNPallas(ExpertFFN):
    """Same MLP through the Pallas tiled-matmul kernel (ops/ffn_pallas.py):
    the gelu form, one expert at a time."""

    def _experts(self, x, w1, w3, w2):
        import jax.numpy as jnp

        from tenzing_tpu.ops.ffn_pallas import ffn_pallas

        if self._args.gated:
            raise NotImplementedError("no gated Pallas expert kernel yet")
        rows, e_l, cap, d = x.shape
        return jnp.stack(
            [ffn_pallas(x[:, e].reshape(rows * cap, d), w1[e],
                        w2[e]).reshape(rows, cap, d) for e in range(e_l)],
            axis=1)

    def uses_pallas(self) -> bool:
        return True

    def chunkable(self) -> bool:
        return False  # the kernel owns its internal blocking


def ffn_chunk_menu(args: MoEArgs, relax: bool = False):
    """(pruned counts, {count: est hidden µs}) for one chunk's expert FFN —
    the roofline sketch constraint (bench/roofline.py::prune_chunkings).
    The neighboring transfer is the combine all-to-all returning the expert
    outputs; ``relax=True`` (tests / toy shapes) keeps every structurally
    valid count."""
    from tenzing_tpu.bench import roofline

    bpe = np.dtype(args.dtype).itemsize
    cap = args.chunk_tokens  # capacity upper bound per (src, dst) pair
    slots = float(args.n_ep * cap)
    d, dff = args.d_model, args.d_ff
    table = slots * d * bpe  # one slot-table pass (the a2a payload)
    cost = roofline.Cost(
        flops=4.0 * slots * d * dff,
        hbm_bytes=2.0 * table + float(2 * d * dff * bpe))
    return roofline.chunk_menu(
        ExpertFFN("probe", 0, args).chunk_counts(), cost,
        comm_us=table / (roofline.V5E_XFER_GBS * 1e9) * 1e6,
        combine_bytes=2.0 * table, relax=relax)


class ExpertFFNChoice(ChoiceOp):
    """Kernel menu for chunk ``c``'s expert MLP: XLA einsums vs Pallas tiles
    (plus T3-style chunked expansions of the XLA kernel when
    ``chunk_counts`` is given — core/chunking.py)."""

    def __init__(self, name: str, c: int, args: MoEArgs,
                 chunk_counts=(), chunk_est=None,
                 names: LayerNames = ALONE):
        super().__init__(name)
        self._c = c
        self._args = args
        self._names = names
        self._chunks = tuple(int(n) for n in chunk_counts if int(n) > 1)
        self._chunk_est = dict(chunk_est or {})
        if chunk_counts:
            from tenzing_tpu.core.chunking import menu_info

            self.chunk_menu = menu_info(name + ".xla", chunk_counts,
                                        self._chunk_est)

    def choices(self) -> List[OpBase]:
        from tenzing_tpu.core.chunking import ChunkedOp

        where = (self._c, self._args, self._names)
        out: List[OpBase] = [
            ExpertFFN(self.name() + ".xla", *where),
            ExpertFFNPallas(self.name() + ".pallas", *where),
        ]
        out += [
            ChunkedOp(ExpertFFN(self.name() + ".xla", *where),
                      n, est_hidden_us=self._chunk_est.get(n))
            for n in self._chunks
        ]
        return out


# -- synthesized all-to-all (collectives/synth.py) --------------------------


def moe_synth_plans(args: MoEArgs, c: int, site: str, cap: int = None,
                    names: LayerNames = ALONE):
    """Ring all-to-all instantiations for chunk ``c``'s dispatch or combine
    exchange (``site`` in ``{"disp", "comb"}``): n-1 single-hop rotations
    replace the fused ``AllToAllStart``, each await free to interleave.
    ``cap`` is the capacity (slots per expert: a peer's row of the table is
    ``experts_per_shard * cap`` wide); the graph-time default
    ``chunk_tokens`` is its upper bound (pricing only — the buffer builder
    passes the routed capacity)."""
    from tenzing_tpu.collectives.synth import plan_ring_all_to_all

    if args.n_ep < 2:
        return []
    cap = int(args.chunk_tokens if cap is None else cap)
    src = f"send_disp_{c}" if site == "disp" else f"ffn_out_{c}"
    dst = f"recv_disp_{c}" if site == "disp" else f"recv_comb_{c}"
    return [plan_ring_all_to_all(
        names.op(f"a2a_{site}_{c}"), names.buf(src), names.buf(dst), AXIS,
        args.n_ep, (args.experts_per_shard * cap, args.d_model),
        itemsize=np.dtype(args.dtype).itemsize)]


class CombineScatter(_ChunkOp):
    """Bring the returned expert outputs back into token order: each token
    gathers the slots its ``top_k`` experts answered in and sums them,
    scaled by the slots' combine weights, in float32, on top of the shared
    expert's output where the layer has one.  A zero expert's pick answers
    in no slot: its weight is masked out of the sum (``topk_c``), and the
    token itself, times the sum of such picks' weights (``zero_w_c``), is
    added on this shard: the identity experts' term."""

    def reads(self):
        c, b, a = self._c, self._b, self._args
        return ([b(f"recv_comb_{c}"), b(f"comb_idx_{c}"), b(f"disp_w_{c}")]
                + ([b(f"shared_out_{c}")] if a.shared_ff else [])
                + ([b("X"), b(f"topk_{c}"), b(f"zero_w_{c}")]
                   if a.zero_experts else []))

    def writes(self):
        return [self._b(f"Y_{self._c}")]

    def apply(self, bufs, ctx):
        import jax.numpy as jnp

        c, b, a = self._c, self._b, self._args
        vals = bufs[b(f"recv_comb_{c}")]  # (n_ep, E_l*C, d) by expert shard
        d = vals.shape[-1]
        vals = vals.reshape(-1, d)
        idx = bufs[b(f"comb_idx_{c}")]  # (Tc, top_k) flat slots
        w = bufs[b(f"disp_w_{c}")].reshape(-1)[idx].astype(jnp.float32)
        if a.shared_ff:
            y = bufs[b(f"shared_out_{c}")].astype(jnp.float32)
        else:
            y = jnp.zeros((a.chunk_tokens, d), jnp.float32)
        if a.zero_experts:
            w = jnp.where(bufs[b(f"topk_{c}")] < a.n_experts, w, 0.0)
            y = y + bufs[b(f"zero_w_{c}")] * self._tokens(bufs).astype(
                jnp.float32)
        for k in range(a.top_k):  # a fixed order of sums
            y = y + w[:, k, None] * vals[idx[:, k]].astype(jnp.float32)
        return {b(f"Y_{c}"): y.astype(vals.dtype)}


class ConcatChunks(DeviceOp):
    """Stitch the per-chunk outputs back into the token-order output."""

    def __init__(self, name: str, args: MoEArgs, names: LayerNames = ALONE):
        super().__init__(name)
        self._args = args
        self._b = names.buf

    def reads(self):
        return [self._b(f"Y_{c}") for c in range(self._args.n_chunks)]

    def writes(self):
        return [self._b("Y")]

    def apply(self, bufs, ctx):
        import jax.numpy as jnp

        return {
            self._b("Y"): jnp.concatenate(
                [bufs[name] for name in self.reads()], axis=0
            )
        }


class MoELayer(CompoundOp):
    """The whole EP layer as one compound: ``n_chunks`` independent
    dispatch -> expert -> combine chains joined by the final concat, each
    combine also fed by the chunk's gate (sigmoid scoring) and shared
    expert where the layer has them.  With
    ``impl_choice`` each chunk's FFN kernel is searched; ``chunk=True``
    adds T3-style chunked expert-FFN alternatives to the menus
    (core/chunking.py; :func:`ffn_chunk_menu` prunes the counts through
    the roofline — ``chunk_relax`` skips the pruning, the tests mode).
    ``synth=True`` puts synthesized ring all-to-all decompositions
    (collectives/synth.py) next to each chunk's fused dispatch/combine
    exchange in one ChooseOp; ``synth_relax`` keeps analytically-dominated
    instantiations searchable.  ``names`` (:class:`LayerNames`) makes it one
    layer of several in a graph."""

    def __init__(self, args: MoEArgs, name: str = "moe",
                 impl_choice: bool = False, chunk: bool = False,
                 chunk_relax: bool = False, synth: bool = False,
                 synth_relax: bool = False, names: LayerNames = ALONE):
        super().__init__(name)
        self._args = args
        self._names = names
        self._impl_choice = impl_choice
        self._chunk = chunk
        self._chunk_relax = chunk_relax
        self._synth = synth
        self._synth_relax = synth_relax

    def args(self) -> MoEArgs:
        return self._args

    def graph(self) -> Graph:
        g = Graph()
        nm = self._names
        cat = ConcatChunks(nm.op("moe_concat"), self._args, nm)
        counts, est = ((), None)
        if self._chunk:
            counts, est = ffn_chunk_menu(self._args,
                                         relax=self._chunk_relax)
        if self._impl_choice:
            mk = lambda name, c_, a_, nm_: ExpertFFNChoice(
                name, c_, a_, chunk_counts=counts, chunk_est=est, names=nm_)
        elif any(int(n) > 1 for n in counts):
            from tenzing_tpu.core.chunking import ChunkChoice, chunk_variants

            def mk(name, c_, a_, nm_):
                op = ExpertFFN(name, c_, a_, nm_)
                return ChunkChoice(op, chunk_variants(op, counts, est))
        else:
            mk = ExpertFFN

        def a2a(site, src, dst, prev, nxt):
            base = nm.op(f"a2a_{site}_{c}")
            src, dst = nm.buf(src), nm.buf(dst)
            start = AllToAllStart(base, src, dst, AXIS, split_axis=0)
            await_ = AwaitTransfer(nm.op(f"await_{site}_{c}"), dst)
            if self._synth and self._args.n_ep >= 2:
                from tenzing_tpu.collectives.synth import (
                    FixedCollective, SynthCollectiveChoice, sketch_menu)
                from tenzing_tpu.collectives.topology import mesh_topology

                a = self._args
                cap = a.chunk_tokens  # capacity upper bound for pricing
                bpe = np.dtype(a.dtype).itemsize
                variants, menu = sketch_menu(
                    moe_synth_plans(a, c, site, names=nm),
                    mesh_topology({AXIS: a.n_ep}, host=False),
                    fixed_bytes=float(a.n_ep * cap * a.d_model * bpe),
                    relax=self._synth_relax, collective="all_to_all")
                if variants:
                    node = SynthCollectiveChoice(
                        base, FixedCollective(base, [start, await_]),
                        variants, menu)
                    g.then(prev, node)
                    g.then(node, nxt)
                    return
            g.then(prev, start)
            g.then(start, await_)
            g.then(await_, nxt)

        for c in range(self._args.n_chunks):
            pack = DispatchPack(nm.op(f"pack_{c}"), c, self._args, nm)
            ffn = mk(nm.op(f"ffn_{c}"), c, self._args, nm)
            scat = CombineScatter(nm.op(f"combine_{c}"), c, self._args, nm)
            g.start_then(pack)
            a2a("disp", f"send_disp_{c}", f"recv_disp_{c}", pack, ffn)
            a2a("comb", f"ffn_out_{c}", f"recv_comb_{c}", ffn, scat)
            # the chains that cross no chip: free to run under either
            # exchange of any chunk
            if self._args.gate_in_iteration:
                gate = GateWeights(nm.op(f"gate_{c}"), c, self._args, nm)
                g.start_then(gate)
                g.then(gate, scat)
            if self._args.shared_ff:
                shared = SharedExpert(nm.op(f"shared_{c}"), c, self._args,
                                      nm)
                g.start_then(shared)
                g.then(shared, scat)
            g.then(scat, cat)
        g.then_finish(cat)
        return g


def buffer_layout(args: MoEArgs, cap: int = 1, names: LayerNames = ALONE,
                  router_dtype: Optional[str] = None) -> Dict[str, tuple]:
    """``{name: (global shape, dtype, partition spec)}`` of every buffer of
    the layer on the ``("ep",)`` mesh at ``cap`` slots per (source shard,
    expert, chunk): tokens, slot tables and exchange buffers by shard,
    expert weights by expert, the router (``n_router`` columns, the zero
    experts' last; ``router_dtype``, the layer's where not given) and the
    shared expert replicated.  Data and tables are made at set-up; what the
    iteration writes starts at zero."""
    return {names.buf(base): v for base, v in _layout(
        args, cap, router_dtype or args.dtype).items()}


def _layout(args: MoEArgs, cap: int, router_dtype: str) -> Dict[str, tuple]:
    """:func:`buffer_layout` under the layer's plain names."""
    from jax.sharding import PartitionSpec as P

    n, d, t = args.n_ep, args.d_model, args.tokens_per_shard
    tc, n_e, k, dt = args.chunk_tokens, args.n_experts, args.top_k, args.dtype
    slots = args.experts_per_shard * cap
    rows, stacked, everywhere = P(AXIS, None), P(AXIS, None, None), P()
    out = {"X": ((n * t, d), dt, rows), "Y": ((n * t, d), dt, rows),
           "W1": ((n_e, d, args.d_ff), dt, stacked),
           "W2": ((n_e, args.d_ff, d), dt, stacked)}
    if args.shared_ff:
        out["Ws1"] = ((d, args.shared_ff), dt, everywhere)
        out["Ws2"] = ((args.shared_ff, d), dt, everywhere)
    if args.gated:
        out["W3"] = out["W1"]
        if args.shared_ff:
            out["Ws3"] = out["Ws1"]
    if args.gate_in_iteration:
        out["Wg"] = ((d, args.n_router), router_dtype, everywhere)
    for c in range(args.n_chunks):
        for nm in ("send_disp", "recv_disp", "ffn_out", "recv_comb"):
            out[f"{nm}_{c}"] = ((n * n, slots, d), dt, stacked)
        out[f"Y_{c}"] = ((n * tc, d), dt, rows)
        out[f"disp_idx_{c}"] = ((n, n, slots), "int32", stacked)
        out[f"comb_idx_{c}"] = ((n * tc, k), "int32", rows)
        # in-iteration weights are float32; fixed ones the layer's dtype
        out[f"disp_w_{c}"] = ((n, n, slots), "float32"
                              if args.gate_in_iteration else dt, stacked)
        if args.gate_in_iteration:
            out[f"slot_tk_{c}"] = ((n, n, slots), "int32", stacked)
            out[f"topk_{c}"] = ((n * tc, k), "int32", rows)
        if args.zero_experts:
            out[f"zero_w_{c}"] = ((n * tc, 1), "float32", rows)
        if args.shared_ff:
            out[f"shared_out_{c}"] = ((n * tc, d), dt, rows)
    return out


def layer_specs(args: MoEArgs, names: LayerNames = ALONE
                ) -> Dict[str, object]:
    """Partition spec of every buffer of the layer (:func:`buffer_layout`)."""
    return {name: spec
            for name, (_, _, spec) in buffer_layout(args, 1, names).items()}


def note_routing(args: MoEArgs, cap: int, loads) -> int:
    """Counters of what the set-up negotiation did, from the loads
    ``(..., n_experts)`` of every (shard, chunk): ``moe.capacity_slots``
    (slots the exchange carries), ``moe.routed_slots`` (slots that hold a
    token), ``moe.max_expert_load`` (largest load of one (shard, expert,
    chunk)), ``moe.dropped_slots`` (selections beyond capacity) and, for a
    layer with zero experts, ``moe.zero_picks`` (selections that need no
    slot: every token makes ``top_k``, and the loads count the real ones).
    Returns the dropped, and refuses a layer that drops: no token may be."""
    from tenzing_tpu.obs.metrics import get_metrics

    loads = np.asarray(loads).reshape(-1, args.n_experts)
    dropped = int(np.maximum(loads - cap, 0).sum())
    reg = get_metrics()
    if args.zero_experts:
        picks = (loads.shape[0] // args.n_chunks) * args.tokens_per_shard \
            * args.top_k
        reg.counter("moe.zero_picks").inc(picks - int(loads.sum()))
    for name, n in (("capacity_slots", loads.size * cap),
                    ("routed_slots", int(loads.sum()) - dropped),
                    ("max_expert_load", int(loads.max())),
                    ("dropped_slots", dropped)):
        reg.counter(f"moe.{name}").inc(n)
    if dropped:
        raise ValueError(
            f"{dropped} selection(s) beyond the capacity of {cap} slots per "
            f"(shard, expert, chunk), largest load {int(loads.max())}: the "
            "layer drops no token; raise capacity_factor")
    return dropped


def mesh_moe_buffers(args: MoEArgs, mesh, data: Dict[str, object],
                     names: LayerNames = ALONE, route_on=None):
    """``(buffers, specs)`` for a layer whose weights are made in the
    iteration on ``mesh`` (``("ep",)``) from ``data`` that already lie there
    under :func:`layer_specs` (``X``, the expert weights, ``Wg``, the shared
    expert; ``gate_bias`` replicated, zeros if absent): the set-up
    negotiation and the zeroed work buffers, every shard's made on its own
    device — nothing of the global size passes through the host.  Needs a
    fixed capacity (``capacity_factor``): the shapes are then the same for
    every seed.  Raises where a selection finds no slot.

    The selection is taken from ``route_on`` (rows by shard as ``X``; ``X``
    itself where not given): a layer whose input another vertex writes in
    the iteration has no ``X`` at set-up, and is handed the float32
    forward's.  ``Wg`` has ``n_router`` columns; a zero expert (a column at
    or above ``n_experts``, see :class:`MoEArgs`) is selected like any
    other and given no slot."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tenzing_tpu.obs.tracer import get_tracer

    cap = args.fixed_capacity()
    if cap is None or not args.gate_in_iteration:
        raise ValueError("a layer made on the mesh needs capacity_factor and "
                         "its weights made in the iteration "
                         "(make_moe_buffers makes the rest)")
    b = names.buf
    wg = data[b("Wg")]
    layout = buffer_layout(args, cap, names, router_dtype=str(wg.dtype))
    specs = {name: spec for name, (_, _, spec) in layout.items()}
    bias = data.get(b("gate_bias"))
    if bias is None:
        bias = jnp.zeros((args.n_router,), jnp.float32)
    if route_on is None:
        route_on = data[b("X")]

    def route(x, wg, b):
        sel = select_experts(args, x, wg, b)
        return slot_tables(sel, args, cap), expert_loads(sel, args)[None]

    with get_tracer().span("moe.route", n_ep=args.n_ep, capacity=cap):
        table_specs = {f"{nm}_{c}": specs[b(f"{nm}_{c}")]
                       for c in range(args.n_chunks)
                       for nm in ("disp_idx", "slot_tk", "comb_idx", "topk")}
        tables, loads = jax.jit(jax.shard_map(
            route, mesh=mesh, in_specs=(specs[b("X")], P(), P()),
            out_specs=(table_specs, P(AXIS, None, None))))(
                route_on, wg, bias)
        note_routing(args, cap, jax.device_get(loads))
    bufs = {k: data[k] for k in specs if k in data}
    bufs.update({b(k): v for k, v in tables.items()})
    for name, (shape, dtype, spec) in layout.items():
        if name not in bufs:  # what the iteration writes
            bufs[name] = jnp.zeros(shape, dtype,
                                   device=NamedSharding(mesh, spec))
    return bufs, specs


def make_moe_buffers(
    args: MoEArgs, seed: int = 0, synth: bool = False
) -> Tuple[Dict[str, np.ndarray], Dict[str, object], np.ndarray]:
    """(buffers, partition specs, expected Y) for the EP layer on a 1-D
    ``("ep",)`` mesh, as host arrays (tests and small sizes;
    :func:`mesh_moe_buffers` makes a layer of real size on its devices).
    The selection runs here against a fixed random gate matrix — the
    setup-negotiation analog; its product is the static slot tables the
    device ops consume.  Expected Y: a dense float64 evaluation of the
    routed softmax layer, the plain reference
    (models/moe_reference.py) for the sigmoid one."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    n, t, d, dff = args.n_ep, args.tokens_per_shard, args.d_model, args.d_ff
    n_e, k = args.n_experts, args.top_k
    dt = jnp.dtype(args.dtype)
    if not args.gate_in_iteration and (k != 1 or args.gated
                                       or args.shared_ff):
        raise ValueError("softmax scoring is the top-1 gelu layer")

    def draw(*shape, fan_in=None):
        a = rng.standard_normal(shape)
        return (a / np.sqrt(fan_in) if fan_in else a).astype(dt)

    x = draw(n * t, d)
    wg = draw(d, n_e)
    bufs: Dict[str, np.ndarray] = {
        "X": x, "W1": draw(n_e, d, dff, fan_in=d),
        "W2": draw(n_e, dff, d, fan_in=dff)}
    if args.gated:
        bufs["W3"] = draw(n_e, d, dff, fan_in=d)
    if args.shared_ff:
        bufs["Ws1"] = draw(d, args.shared_ff, fan_in=d)
        bufs["Ws2"] = draw(args.shared_ff, d, fan_in=args.shared_ff)
        if args.gated:
            bufs["Ws3"] = draw(d, args.shared_ff, fan_in=d)

    # the selection, and for softmax scoring the fixed weights
    if args.gate_in_iteration:
        bufs["Wg"] = wg / np.sqrt(d).astype(dt)
        sel = np.asarray(select_experts(args, x, bufs["Wg"],
                                        np.zeros(n_e, np.float32)))
        gate = None
    else:
        expert, gate = top1_route(x, wg)
        sel = expert[:, None].astype(np.int32)
    per_shard = sel.reshape(n, t, k)
    loads = np.stack([np.asarray(expert_loads(s, args)) for s in per_shard])
    cap = args.fixed_capacity() or max(1, int(loads.max()))
    note_routing(args, cap, loads)

    specs = layer_specs(args)
    tables = [slot_tables(s, args, cap) for s in per_shard]
    for name in tables[0]:
        if name in specs:
            bufs[name] = np.concatenate([np.asarray(tb[name])
                                         for tb in tables])
    if gate is not None:
        tc_ = args.chunk_tokens
        for c in range(args.n_chunks):
            bufs[f"disp_w_{c}"] = np.concatenate([
                np.asarray(slot_weights(
                    gate[s * t + c * tc_:s * t + (c + 1) * tc_, None].astype(dt),
                    tables[s][f"slot_tk_{c}"])) for s in range(n)]).astype(dt)
    for name, (shape, dtype, _) in buffer_layout(args, cap).items():
        if name not in bufs:  # what the iteration writes
            bufs[name] = np.zeros(shape, jnp.dtype(dtype))
    if synth:
        # staging buffers for the synthesized ring all-to-all: plans
        # price against the chunk_tokens upper bound, but allocation
        # uses the routed capacity so runtime shapes line up
        from jax.sharding import PartitionSpec as P

        for c in range(args.n_chunks):
            for site in ("disp", "comb"):
                for plan in moe_synth_plans(args, c, site, cap=cap):
                    for decl in plan.buffers:
                        if decl.name in bufs:
                            continue
                        gshape = ((n * decl.shape[0],)
                                  + tuple(decl.shape[1:]))
                        bufs[decl.name] = np.zeros(gshape, dt)
                        specs[decl.name] = P(
                            AXIS, *([None] * (len(gshape) - 1)))

    if args.gate_in_iteration:
        from tenzing_tpu.models.moe_reference import moe_layer

        shared = ((bufs["Ws1"], bufs["Ws3"], bufs["Ws2"])
                  if args.shared_ff else None)
        want = np.asarray(moe_layer(
            jnp.asarray(x), bufs["Wg"], np.zeros(n_e, np.float32),
            bufs["W1"], bufs["W3"], bufs["W2"], shared, k,
            args.routed_scale))
        return bufs, specs, want.astype(dt)
    # dense host reference: y[t] = gate * expert_e(x[t]) in float64
    x64 = x.astype(np.float64)
    want = np.zeros((n * t, d), np.float64)
    for e in range(n_e):
        sel_e = expert == e
        h = _gelu(x64[sel_e] @ bufs["W1"][e].astype(np.float64))
        want[sel_e] = gate[sel_e, None] * (
            h @ bufs["W2"][e].astype(np.float64))
    # expected cast to the workload dtype (ADVICE r2) so a bf16 config
    # compares bf16-vs-bf16; callers comparing a non-f32 config must
    # choose tolerances to match (~0.4% relative at bf16)
    return bufs, specs, want.astype(dt)
