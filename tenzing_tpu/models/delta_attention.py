"""One decode step of Kimi Delta Attention (KDA) layers on a recurrent state,
as a searchable op DAG, and the hybrid period that strings them with
``models/latent_attention.py``'s MLA layer (Kimi-Linear: three KDA layers,
then one latent-attention layer without positional encoding).

A KDA layer keeps, per sequence, a state of fixed size: ``S`` ``(H, d, d)``
float32 (the gated delta rule's fast weights) and the short convolution's
last ``taps - 1`` input rows ``Cv``.  One step reads both whole and writes
both whole; what it costs does not depend on the sequence's length
(``ops/kda_pallas.py`` states the four steps: convolution, gates, state
step, output norm and gate).

**Buffers** of layer ``<l>`` (``buffer_shapes``): the inputs as they would
arrive from the projections left out here (``x.<l>`` the new ``[q ; k ; v]``
row, ``f.<l>`` the decay gate's, ``b.<l>`` beta's and ``go.<l>`` the output
gate's pre-activations), the parameters (``Wc``, ``dt_bias``, ``A_log``,
``w_norm``), the state read (``S.<l>``, ``Cv.<l>``) and the state written
(``Snew.<l>``, ``Cvnew.<l>``), ``o.<l>``, and the chain's intermediates, a
set a group (``y.<l>.g<i>`` ...).
**The step reads ``S`` and writes ``Snew``**: the bytes are those of an
update in place, the footprint twice the state's, and an iteration is the
same step again (n repeats leave every buffer as one leaves it), as the
other decode steps' lengths that do not advance.

Sequences are cut into ``groups`` equal runs (the cost does not depend on a
length, so any run will do); each (layer, group) is one
:class:`KdaEngineChoice`: **one fused ``kda_step`` kernel** (steps 1 to 4
in one pass: ``S`` read once, ``Snew`` written once) **or the chain of the
four XLA vertices** (:class:`ConvStep`, :class:`Gates`, :class:`KdaStep`,
:class:`OutNorm`), which passes the state through HBM four times.  A
vertex writes its group's rows of the layer's buffers in place.

The program's counters (at trace time, once a traced body): ``kda.rows``
(sequences stepped), ``kda.state_bytes`` (bytes of ``S``, ``Snew``, ``Cv``
and ``Cvnew`` the traced vertices move, from shapes and engine),
``kda.state_min_bytes`` (one read and one write of each),
``kda.fused_vertices`` and ``kda.chain_vertices``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Sequence, Tuple

import numpy as np

from tenzing_tpu.core.graph import Graph
from tenzing_tpu.core.operation import ChoiceOp, CompoundOp, DeviceOp, OpBase

#: passes over a group's state through HBM: the kernel reads ``S`` and
#: writes ``Snew``; the XLA chain reads ``S`` for ``k^T S'`` and again for
#: ``Snew``, writes ``Snew`` and reads it back for ``o``
FUSED_PASSES, CHAIN_PASSES = 2, 4


@dataclass(frozen=True)
class DeltaDecodeArgs:
    batch: int
    heads: int = 32
    d: int = 128           # linear_attn_config.head_dim, keys and values
    taps: int = 4          # short_conv_kernel_size
    groups: int = 4
    eps: float = 1e-5      # rms_norm_eps of the output norm
    dtype: str = "bfloat16"

    def __post_init__(self):
        if self.batch % self.groups:
            raise ValueError(f"{self.batch} sequences in {self.groups} "
                             "groups")

    @property
    def rows(self) -> int:
        """Sequences a group."""
        return self.batch // self.groups

    @property
    def state_bytes(self) -> int:
        """Bytes of one sequence's ``S`` in a layer."""
        return self.heads * self.d * self.d * 4

    @property
    def conv_bytes(self) -> int:
        """Bytes of one sequence's ``Cv`` in a layer."""
        return (self.taps - 1) * 3 * self.heads * self.d * (
            2 if self.dtype == "bfloat16" else np.dtype(self.dtype).itemsize)


_LAYER = ("x", "Cv", "Cvnew", "Wc", "f", "dt_bias", "A_log", "b", "go",
          "w_norm", "S", "Snew", "o")
#: the chain's intermediates, a buffer a (layer, group): what only one engine
#: writes is that engine's scratch, and a schedule that runs a group on the
#: kernel leaves that group's untouched (the driver's integrity gate compares
#: the buffers both schedules write)
_SCRATCH = ("y", "qkv", "decay", "beta", "o_raw")


def _names(layer: str, group: int = None) -> Dict[str, str]:
    t = f".{layer}" if layer else ""
    n = {k: k + t for k in _LAYER}
    if group is not None:
        n.update({k: f"{k}{t}.g{group}" for k in _SCRATCH})
    return n


def _prefix(layer: str, group: int) -> str:
    return (f"{layer}." if layer else "") + f"g{group}."


def note_state(args: DeltaDecodeArgs, passes: int, conv: bool = True
               ) -> None:
    """The program's counters for one traced vertex that moves a group's
    state ``passes`` times through HBM (0: none of ``S``) and, with
    ``conv``, its convolution windows in and out."""
    from tenzing_tpu.obs.metrics import get_metrics

    reg = get_metrics()
    window = 2 * args.conv_bytes if conv else 0
    reg.counter("kda.state_bytes").inc(
        args.rows * (passes * args.state_bytes + window))
    reg.counter("kda.state_min_bytes").inc(
        args.rows * ((FUSED_PASSES * args.state_bytes if passes else 0)
                     + window))
    if passes:
        reg.counter("kda.rows").inc(args.rows)
        reg.counter("kda.fused_vertices" if passes == FUSED_PASSES
                    else "kda.chain_vertices").inc()


class _GroupOp(DeviceOp):
    """A vertex over the rows of one group of one layer: reads its rows of
    :attr:`READS`, writes its rows of :attr:`WRITES` in place (a layer's
    buffers) or whole (its group's scratch)."""

    READS: Tuple[str, ...] = ()
    WRITES: Tuple[str, ...] = ()
    # no sequence axis, or the group's own
    WHOLE = ("Wc", "dt_bias", "A_log", "w_norm") + _SCRATCH

    def __init__(self, name: str, args: DeltaDecodeArgs, group: int,
                 layer: str = ""):
        super().__init__(name)
        self._args, self._lead0 = args, group * args.rows
        self._n = _names(layer, group)

    def reads(self):
        return [self._n[k] for k in self.READS + self.WRITES]

    def writes(self):
        return [self._n[k] for k in self.WRITES]

    def _rows(self, bufs, key: str):
        from jax import lax

        x = bufs[self._n[key]]
        if key in self.WHOLE:
            return x
        return lax.slice_in_dim(x, self._lead0, self._lead0 + self._args.rows)

    def _put(self, bufs, **rows):
        from jax import lax

        out = {}
        for k, v in rows.items():
            whole = bufs[self._n[k]]
            v = v.astype(whole.dtype)
            out[self._n[k]] = v if k in _SCRATCH else \
                lax.dynamic_update_slice_in_dim(whole, v, self._lead0, 0)
        return out


class ConvStep(_GroupOp):
    """Step 1: the short convolution's output for the new row, and the
    window moved on by one."""

    READS = ("x", "Cv", "Wc")
    WRITES = ("y", "Cvnew")

    def apply(self, bufs, ctx):
        from tenzing_tpu.ops.kda_pallas import conv_step

        note_state(self._args, 0)
        y, moved = conv_step(*(self._rows(bufs, k) for k in self.READS))
        return self._put(bufs, y=y, Cvnew=moved)


class Gates(_GroupOp):
    """Step 2: normalised ``q`` and ``k``, ``v``, the decay ``exp(g)`` a key
    channel, beta."""

    READS = ("y", "f", "dt_bias", "A_log", "b")
    WRITES = ("qkv", "decay", "beta")

    def apply(self, bufs, ctx):
        import jax.numpy as jnp

        from tenzing_tpu.ops.kda_pallas import gates

        q, k, v, decay, beta = gates(
            *(self._rows(bufs, k) for k in self.READS))
        return self._put(bufs, qkv=jnp.stack([q, k, v], axis=1),
                         decay=decay, beta=beta)


class KdaStep(_GroupOp):
    """Step 3 in XLA: decay, rank-one correction and read-out, float32 on
    the VPU; the state passes through HBM :data:`CHAIN_PASSES` times."""

    READS = ("S", "qkv", "decay", "beta")
    WRITES = ("Snew", "o_raw")

    def apply(self, bufs, ctx):
        from tenzing_tpu.ops.kda_pallas import state_step

        note_state(self._args, CHAIN_PASSES, conv=False)
        s, qkv, decay, beta = (self._rows(bufs, k) for k in self.READS)
        snew, o = state_step(s, qkv[:, 0], qkv[:, 1], qkv[:, 2], decay, beta)
        return self._put(bufs, Snew=snew, o_raw=o)


class OutNorm(_GroupOp):
    """Step 4: the output's norm a head, and its gate."""

    READS = ("o_raw", "go", "w_norm")
    WRITES = ("o",)

    def apply(self, bufs, ctx):
        from tenzing_tpu.ops.kda_pallas import out_norm

        return self._put(bufs, o=out_norm(
            *(self._rows(bufs, k) for k in self.READS), self._args.eps))


class KdaFused(_GroupOp):
    """Steps 1 to 4 of a group in one ``kda_step`` kernel: ``S`` read once,
    ``Snew`` written once, its rows of ``Snew``, ``Cvnew`` and ``o`` in
    place."""

    READS = ("x", "Cv", "Wc", "f", "dt_bias", "A_log", "b", "go", "w_norm",
             "S")
    WRITES = ("Snew", "Cvnew", "o")

    def apply(self, bufs, ctx):
        from tenzing_tpu.ops.kda_pallas import kda_step_pallas

        a, n = self._args, self._n
        note_state(a, FUSED_PASSES)
        out = kda_step_pallas(
            *(bufs[n[k]] for k in self.READS + self.WRITES),
            lead0=self._lead0, rows=a.rows, eps=a.eps)
        return dict(zip((n[k] for k in self.WRITES), out))

    def uses_pallas(self) -> bool:
        return True


class KdaChain(CompoundOp):
    """A group's four XLA vertices as one expandable vertex, in order."""

    def __init__(self, name: str, args: DeltaDecodeArgs, group: int,
                 layer: str = ""):
        super().__init__(name)
        self._where = (args, group, layer)

    def graph(self) -> Graph:
        g = Graph()
        pre = _prefix(self._where[2], self._where[1])
        ops = [cls(pre + name, *self._where) for name, cls in (
            ("conv_step", ConvStep), ("gates", Gates),
            ("kda_state", KdaStep), ("out_norm", OutNorm))]
        g.start_then(ops[0])
        for a, b in zip(ops, ops[1:]):
            g.then(a, b)
        g.then_finish(ops[-1])
        return g


class KdaEngineChoice(ChoiceOp):
    """Engine menu of one (layer, group): the XLA chain or the one fused
    kernel (``MlaEngineChoice``'s pattern and suffixes)."""

    def __init__(self, args: DeltaDecodeArgs, group: int, layer: str = ""):
        super().__init__(_prefix(layer, group) + "kda")
        self._where = (args, group, layer)

    def choices(self) -> List[OpBase]:
        return [KdaChain(self.name() + ".chain", *self._where),
                KdaFused(self.name() + ".fused", *self._where)]


def add_kda_layer(g: Graph, args: DeltaDecodeArgs, tag: str,
                  after: Sequence[OpBase]) -> List[OpBase]:
    """One KDA layer's engine menus, side by side, each behind every vertex
    of ``after`` (none: behind the graph's start); returns them."""
    menus = [KdaEngineChoice(args, i, tag) for i in range(args.groups)]
    for m in menus:
        if not after:
            g.start_then(m)
        for prev in after:
            g.then(prev, m)
    return menus


def kda_graph(args: DeltaDecodeArgs, layers) -> Graph:
    """KDA layers one after another, as the residual stream orders them
    (layer l+1 starts when layer l's ``o`` is final)."""
    return hybrid_decode_graph(args, None, [("kda", t) for t in layers])


def hybrid_decode_graph(kda_args: DeltaDecodeArgs, mla_args, pattern,
                        impl_choice: bool = False) -> Graph:
    """The layers of ``pattern`` (``[(kind, tag)]``, kind ``"kda"`` or
    ``"mla"``) in the residual stream's order: a layer's first vertices
    start when the layer before's ``o`` is final.  An MLA layer is
    ``models/latent_attention.py``'s (append and absorb, the groups' engine
    menus by sorted length, the up-projection)."""
    from tenzing_tpu.models.latent_attention import add_layer, decode_plan

    g = Graph()
    plan = None
    last: List[OpBase] = []
    for kind, tag in pattern:
        if kind == "kda":
            last = add_kda_layer(g, kda_args, tag, last)
        elif kind == "mla":
            plan = plan or decode_plan(mla_args)
            last = [add_layer(g, mla_args, plan, tag, last, impl_choice)]
        else:
            raise ValueError(f"layer kind {kind!r}")
    for op in last:
        g.then_finish(op)
    return g


def buffer_shapes(args: DeltaDecodeArgs, layers) -> Dict[str, tuple]:
    """``{name: (shape, dtype)}`` of the KDA layers' buffers."""
    a, dt, f32 = args, args.dtype, "float32"
    b, h, d, t = a.batch, a.heads, a.d, a.taps
    out = {}
    for tag in layers:
        n = _names(tag)
        out.update({
            n["x"]: ((b, 3, h, d), dt), n["Cv"]: ((b, t - 1, 3, h, d), dt),
            n["Cvnew"]: ((b, t - 1, 3, h, d), dt),
            n["Wc"]: ((t, 3, h, d), dt), n["f"]: ((b, h, d), dt),
            n["dt_bias"]: ((h, d), f32), n["A_log"]: ((h, 1), f32),
            n["b"]: ((b, h, 1), dt), n["go"]: ((b, h, d), dt),
            n["w_norm"]: ((1, d), f32),
            n["S"]: ((b, h, d, d), f32), n["Snew"]: ((b, h, d, d), f32),
            n["o"]: ((b, h, d), dt)})
        r = a.rows
        for group in range(a.groups):
            n = _names(tag, group)
            out.update({
                n["y"]: ((r, 3, h, d), f32), n["qkv"]: ((r, 3, h, d), f32),
                n["decay"]: ((r, h, d), f32), n["beta"]: ((r, h, 1), f32),
                n["o_raw"]: ((r, h, d), f32)})
    return out


#: the decay a step and key channel wanted of the draws: ``exp(g)`` between
#: 0.2 and 0.999 (a state that forgets within a few tokens on some channels
#: and holds for a thousand on others)
DECAY_RANGE = (0.2, 0.999)


def draw_layer(args: DeltaDecodeArgs, rng, zero_state: bool = False
               ) -> Dict[str, np.ndarray]:
    """One layer's inputs, parameters and state (float64, named without the
    layer's tag).  ``A_log`` and ``dt_bias`` are drawn so that with ``f``
    standard normal the decay lies in :data:`DECAY_RANGE` for all but the
    tails: ``-g = exp(A_log) softplus(f + dt_bias)`` log-uniform a channel
    between ``-ln 0.999`` and ``-ln 0.2`` at ``f = 0``.  ``S`` is a state
    after many tokens, not zeros: rows of the size a unit-norm key's
    rank-one updates leave."""
    a = args
    b, h, d, t = a.batch, a.heads, a.d, a.taps
    lo, hi = (-np.log(x) for x in reversed(DECAY_RANGE))
    a_log = rng.uniform(-0.5, 0.5, (h, 1))
    rate = np.exp(rng.uniform(np.log(lo), np.log(hi), (h, d)))
    sp = rate / np.exp(a_log)               # softplus(dt_bias) wanted
    dt_bias = np.log(np.expm1(sp))
    return {
        "x": rng.standard_normal((b, 3, h, d)),
        "Cv": rng.standard_normal((b, t - 1, 3, h, d)),
        "Wc": rng.standard_normal((t, 3, h, d)) * t ** -0.5,
        "f": rng.standard_normal((b, h, d)) * 0.5,
        "dt_bias": dt_bias, "A_log": a_log,
        "b": rng.standard_normal((b, h, 1)),
        "go": rng.standard_normal((b, h, d)),
        "w_norm": 1.0 + 0.1 * rng.standard_normal((1, d)),
        "S": (np.zeros((b, h, d, d)) if zero_state
              else rng.standard_normal((b, h, d, d)) * d ** -0.5),
    }


def make_kda_buffers(args: DeltaDecodeArgs, layers, seed: int = 0,
                     zero_state: bool = False) -> Dict[str, np.ndarray]:
    """Host buffers of KDA layers at a small size (tests and smoke): what
    :func:`draw_layer` draws in the buffers' dtypes, the written state, the
    output and the chain's intermediates zero."""
    import jax.numpy as jnp

    from tenzing_tpu.obs.tracer import get_tracer

    rng = np.random.default_rng(seed)
    with get_tracer().span("kda.make_buffers", layers=len(layers),
                           batch=args.batch):
        bufs = {name: np.zeros(shape, jnp.dtype(dtype))
                for name, (shape, dtype) in buffer_shapes(args,
                                                          layers).items()}
        for tag in layers:
            for key, x in draw_layer(args, rng, zero_state).items():
                name = _names(tag)[key]
                bufs[name] = x.astype(bufs[name].dtype)
    return bufs
