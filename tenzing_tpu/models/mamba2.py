"""The mixer of a Mamba-2 layer over packed prompts as a searchable op DAG:
one prefill step from what the layer's in-projection delivers (``z``,
``xBC``, ``dt``) to what its out-projection reads (``out``), with no
projection and no residual stream (nemotron_h's ``M`` block between its two
products).  ``H`` heads of ``P`` channels, ``G`` groups of ``N`` state
columns, head ``h`` reading group ``h // (H / G)``; per token ``t`` of a
prompt::

    xc_t   = silu(b_c + sum_{k<taps} w_c[k] xBC_{t-taps+1+k})  # zeros before
    x, B, C = split(xc)                                # the prompt's start
    d_t    = softplus(dt_t + dt_bias);   a_t = exp(d_t A),  A = -exp(A_log)
    S_t    = a_t S_{t-1} + d_t x_t (outer) B_t         # (P, N) float32, S = 0
    y_t    = S_t C_t + D x_t                           # before the prompt
    u      = y silu(z);  out = u rsqrt(mean_group(u^2) + eps) w_norm

The step holds several prompts one after another (``Mamba2Args.lens``): the
convolution takes no tap across a prompt's start, the state restarts there,
and beside ``out`` the layer writes what a decode step would read: each
prompt's final state ``Sfin.<tag>`` ``(prompts, H, P, N)`` float32 and the
last ``taps - 1`` rows of its ``xBC`` before the convolution,
``tail.<tag>``.

**Vertices** of layer ``<tag>`` (:func:`add_layer`; names ``<tag>.<part>``):
:class:`CausalConv` (``conv``), the scan's engine menu
:class:`SsdEngineChoice` (``ssd``) and :class:`GatedGroupNorm`
(``gated_norm``).  The menu: **``.fused``**, one ``ssd_scan`` kernel
(``ops/ssd_pallas.py``: the state in VMEM across a head group's chunks), or
**``.chain``**, the four-step form as four XLA vertices (:class:`SsdDiag`,
:class:`SsdChunkStates`, :class:`SsdStateScan`, :class:`SsdOut`) with the
``(heads, chunks, Q, Q)`` decays, the chunks' states and the diagonal part
through HBM.

**Buffers** (:func:`buffer_shapes`).  A layer's own, ``<kind>.<tag>``: its
inputs (``z``, ``xBC``, ``dt``), its parameters (``Wc``, ``bc``,
``dt_bias``, ``A_log``, ``D``, ``Wgn``) and what it leaves (``out``,
``Sfin``, ``tail``).  The layers of a graph run one after another and share
their work buffers (``ssd.*``: the convolved ``xc``, the scan's ``y`` and
the chain's intermediates): each is written before it is read in every
layer, so an iteration is the same step again.  ``seg`` (a token's prompt)
and ``ends`` (a prompt's last token) describe the packing to every layer.

The program's span ``ssd.plan`` (a layer's vertices made: chunks, head
groups, prompts) and counters (at trace time, once a traced scan):
``ssd.chunks``, ``ssd.boundary_chunks`` (chunks a prompt starts inside of),
``ssd.prompts``, ``ssd.state_bytes_written`` (the final states),
``ssd.fused_vertices`` and ``ssd.chain_vertices``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from tenzing_tpu.core.graph import Graph
from tenzing_tpu.core.operation import ChoiceOp, CompoundOp, DeviceOp, OpBase


@dataclass(frozen=True)
class Mamba2Args:
    """The defaults are NVIDIA-Nemotron-3-Nano's widths."""

    lens: Tuple[int, ...]  # prompt lengths of the packed step, in order
    heads: int = 64        # mamba_num_heads
    head_dim: int = 64     # mamba_head_dim
    groups: int = 8        # n_groups: heads // groups heads share B and C
    state: int = 128       # ssm_state_size
    taps: int = 4          # conv_kernel
    chunk: int = 128       # chunk_size
    eps: float = 1e-5
    dtype: str = "bfloat16"

    def __post_init__(self):
        object.__setattr__(self, "lens", tuple(int(n) for n in self.lens))
        if not self.lens or min(self.lens) < 1:
            raise ValueError(f"prompt lengths {self.lens}")
        if self.heads % self.groups:
            raise ValueError(f"{self.heads} heads in {self.groups} groups")

    @property
    def tokens(self) -> int:
        return sum(self.lens)

    @property
    def prompts(self) -> int:
        return len(self.lens)

    @property
    def starts(self) -> Tuple[int, ...]:
        return tuple(int(s) for s in np.cumsum((0,) + self.lens[:-1]))

    @property
    def inner(self) -> int:
        return self.heads * self.head_dim

    @property
    def conv_width(self) -> int:
        """Channels of ``xBC``: ``x``, then ``B`` and ``C`` a group."""
        return self.inner + 2 * self.groups * self.state

    @property
    def chunks(self) -> int:
        return -(-self.tokens // self.chunk)

    @property
    def boundary_chunks(self) -> int:
        return len({s // self.chunk for s in self.starts if s % self.chunk})

    @property
    def dims(self) -> Dict[str, int]:
        """The scan's static sizes, as ``ops/ssd_pallas.py`` names them."""
        return dict(heads=self.heads, head_dim=self.head_dim,
                    groups=self.groups, state=self.state, chunk=self.chunk)


INPUTS = ("z", "xBC", "dt")
PARAMS = ("Wc", "bc", "dt_bias", "A_log", "D", "Wgn")
LEFT = ("out", "Sfin", "tail")
_WORK = ("xc", "y", "ydiag", "Sloc", "decay", "finloc", "finkeep", "Sin")
PACKING = ("seg", "ends")


def _names(tag: str) -> Dict[str, str]:
    n = {k: f"{k}.{tag}" for k in INPUTS + PARAMS + LEFT}
    n.update({k: f"ssd.{k}" for k in _WORK})
    n.update({k: k for k in PACKING})
    return n


def packing(lens: Sequence[int]) -> Dict[str, np.ndarray]:
    """``seg`` ``(tokens,)`` and ``ends`` ``(prompts,)`` int32 of a step that
    packs prompts of ``lens``."""
    lens = np.asarray(lens, np.int64)
    return {"seg": np.repeat(np.arange(len(lens)), lens).astype(np.int32),
            "ends": (np.cumsum(lens) - 1).astype(np.int32)}


def note_scan(args: Mamba2Args, fused: bool) -> None:
    """The program's counters for one traced scan of a layer."""
    from tenzing_tpu.obs.metrics import get_metrics

    reg = get_metrics()
    for name, n in (
            ("chunks", args.chunks),
            ("boundary_chunks", args.boundary_chunks),
            ("prompts", args.prompts),
            ("state_bytes_written",
             args.prompts * args.inner * args.state * 4)):
        reg.counter(f"ssd.{name}").inc(n)
    reg.counter("ssd.fused_vertices" if fused else "ssd.chain_vertices").inc()


class _LayerOp(DeviceOp):
    """A vertex of one layer: reads :attr:`READS`, writes :attr:`WRITES`
    (kinds of :func:`_names`)."""

    READS: Tuple[str, ...] = ()
    WRITES: Tuple[str, ...] = ()

    def __init__(self, name: str, args: Mamba2Args, tag: str):
        super().__init__(name)
        self._args, self._n = args, _names(tag)

    def reads(self):
        return [self._n[k] for k in self.READS]

    def writes(self):
        return [self._n[k] for k in self.WRITES]

    def _in(self, bufs) -> Dict[str, object]:
        return {k: bufs[self._n[k]] for k in self.READS}

    def _out(self, bufs, *values):
        return {self._n[k]: v.astype(bufs[self._n[k]].dtype)
                for k, v in zip(self.WRITES, values)}


def causal_conv(xbc, wc, bias, seg, ends):
    """``(silu(conv) float32, tails)``: the depthwise convolution of
    ``xbc`` ``(T, channels)`` along the packed tokens, a tap taken only from
    a token of the same prompt; ``tails`` ``(prompts, taps - 1, channels)``
    each prompt's last rows of ``xbc``, zeros before its start."""
    import jax.numpy as jnp

    from tenzing_tpu.ops.kda_pallas import silu

    f32 = jnp.float32
    taps, t = wc.shape[0], xbc.shape[0]
    y = bias.astype(f32) + wc[taps - 1].astype(f32) * xbc.astype(f32)
    for back in range(1, taps):
        same = jnp.pad(seg, (back, 0), constant_values=-1)[:t] == seg
        rows = jnp.pad(xbc, ((back, 0), (0, 0)))[:t]
        y = y + jnp.where(same[:, None],
                          wc[taps - 1 - back].astype(f32) * rows.astype(f32),
                          0.0)
    at = ends[:, None] - jnp.arange(taps - 2, -1, -1, dtype=ends.dtype)
    own = (at >= 0) & (seg[jnp.maximum(at, 0)] == jnp.arange(
        ends.shape[0], dtype=seg.dtype)[:, None])
    tails = jnp.where(own[:, :, None], xbc[jnp.maximum(at, 0)],
                      jnp.zeros((), xbc.dtype))
    return silu(y), tails


class CausalConv(_LayerOp):
    """The depthwise convolution of ``xBC`` with its bias and silu; each
    prompt's last ``taps - 1`` rows go to ``tail.<tag>``."""

    READS = ("xBC", "Wc", "bc", "seg", "ends")
    WRITES = ("xc", "tail")

    def apply(self, bufs, ctx):
        return self._out(bufs, *causal_conv(*self._in(bufs).values()))


class _ScanOp(_LayerOp):
    """A vertex of the scan: :meth:`_scan_in` hands ``ops/ssd_pallas.py``
    its operands by name, the step ``d = softplus(dt + dt_bias)`` and ``A =
    -exp(A_log)`` taken in float32 on the way (64 values a token)."""

    def _scan_in(self, bufs) -> Dict[str, object]:
        import jax.numpy as jnp

        from tenzing_tpu.ops.kda_pallas import softplus

        f32 = jnp.float32
        got = self._in(bufs)
        if "dt" in got:
            got["dt"] = softplus(got["dt"].astype(f32)
                                 + got.pop("dt_bias").astype(f32))
            got["a"] = -jnp.exp(got.pop("A_log").astype(f32))
        if "D" in got:
            got["d_skip"] = got.pop("D")
        return got


_STEP = ("xc", "dt", "dt_bias", "A_log", "seg", "ends")


class SsdFused(_ScanOp):
    """The whole scan of a layer in one ``ssd_scan`` kernel."""

    READS = _STEP + ("D",)
    WRITES = ("y", "Sfin")

    def apply(self, bufs, ctx):
        from tenzing_tpu.ops.ssd_pallas import ssd_chunk_scan

        note_scan(self._args, fused=True)
        return self._out(bufs, *ssd_chunk_scan(**self._scan_in(bufs),
                                               **self._args.dims))

    def uses_pallas(self) -> bool:
        return True


class SsdDiag(_ScanOp):
    """Step 1 in XLA: the diagonal blocks' part of ``y``, float32."""

    READS = _STEP
    WRITES = ("ydiag",)

    def apply(self, bufs, ctx):
        from tenzing_tpu.ops.ssd_pallas import ssd_diag

        return self._out(bufs, ssd_diag(**self._scan_in(bufs),
                                        **self._args.dims))


class SsdChunkStates(_ScanOp):
    """Step 2 in XLA: what each chunk adds to its last prompt's state, and
    each prompt at its last token."""

    READS = _STEP
    WRITES = ("Sloc", "decay", "finloc", "finkeep")

    def apply(self, bufs, ctx):
        from tenzing_tpu.ops.ssd_pallas import ssd_chunk_states

        return self._out(bufs, *ssd_chunk_states(**self._scan_in(bufs),
                                                 **self._args.dims))


class SsdStateScan(_ScanOp):
    """Step 3 in XLA: the scan across chunks."""

    READS = ("Sloc", "decay")
    WRITES = ("Sin",)

    def apply(self, bufs, ctx):
        from tenzing_tpu.ops.ssd_pallas import ssd_state_scan

        got = self._in(bufs)
        return self._out(bufs, ssd_state_scan(got["Sloc"], got["decay"]))


class SsdOut(_ScanOp):
    """Step 4 in XLA: the incoming states' part of ``y``, the skip, and the
    prompts' final states."""

    READS = ("ydiag", "Sin", "finloc", "finkeep") + _STEP + ("D",)
    WRITES = ("y", "Sfin")

    def apply(self, bufs, ctx):
        from tenzing_tpu.ops.ssd_pallas import ssd_out

        note_scan(self._args, fused=False)
        got = self._scan_in(bufs)
        return self._out(bufs, *ssd_out(
            got.pop("ydiag"), got.pop("Sin"), got.pop("finloc"),
            got.pop("finkeep"), **got, **self._args.dims))


class SsdChain(CompoundOp):
    """The four XLA vertices as one expandable vertex: the diagonal blocks
    beside the chunk states, the scan behind the states, the output behind
    both."""

    def __init__(self, name: str, args: Mamba2Args, tag: str):
        super().__init__(name)
        self._where = (args, tag)

    def graph(self) -> Graph:
        g = Graph()
        tag = self._where[1]
        diag, states, scan, out = (
            cls(f"{tag}.{name}", *self._where) for name, cls in (
                ("ssd_diag", SsdDiag), ("ssd_states", SsdChunkStates),
                ("ssd_carry", SsdStateScan), ("ssd_out", SsdOut)))
        g.start_then(diag)
        g.start_then(states)
        g.then(states, scan)
        g.then(diag, out)
        g.then(scan, out)
        g.then_finish(out)
        return g


class SsdEngineChoice(ChoiceOp):
    """Engine menu of one layer's scan: the XLA chain or the one fused
    kernel (``KdaEngineChoice``'s pattern and suffixes)."""

    def __init__(self, args: Mamba2Args, tag: str):
        super().__init__(f"{tag}.ssd")
        self._where = (args, tag)

    def choices(self) -> List[OpBase]:
        return [SsdChain(self.name() + ".chain", *self._where),
                SsdFused(self.name() + ".fused", *self._where)]


class GatedGroupNorm(_LayerOp):
    """``out = GroupRMSNorm(y . silu(z)) . w``: the gate first, then the
    norm over each of the ``groups`` runs of channels, float32."""

    READS = ("y", "z", "Wgn")
    WRITES = ("out",)

    def apply(self, bufs, ctx):
        import jax.numpy as jnp
        from jax import lax

        from tenzing_tpu.ops.kda_pallas import silu

        y, z, w = (t.astype(jnp.float32) for t in self._in(bufs).values())
        u = (y * silu(z)).reshape(y.shape[0], self._args.groups, -1)
        u = u * lax.rsqrt(jnp.mean(u * u, axis=-1, keepdims=True)
                          + self._args.eps)
        return self._out(bufs, u.reshape(y.shape) * w)


def add_layer(g: Graph, args: Mamba2Args, tag: str,
              after: Optional[OpBase] = None) -> OpBase:
    """One layer's vertices into ``g`` behind ``after`` (none: the graph's
    start); returns the layer's last vertex."""
    from tenzing_tpu.obs.tracer import get_tracer

    with get_tracer().span("ssd.plan", layer=tag, chunks=args.chunks,
                           head_groups=args.groups, prompts=args.prompts,
                           boundary_chunks=args.boundary_chunks):
        ops = [CausalConv(f"{tag}.conv", args, tag),
               SsdEngineChoice(args, tag),
               GatedGroupNorm(f"{tag}.gated_norm", args, tag)]
    if after is None:
        g.start_then(ops[0])
    else:
        g.then(after, ops[0])
    for a, b in zip(ops, ops[1:]):
        g.then(a, b)
    return ops[-1]


def buffer_shapes(args: Mamba2Args, tags: Sequence[str]) -> Dict[str, tuple]:
    """``{name: (shape, dtype)}`` of the layers' buffers: each layer's own,
    the shared work buffers, and the packing."""
    a, dt, f32 = args, args.dtype, "float32"
    t, p = a.tokens, a.prompts
    nc, state = a.chunks, (a.heads, a.head_dim, a.state)
    n = _names("")
    out = {"seg": ((t,), "int32"), "ends": ((p,), "int32"),
           n["xc"]: ((t, a.conv_width), dt), n["y"]: ((t, a.inner), dt),
           n["ydiag"]: ((nc * a.chunk, a.inner), f32),
           n["Sloc"]: ((nc,) + state, f32), n["decay"]: ((nc, a.heads), f32),
           n["finloc"]: ((p,) + state, f32),
           n["finkeep"]: ((p, a.heads), f32), n["Sin"]: ((nc,) + state, f32)}
    for tag in tags:
        n = _names(tag)
        out.update({
            n["z"]: ((t, a.inner), dt), n["xBC"]: ((t, a.conv_width), dt),
            n["dt"]: ((t, a.heads), f32),
            n["Wc"]: ((a.taps, a.conv_width), dt),
            n["bc"]: ((a.conv_width,), f32),
            n["dt_bias"]: ((a.heads,), f32), n["A_log"]: ((a.heads,), f32),
            n["D"]: ((a.heads,), f32), n["Wgn"]: ((a.inner,), f32),
            n["out"]: ((t, a.inner), dt), n["Sfin"]: ((p,) + state, f32),
            n["tail"]: ((p, a.taps - 1, a.conv_width), dt)})
    return out


#: the family's initialisation of the decay: ``-A`` uniform on 1 .. 16 and
#: ``dt_bias`` the inverse softplus of a log-uniform step
A_RANGE = (1.0, 16.0)


def draw_layer(args: Mamba2Args, rng, dt_min: float = 1e-3,
               dt_max: float = 0.1) -> Dict[str, np.ndarray]:
    """One layer's inputs and parameters (float64, named without the tag):
    the inputs standard normal, as a projection would deliver them, the
    convolution uniform on +-1/2 (a depthwise kernel of four taps), ``A_log``,
    ``dt_bias`` and ``D`` as the family initialises them."""
    a = args
    step = np.exp(rng.uniform(np.log(dt_min), np.log(dt_max), a.heads))
    return {
        "z": rng.standard_normal((a.tokens, a.inner)),
        "xBC": rng.standard_normal((a.tokens, a.conv_width)),
        "dt": rng.standard_normal((a.tokens, a.heads)),
        "Wc": rng.uniform(-0.5, 0.5, (a.taps, a.conv_width)),
        "bc": rng.uniform(-0.5, 0.5, a.conv_width),
        "dt_bias": step + np.log(-np.expm1(-step)),
        "A_log": np.log(rng.uniform(*A_RANGE, a.heads)),
        "D": np.ones(a.heads),
        "Wgn": 1.0 + 0.1 * rng.standard_normal(a.inner),
    }


def make_mamba2_buffers(args: Mamba2Args, tags: Sequence[str],
                        seed: int = 0) -> Dict[str, np.ndarray]:
    """Host buffers of the layers at a small size (tests): the packing, the
    draws of :func:`draw_layer` in the buffers' dtypes, everything an
    iteration writes zero."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    bufs = {name: np.zeros(shape, jnp.dtype(dtype))
            for name, (shape, dtype) in buffer_shapes(args, tags).items()}
    bufs.update(packing(args.lens))
    for tag in tags:
        for key, x in draw_layer(args, rng).items():
            name = _names(tag)[key]
            bufs[name] = x.astype(bufs[name].dtype)
    return bufs
