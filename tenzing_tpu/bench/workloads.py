"""What a workload is: one row of :data:`WORKLOADS` for each name a
:class:`~tenzing_tpu.bench.driver.DriverRequest` may carry in ``workload``.

A row holds what the layers above and below the driver ask of a workload,
as plain functions of the request: its builders (device-placing and
backend-free), its shape and lane rule (the serving fingerprint's inputs),
its metric name and roofline cost, its naive schedule, its hand incumbents,
its climb policy and the climbs the driver runs from it.  The module-level
functions (``metric_for``, ``workload_shape``, ``graph_for``, ...) keep the
names and signatures they had in ``bench/driver.py`` and are one look-up
each; ``driver.py`` re-exports them.  Adding a workload is adding a row.

Importing this module imports no backend, no solver and nothing of the
driver: a row's functions import their model modules when they are called.

The workloads (``DriverRequest.workload`` / the CLI's ``--workload``):
* ``halo`` (default, the north-star metric — BASELINE.md): the 3D
  halo-exchange pipeline (nQ=3, 512^3 cells, radius 3, the reference config
  halo_run_strategy.hpp:42-49) as six pack -> post -> await -> unpack chains
  whose transfers are async host round-trip DMAs; MCTS searches order x lane x
  kernel (XLA slice vs Pallas plane-DMA) against the fully-synchronous naive
  serialization.
* ``spmv``: distributed-SpMV iteration (reference config: m=150000 rows,
  nnz=10*m, band matrix, 2 lanes — spmv_run_strategy.cuh:44-47).
* ``attn``: single-chip blockwise (flash) attention over a long context —
  the engine menu (per-block chain vs one fused kernel), the kernel menu
  (XLA vs Pallas MXU) plus order x lane space.  The model
  (``models/ring_attention.py`` ``BlockedAttention``) takes query heads
  grouped over key/value heads, a causal mask, a sliding window and query
  blocks (one chain per query block over the K/V blocks it can see, the
  first fold writing the softmax state, so that an iteration is
  idempotent); this row runs its unmasked single-group shape (8k context,
  8 blocks of 1024), the benchmark's ``trinity-attn32k`` a model's layers.
* ``mla_decode``: one decode step of latent attention (MLA) over a paged
  latent cache (``models/latent_attention.py``): per layer an append into
  each sequence's open page, the absorb einsum, one engine menu a group of
  sequences (one ``mla_decode`` kernel over the group's whole cache against
  a split-K chain of ``mla_fold`` links) and the up-projection.  This row
  runs DeepSeek-V3's widths on eight short sequences (toy widths with
  ``--smoke``), the benchmark's ``dsv3-mla-decode`` 16 sequences of 8k to
  128k through four layers.
* ``dsa_decode``: one decode step of learned sparse attention (DeepSeek-
  V3.2's DSA) over a paged index-key cache and a paged latent cache
  (``models/sparse_attention.py``): per layer the two appends, the absorb
  einsum, one chain a group of sequences (the ``dsa_index`` kernel over the
  group's pages of index keys, an exact top-k, and the ``mla_decode_rows``
  kernel over the selected latent rows, gathered as rows; or, the menu's
  other entry, one selection for the layer between the indexes and the reads)
  and the up-projection.  This row runs DeepSeek-V3.2's
  widths on ``mla_decode``'s eight short sequences with 1024 selected
  (toy widths with ``--smoke``), the benchmark's ``dsv32-dsa-decode`` 16
  sequences of 8k to 128k with the published 2048 through four layers.
* ``kda_decode``: one decode step of one period of a linear-attention
  hybrid (Kimi-Linear; ``models/delta_attention.py``): three KDA layers on
  a recurrent state (one engine menu a group of sequences: one fused
  ``kda_step`` kernel that reads the state once and writes it once against
  the chain of four XLA vertices), then ``mla_decode``'s latent-attention
  layer at 32 heads.  This row runs Kimi-Linear's widths on ``mla_decode``'s
  eight short sequences (toy widths with ``--smoke``), the benchmark's
  ``kimi-linear-kda-decode`` 128 sequences of 1k to 131k.
* ``moe``: single-chip MoE dispatch/combine pipeline — routed tokens staged
  through async host round-trip DMAs to the resident experts (the
  expert-parallel network-hop analog), searched over order x lane x
  expert-kernel (XLA vs Pallas) across independent microbatch chunk chains.
"""

from __future__ import annotations

import importlib
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Tuple

from tenzing_tpu.bench import roofline


class DriverConfigError(ValueError):
    """An invalid :class:`DriverRequest` — the library analog of
    ``argparse.ArgumentParser.error`` (the CLI shim catches it and calls
    exactly that, so bad flag combinations fail identically to the
    monolith)."""


# the measured per-face aliased-unpack recipe (the r5 discovery, see
# experiments/MENU_INCUMBENT2.json / MENU_INCUMBENT3.json): the ghost-shell
# write must lower IN PLACE (a non-aliased write copies the 2.07 GB grid,
# ~5 ms) and these are the aliased Pallas kernels per face axis, first
# choice first.  A z face takes the window kernel that reads and writes one
# tile column of the grid (PR 48; ``.pallasb``, the r5 choice, where the
# menu has no ``.window``: a z face that is not lane-thin).  ONE definition
# — the greedy incumbents and the climb seeds must refine the same recipe.
ALIAS_UNPACK = {"x": (".pallas",), "y": (".pallasf",),
                "z": (".window", ".pallasb")}


def _first_choice(choices, suffixes):
    """The menu entry with the first of ``suffixes`` the menu has, or None."""
    return next((c for want in suffixes for c in choices
                 if c.endswith(want)), None)


def alias_unpack_choice(op_name, choices):
    """The aliased kernel for an ``unpack_*`` op from the menu, or None when
    it is off-menu — the one lookup both the greedy seeding and the climb
    disciplines share."""
    return _first_choice(choices, ALIAS_UNPACK[op_name[-1]])


def generic_xla_prefer(op_name, choices):
    """Workload-agnostic default policy: the plain XLA lowering when the
    menu has one — the fleet's smoke-job prefer (safe on any workload)."""
    return next((c for c in choices if c.endswith(".xla")), None)


def halo_alias_prefer(op_name, choices):
    """The halo climb policy: all-rdma + the aliased-unpack kernel map (the
    measured r5 recipe — in-place ghost-shell writes per face,
    MENU_INCUMBENT2/3) + the window pack where the menu has one (a z face:
    it feeds ``unpack_*.window`` the turned face as the kernel wrote it),
    XLA's slice elsewhere.  Module-level so a fleet worker process can
    rebuild it by name from the job spec (search/fleet.py resolve_prefer)."""
    if op_name.startswith("xfer_"):
        return _first_choice(choices, (".rdma",))
    if op_name.startswith("unpack_"):
        hit = alias_unpack_choice(op_name, choices)
        if hit is not None:
            return hit
    return _first_choice(choices, (".window", ".xla"))


def moe_bf16_prefer(op_name, choices):
    """The moe climb policy: whole-chain staging choice — device-resident
    bf16 transfers (the measured 10.97x winner); kernel choices default to
    XLA."""
    return next(
        (c for c in choices if c.endswith(".bf16-rdma")),
        next((c for c in choices if c.endswith(".xla")), None),
    )


def attn_fused_prefer(op_name, choices):
    """The attn climb policy: every query block's folds in one fused kernel
    over its visible range (state in VMEM), the Pallas kernel where a chain
    is unrolled: the start point a long prompt's search climbs from."""
    for want in (".fused", ".pallas", ".xla"):
        hit = next((c for c in choices if c.endswith(want)), None)
        if hit is not None:
            return hit
    return None


def recorded_prefer(chosen: Dict[str, str]):
    """The climb policy replicating a recorded winner's menu choices
    (``chosen``: base op name -> ``".suffix"``) — the factory form of the
    legacy closure, so a fleet worker can rebuild it from the job spec's
    serialized ``chosen`` map."""

    def prefer(op_name, choices):
        want = chosen.get(op_name)
        if want is not None:
            c = next((c for c in choices if c.endswith(want)), None)
            if c is not None:
                return c
        if op_name.startswith("xfer_"):
            # a recorded host-staged transfer leaves no "xfer_*" vertex
            # (the HostRoundTrip compound expands into spill/fetch)
            return next((c for c in choices if c.endswith(".host")), None)
        return next((c for c in choices if c.endswith(".xla")), None)

    return prefer


def nbytes_of(bufs) -> Dict[str, int]:
    """Buffer name -> bytes: the surrogate's comm-bytes features' input."""
    return {k: int(getattr(v, "nbytes", 0)) for k, v in bufs.items()}


def _model(path: str):
    """``tenzing_tpu.models.<module>:<name>``, imported when asked for (the
    model modules import jax)."""
    module, name = path.split(":")
    return getattr(importlib.import_module(f"tenzing_tpu.models.{module}"),
                   name)


def _device_free(parts):
    """``graph_for``'s half of a builder: ``(graph, nbytes)`` from the first
    two returns of ``parts(req)`` (no buffers: ``{}``)."""
    def graph(req):
        g, bufs = parts(req)[:2]
        return g, nbytes_of(bufs or {})

    return graph


# -- halo ---------------------------------------------------------------------

def _halo_shape(req):
    if req.smoke:
        return {"nq": 2, "n": 4, "radius": 1}
    return {"nq": 3, "n": req.halo_n, "radius": 3}


def _halo_parts(req, buffers=None):
    """``(graph, host buffers, HaloArgs)``; by default no buffers at full
    size (``graph_for``'s docstring)."""
    from tenzing_tpu.models.halo import HaloArgs
    from tenzing_tpu.models.halo_pipeline import (
        build_graph,
        make_pipeline_buffers,
    )

    s = _halo_shape(req)
    hargs = HaloArgs(nq=s["nq"], lx=s["n"], ly=s["n"], lz=s["n"],
                     radius=s["radius"])
    # kernel + transfer-engine menus only where a real TPU compiles them;
    # interpret-mode Pallas would dominate a CPU smoke timing
    impl_choice = not req.smoke
    g = build_graph(hargs, impl_choice=impl_choice, xfer_choice=impl_choice)
    bufs = None
    if req.smoke if buffers is None else buffers:
        bufs, _ = make_pipeline_buffers(hargs, seed=0, with_expected=False)
    return g, bufs, hargs


def build_halo(args):
    from tenzing_tpu.models.halo_pipeline import host_buffer_names
    from tenzing_tpu.runtime.executor import TraceExecutor

    g, bufs, hargs = _halo_parts(args, buffers=True)
    jbufs = TraceExecutor.place_host_buffers(bufs, host_buffer_names())
    return g, jbufs, metric_for("halo", args), hargs


def _halo_cost(built):
    h = built[3]
    return roofline.halo_cost(h.nq, h.lx, h.ly, h.lz, h.radius)


def _halo_incumbents(req, g, hargs, plat):
    """An engine x lane-count grid of the post-all-before-await-any overlap
    discipline — the one the reference's graph hard-codes via its
    every-post-before-any-wait edges (ops_halo_exchange.cu:249-256)."""
    from tenzing_tpu.core.platform import Platform
    from tenzing_tpu.models.halo_pipeline import (
        greedy_overlap_order,
        paired_overlap_order,
    )

    inc = Incumbents()
    if req.smoke:
        inc.seqs.append(("greedy-overlap", greedy_overlap_order(hargs, plat)))
        return inc
    from tenzing_tpu.models.halo import (
        DIRECTIONS as _DIRS,
        dir_name as _dn,
    )
    from tenzing_tpu.models.halo_pipeline import (
        HALO_PHASES as _PH,
        paired_priority,
    )
    from tenzing_tpu.solve.local import drive, phase_policy

    _dirs = [_dn(d) for d in _DIRS]

    def mk_prefer(engine):
        if engine == "alias":
            return halo_alias_prefer

        def prefer(op_name, choices):
            if op_name.startswith("xfer_"):
                i = _dirs.index(op_name.split("_", 1)[1])
                want = {"host": ".host", "rdma": ".rdma"}.get(
                    engine, ".rdma" if i % 2 == 0 else ".host")
                return next((c for c in choices if c.endswith(want)), None)
            return next((c for c in choices if c.endswith(".xla")), None)

        return prefer

    # rollouts complete with the measured r5 alias discipline
    # (phase_policy is stateful via its lane round-robin, which
    # adds completion diversity on top of rollout_eps)
    inc.rollout_policy = phase_policy(plat, _PH, mk_prefer("alias"))

    # search-platform (8-lane) incumbents are driven on the
    # CHOICE graph itself, and their decision paths double as the
    # MCTS warm-start seeds (re-measured at the cheap screen
    # floor — a few ms of device time — since the multi-fidelity
    # split keys the cache per-floor)
    for label, engine, pri in (
        ("greedy-host-8l", "host", None),
        ("greedy-rdma-8l", "rdma", None),
        ("greedy-mixed-8l", "mixed", None),
        ("greedy-paired-8l", "mixed", paired_priority("mixed")),
        ("greedy-alias-8l", "alias", None),
    ):
        seq, decs = drive(g, plat, phase_policy(
            plat, _PH, mk_prefer(engine), priority=pri))
        inc.seqs.append((label, seq))
        inc.seed_paths.append(decs)
    # other lane counts: engine-fixed graphs (probed on v5e:
    # rdma peaks at 2-3 lanes, mixed also strong at 6)
    for label, engine, nl in (
        ("greedy-rdma-2l", "rdma", 2),
        ("greedy-rdma-3l", "rdma", 3),
        ("greedy-mixed-6l", "mixed", 6),
    ):
        inc.seqs.append((label, greedy_overlap_order(
            hargs, Platform.make_n_lanes(nl), engine=engine)))
    inc.seqs.append(("greedy-paired-6l", paired_overlap_order(
        hargs, Platform.make_n_lanes(6), engine="mixed")))
    # the aliased-unpack recipe at the probed lane counts
    # (experiments/MENU_INCUMBENT3.json: 3.2-3.4x paired at
    # 2/3/6 lanes, best at 6) — driven on the choice graph so
    # their decision paths also seed the tree
    for label, nl in (("greedy-alias-3l", 3),
                      ("greedy-alias-6l", 6)):
        plat_a = Platform.make_n_lanes(nl)
        seq, decs = drive(g, plat_a, phase_policy(
            plat_a, _PH, mk_prefer("alias")))
        inc.seqs.append((label, seq))
        inc.seed_paths.append(decs)
    return inc


def _halo_climbs(req, plat, recorded):
    """One climb seeded from the best RECORDED schedule's menu choices
    (when a database is present — the cross-run memory), then the two
    strongest measured disciplines, split 4:3: the aliased-unpack all-rdma
    recipe at its two best probed lane counts (MENU_INCUMBENT3.json:
    3.2-3.4x paired at 3 and 6 lanes) — the climb refines
    order/lane/kernel-flip moves from there."""
    from tenzing_tpu.core.platform import Platform

    if req.smoke:
        return []
    phases = _model("halo_pipeline:HALO_PHASES")
    b_rec = (req.climb_budget // 3) if recorded else 0
    rest = req.climb_budget - b_rec
    b1 = (rest * 4) // 7
    return _recorded_climb(recorded, phases, b_rec) + [
        (Platform.make_n_lanes(3), phases, halo_alias_prefer, None, b1,
         "halo_alias", None),
        (Platform.make_n_lanes(6), phases, halo_alias_prefer, None,
         rest - b1, "halo_alias", None),
    ]


# -- spmv ---------------------------------------------------------------------

def _spmv_m(req):
    return req.m if req.m is not None else (512 if req.smoke else 150_000)


def _spmv_shape(req):
    m = _spmv_m(req)
    # bw resolves exactly as models/spmv.py make_spmv_buffers does
    # (None -> max(1, m // 8)): a default request and an explicit
    # --spmv-bw of the same value build the SAME matrix and must
    # fingerprint identically, or independently-warmed stores
    # fragment and exact hits are missed
    bw = req.spmv_bw if req.spmv_bw is not None else max(1, m // 8)
    return {"m": m, "nnz_per_row": 10, "bw": bw}


def _spmv_metric(req):
    sfx = f"_bw{req.spmv_bw}" if req.spmv_bw is not None else ""
    return f"spmv_iter_pct50_searched_m{_spmv_m(req)}{sfx}"


def _spmv_parts(req):
    """``(graph, host buffers)``: the graph depends on the buffers."""
    from tenzing_tpu.core.graph import Graph
    from tenzing_tpu.models.spmv import SpMVCompound, make_spmv_buffers

    s = _spmv_shape(req)
    # --spmv-bw widens the band, growing the remote-column exchange relative
    # to the local compute: the transfer-bound sweep of VERDICT r2 item 7
    synth = bool(req.synth_collectives)
    bufs, _ = make_spmv_buffers(m=s["m"], nnz_per_row=s["nnz_per_row"],
                                bw=req.spmv_bw, seed=0, synth=synth)
    # impl_choice: the kernel menu (XLA gather vs Pallas vreg-gather) is part
    # of the searched space alongside order and lane assignment; known x sizes
    # prune Pallas choices that would only alias the XLA path (ADVICE r1).
    # exchange="host": the x exchange is a posted async host round-trip DMA
    # (the reference's MPI hop), so the post/wait split gives the search a
    # real transfer to hide behind the local SpMV
    x_sizes = {"x_local": int(bufs["x_local"].shape[0]),
               "x_remote": int(bufs["x_remote"].shape[0])}
    mk = lambda: SpMVCompound(impl_choice=True, x_sizes=x_sizes,
                              exchange="host", synth=synth,
                              synth_relax=req.smoke)
    g = Graph()
    g.start_then(mk())
    g.then_finish(mk())
    return g, bufs


def build_spmv(args):
    from tenzing_tpu.models.spmv import spmv_host_buffer_names
    from tenzing_tpu.runtime.executor import TraceExecutor

    g, bufs = _spmv_parts(args)
    jbufs = TraceExecutor.place_host_buffers(bufs, spmv_host_buffer_names(
        int(bufs["x_remote"].shape[0]), synth=bool(args.synth_collectives)))
    return g, jbufs, metric_for("spmv", args), _spmv_m(args)


def _spmv_cost(built):
    m = built[3]
    return roofline.spmv_cost(m, nnz=10 * m)


# -- moe ----------------------------------------------------------------------

def _moe_shape(req):
    if req.smoke:
        return {"n_experts": 4, "tokens": 32, "d_model": 8, "d_ff": 16,
                "n_chunks": 2}
    return {"tokens": req.moe_tokens}


def _moe_parts(req):
    """``(graph, host buffers, (MoEPipeArgs, capacity), staging)``."""
    from tenzing_tpu.models.moe_pipeline import (
        MoEPipeArgs,
        build_graph,
        make_pipe_buffers,
    )

    margs = MoEPipeArgs(**_moe_shape(req))
    # the searched space includes the staging-precision menu (f32 vs
    # half-width bf16 transfers) on the real chip
    staging = "f32" if req.smoke else "choice"
    bufs, _, cap = make_pipe_buffers(margs, seed=0, with_expected=False,
                                     staging=staging)
    impl_choice = not req.smoke  # same rationale as the halo's
    g = build_graph(margs, cap, impl_choice=impl_choice, staging=staging,
                    chunk=req.chunk, chunk_relax=req.smoke)
    return g, bufs, (margs, cap), staging


def build_moe(args):
    from tenzing_tpu.models.moe_pipeline import host_buffer_names
    from tenzing_tpu.runtime.executor import TraceExecutor

    g, bufs, wargs, staging = _moe_parts(args)
    jbufs = TraceExecutor.place_host_buffers(
        bufs, host_buffer_names(wargs[0], staging=staging))
    return g, jbufs, metric_for("moe", args), wargs


def _moe_cost(built):
    margs = built[3][0]
    return roofline.moe_cost(margs.tokens, margs.d_model, margs.d_ff,
                             staged=True, n_experts=margs.n_experts)


def _moe_incumbents(req, g, wargs, plat):
    from tenzing_tpu.models.moe_pipeline import greedy_overlap_order

    margs_, cap_ = wargs
    inc = Incumbents(seqs=[
        ("greedy-overlap", greedy_overlap_order(margs_, cap_, plat))])
    if req.smoke:
        return inc
    from tenzing_tpu.solve.local import drive, phase_policy

    # the half-width-transfer incumbent (bf16 staging) and the
    # device-resident-transfer incumbents (rdma engine): the
    # likely winners the search should start from
    inc.seqs.append((
        "greedy-overlap-bf16",
        greedy_overlap_order(margs_, cap_, plat, staging="bf16"),
    ))
    inc.seqs.append((
        "greedy-bf16-rdma",
        greedy_overlap_order(margs_, cap_, plat, staging="bf16",
                             engine="rdma"),
    ))
    inc.seqs.append((
        "greedy-f32-rdma",
        greedy_overlap_order(margs_, cap_, plat, engine="rdma"),
    ))
    # the warm-start seed and the informed playouts: the climb's policy
    phases = _model("moe_pipeline:PHASES")
    _, decs = drive(g, plat, phase_policy(plat, phases, moe_bf16_prefer))
    inc.seed_paths.append(decs)
    inc.rollout_policy = phase_policy(plat, phases, moe_bf16_prefer)
    return inc


def _moe_climbs(req, plat, recorded):
    if req.smoke:
        return []
    b_rec = (req.climb_budget // 2) if recorded else 0
    phases = _model("moe_pipeline:PHASES")
    return _recorded_climb(recorded, phases, b_rec) + [
        (plat, phases, moe_bf16_prefer, None, req.climb_budget - b_rec,
         "moe_bf16", None)]


# -- attn ---------------------------------------------------------------------

def _attn_shape(req):
    if req.smoke:
        return {"n_devices": 4, "batch": 1, "seq_local": 16,
                "head_dim": 8}
    # 8k context in 8 blocks of 1024, head dim 128
    return {"n_devices": 8, "batch": 4, "seq_local": 1024,
            "head_dim": 128}


def _attn_parts(req):
    from tenzing_tpu.core.graph import Graph
    from tenzing_tpu.models.ring_attention import (
        BlockedAttention,
        RingAttnArgs,
        make_blocked_buffers,
    )

    aargs = RingAttnArgs(**_attn_shape(req))
    bufs, _ = make_blocked_buffers(aargs, seed=0)
    g = Graph()
    op = BlockedAttention(aargs, impl_choice=True, fused_choice=True,
                          chunk=req.chunk, chunk_relax=req.smoke)
    g.start_then(op)
    g.then_finish(op)
    return g, bufs, aargs


def build_attn(args):
    import jax.numpy as jnp

    g, bufs, aargs = _attn_parts(args)
    bufs = {k: jnp.asarray(v) for k, v in bufs.items()}
    return g, bufs, metric_for("attn", args), aargs


def _attn_cost(built):
    a = built[3]
    return roofline.attention_cost(
        a.batch, a.seq, a.head_dim, heads=a.heads, kv_heads=a.kv_heads,
        causal=a.causal, window=a.window)


def _attn_incumbents(req, g, wargs, plat):
    """Kernel incumbents: (a) the per-block chain with every block on the
    bf16 Pallas kernel (the r2-r4 winner), (b) the fused single-kernel
    flash with VMEM-resident state (the r5 HBM-state-traffic fix) — the
    directed search starts from both, the final batch must include
    whichever survives the screen.  Driven on the menu, on one lane: a
    chip that refuses a kernel drops that incumbent, not the run."""
    from tenzing_tpu.core.platform import Platform
    from tenzing_tpu.core.state import ChooseOp, State

    inc = Incumbents(tolerant=True)
    if req.smoke:
        return inc
    naive_plat = Platform.make_n_lanes(1)
    for label, engine_suffix, kernel_suffix in (
            ("bf16-kernel", ".chain", ".pallas_bf16"),
            ("fused-bf16", ".fused_bf16", ".pallas_bf16")):
        st = State(g)
        while not st.is_terminal():
            ds = st.get_decisions(naive_plat)
            pick = next(
                (d for d in ds if isinstance(d, ChooseOp)
                 and d.choice.name().endswith(engine_suffix)),
                None,
            ) or next(
                (d for d in ds if isinstance(d, ChooseOp)
                 and d.choice.name().endswith(kernel_suffix)),
                ds[0],
            )
            st = st.apply(pick)
        inc.seqs.append((label, st.sequence))
    return inc


# -- mla_decode ---------------------------------------------------------------

# cached tokens a sequence: eight sequences, none a multiple of the page
_MLA_SMOKE_LENS = (3, 9, 13, 17, 26, 31, 44, 61)
_MLA_LENS = (1100, 1900, 2700, 3300, 4200, 5100, 6600, 8000)


def _mla_dims(req):
    """The step's sizes as plain numbers (the fingerprint's arithmetic
    imports no model)."""
    if req.smoke:
        return dict(lens=_MLA_SMOKE_LENS, heads=4, rank=16, rope=8, nope=8,
                    v_dim=8, page=8, groups=4, fold_pages=2, dtype="float32")
    # DeepSeek-V3's widths on a toy batch (the benchmark's dsv3-mla-decode
    # runs 16 sequences of 8k to 128k through four layers)
    return dict(lens=_MLA_LENS, heads=128, rank=512, rope=64, nope=128,
                v_dim=128, page=512, groups=4, fold_pages=4,
                dtype="bfloat16")


def _mla_args(req):
    from tenzing_tpu.models.latent_attention import LatentDecodeArgs
    from tenzing_tpu.models.latent_attention_reference import yarn_scale

    d = _mla_dims(req)
    return LatentDecodeArgs(scale=yarn_scale(d["nope"], d["rope"]), **d)


def _mla_shape(req):
    d = _mla_dims(req)
    return {"sequences": len(d["lens"]), "keys": sum(d["lens"]) + len(
        d["lens"]), "page": d["page"], "heads": d["heads"],
        "rank": d["rank"], "rope": d["rope"], "groups": d["groups"]}


def _mla_parts(req):
    from tenzing_tpu.models.latent_attention import (
        decode_graph,
        make_decode_buffers,
    )

    a = _mla_args(req)
    # the kernel menu of a link (XLA gather and einsums against the kernel)
    # only where a chip compiles it, as the halo's
    g = decode_graph(a, ("L0",), impl_choice=not req.smoke)
    return g, make_decode_buffers(a, ("L0",), seed=0), a


def build_mla_decode(args):
    import jax.numpy as jnp

    g, bufs, a = _mla_parts(args)
    bufs = {k: jnp.asarray(v) for k, v in bufs.items()}
    return g, bufs, metric_for("mla_decode", args), a


def _mla_cost(built):
    a = built[3]
    return roofline.latent_decode_cost(a.lens, a.heads, a.rank, a.rope,
                                       a.v_dim, nope=a.nope)


# -- dsa_decode ---------------------------------------------------------------


def _dsa_dims(req):
    """The sparse step's sizes beside the latent step's (``_mla_dims``):
    index heads and width, keys selected."""
    if req.smoke:
        return dict(index_heads=4, index_dim=8, topk=16)
    # DeepSeek-V3.2's indexer on the toy batch, half its 2048 selected so
    # that sequences lie on either side of it
    return dict(index_heads=64, index_dim=128, topk=1024)


def _dsa_args(req):
    from tenzing_tpu.models.sparse_attention import SparseDecodeArgs

    return SparseDecodeArgs(_mla_args(req), **_dsa_dims(req))


def _dsa_shape(req):
    d = _dsa_dims(req)
    return {**_mla_shape(req), "index_heads": d["index_heads"],
            "index_dim": d["index_dim"], "topk": d["topk"]}


def _dsa_parts(req):
    from tenzing_tpu.models.sparse_attention import (
        dsa_graph,
        make_dsa_buffers,
    )

    a = _dsa_args(req)
    # the index's kernel menu only where a chip compiles it, as the halo's
    g = dsa_graph(a, ("L0",), impl_choice=not req.smoke)
    return g, make_dsa_buffers(a, ("L0",), seed=0), a


def build_dsa_decode(args):
    import jax.numpy as jnp

    g, bufs, a = _dsa_parts(args)
    bufs = {k: jnp.asarray(v) for k, v in bufs.items()}
    return g, bufs, metric_for("dsa_decode", args), a


def _dsa_cost(built):
    a = built[3]
    lat = a.latent
    return roofline.sparse_decode_cost(
        lat.lens, lat.heads, lat.rank, lat.rope, lat.v_dim, a.index_heads,
        a.index_dim, a.topk, nope=lat.nope)


# -- kda_decode ---------------------------------------------------------------

_KDA_PATTERN = (("kda", "L0"), ("kda", "L1"), ("kda", "L2"), ("mla", "L3"))


def _kda_dims(req):
    """The KDA layers' sizes beside the latent layer's (``_mla_dims``, at
    Kimi-Linear's 32 heads and plain ``192^(-1/2)`` scale)."""
    if req.smoke:
        return dict(heads=2, d=16, taps=4, groups=2, dtype="float32")
    return dict(heads=32, d=128, taps=4, groups=2, dtype="bfloat16")


def _kda_args(req):
    from tenzing_tpu.models.delta_attention import DeltaDecodeArgs
    from tenzing_tpu.models.latent_attention import LatentDecodeArgs

    m = _mla_dims(req)
    if not req.smoke:
        m["heads"] = 32
    mla = LatentDecodeArgs(scale=(m["nope"] + m["rope"]) ** -0.5, **m)
    return DeltaDecodeArgs(batch=mla.batch, **_kda_dims(req)), mla


def _kda_shape(req):
    d = _kda_dims(req)
    return {**_mla_shape(req), "kda_layers": 3, "kda_heads": d["heads"],
            "kda_dim": d["d"], "kda_groups": d["groups"]}


def _kda_parts(req):
    from tenzing_tpu.models.delta_attention import (
        hybrid_decode_graph,
        make_kda_buffers,
    )
    from tenzing_tpu.models.latent_attention import make_decode_buffers

    kda, mla = _kda_args(req)
    g = hybrid_decode_graph(kda, mla, _KDA_PATTERN,
                            impl_choice=not req.smoke)
    bufs = make_kda_buffers(
        kda, [t for k, t in _KDA_PATTERN if k == "kda"], seed=0)
    bufs.update(make_decode_buffers(
        mla, [t for k, t in _KDA_PATTERN if k == "mla"], seed=0))
    return g, bufs, (kda, mla)


def build_kda_decode(args):
    import jax.numpy as jnp

    g, bufs, a = _kda_parts(args)
    bufs = {k: jnp.asarray(v) for k, v in bufs.items()}
    return g, bufs, metric_for("kda_decode", args), a


def _kda_cost(built):
    kda, mla = built[3]
    state = roofline.kda_decode_cost(kda.batch, kda.heads, kda.d, kda.taps,
                                     layers=3)
    cache = roofline.latent_decode_cost(mla.lens, mla.heads, mla.rank,
                                        mla.rope, mla.v_dim, nope=mla.nope)
    return roofline.Cost(flops=state.flops + cache.flops,
                         hbm_bytes=state.hbm_bytes + cache.hbm_bytes)


# -- the table ----------------------------------------------------------------

@dataclass
class Incumbents:
    """A workload's hand incumbents, before any is measured."""

    seqs: List[Tuple[str, Any]] = field(default_factory=list)  # (label, seq)
    # incumbent disciplines as DECISION PATHS on the search platform over
    # the choice graph: the MCTS warm-start seeds (VERDICT r3 item 1)
    seed_paths: list = field(default_factory=list)
    # informed MCTS playouts: rollouts complete with the workload's best
    # hand discipline (epsilon-noised) instead of uniform random — a
    # ~100-decision halo schedule essentially never assembles a coherent
    # discipline by chance, which is why random-playout MCTS lagged the
    # climbs for four rounds (VERDICT r4 item 2)
    rollout_policy: Any = None
    # a rejected incumbent is dropped with a message instead of raising, and
    # the list is neither prefetched nor completed with a tile directive
    tolerant: bool = False


def first_decision_schedule(graph, wargs, plat):
    """The schedule of always taking the first decision the SDP offers."""
    from tenzing_tpu.core.state import State

    st = State(graph)
    while not st.is_terminal():
        st = st.apply(st.get_decisions(plat)[0])
    return st.sequence


def _recorded_climb(recorded, phases, budget):
    """``[climb config]`` replicating the best recorded schedule's menu
    choices, or ``[]`` without a budget — the climb starts in the recorded
    winner's kernel/engine configuration and searches order/lane/flip moves
    from there.  ``chosen`` rides along so a fleet job spec can serialize
    the policy for a worker process (recorded_prefer rebuilds it)."""
    if not budget:
        return []
    from tenzing_tpu.core.platform import Platform
    from tenzing_tpu.core.serdes import sequence_to_json

    js = sequence_to_json(recorded[0])
    chosen: dict = {}
    for j in js:
        n = j.get("name", "")
        if "." in n:
            base, suffix = n.rsplit(".", 1)
            chosen.setdefault(base, "." + suffix)

    lanes_used = [j.get("lane") for j in js if j.get("lane") is not None]
    n_rec = max(lanes_used) + 1 if lanes_used else 2
    return [(Platform.make_n_lanes(n_rec), phases, recorded_prefer(chosen),
             None, budget, "recorded", chosen)]


@dataclass(frozen=True)
class Workload:
    """One workload, as plain functions of the request (``req``), of the
    builder's return (``built``: graph, buffers, metric, workload args
    ``wargs``) and of the search platform (``plat``)."""

    build: Callable      # req -> built; places buffers, needs a backend
    graph: Callable      # req -> (graph, nbytes), no backend
    shape: Callable      # req -> {parameter: int}: the fingerprint's key
    metric: Callable     # req -> the metric series' name
    cost: Callable       # built -> roofline.Cost of one iteration
    # (graph, wargs, one-lane plat) -> the naive schedule; the default takes
    # the first decision the SDP offers
    naive: Callable = first_decision_schedule
    lanes: Callable = lambda req: 2   # req -> lanes, unless req.lanes says
    phases: Callable = lambda: ("",)  # the climbs' phase order
    # (req, graph, wargs, plat) -> Incumbents
    incumbents: Callable = lambda req, g, wargs, plat: Incumbents()
    # (req, plat, recorded best-first) -> [(plat, phases, prefer, priority,
    # budget, the prefer's name for a fleet job, chosen)]: each
    # climb carries its prefer SPEC beside the callable, so the fleet can
    # ship the policy to a worker process (search/fleet.py resolve_prefer
    # rebuilds the same module-level functions — inline and worker
    # execution agree decision-for-decision)
    climb_config: Callable = lambda req, plat, recorded: []
    seed_csv: str = ""   # default --seed-csv glob: its recorded databases


WORKLOADS: Dict[str, Workload] = {
    # 8 lanes for full-size halo: the probed greedy lane-count curve peaks
    # at 6-8 lanes (paired 1.38-1.42 vs 1.18-1.23 at 2) and the repeat
    # driver winner is the mixed-engine 8-lane incumbent — searching on 8
    # lanes puts the hill-climb and MCTS in the same neighborhood instead
    # of a 6-lane one.  Smoke stays at 2 lanes (the CPU path is cheap).
    "halo": Workload(
        build=build_halo, graph=_device_free(_halo_parts), shape=_halo_shape,
        metric=lambda req: "halo_iter_pct50_searched_n%d" % (
            4 if req.smoke else req.halo_n),
        cost=_halo_cost, lanes=lambda req: 2 if req.smoke else 8,
        naive=lambda g, hargs, plat: _model("halo_pipeline:naive_order")(
            hargs, plat),
        phases=lambda: _model("halo_pipeline:HALO_PHASES"),
        incumbents=_halo_incumbents, climb_config=_halo_climbs,
        seed_csv="experiments/halo_search_tpu_r[45]*.csv"),
    "spmv": Workload(
        build=build_spmv, graph=_device_free(_spmv_parts), shape=_spmv_shape,
        metric=_spmv_metric, cost=_spmv_cost),
    "attn": Workload(
        build=build_attn, graph=_device_free(_attn_parts), shape=_attn_shape,
        metric=lambda req: "attn_blockwise_pct50_searched_n%d" % (
            4 * 16 if req.smoke else 8 * 1024),
        cost=_attn_cost, incumbents=_attn_incumbents,
        seed_csv="experiments/attn_search_tpu_r[45]*.csv"),
    "mla_decode": Workload(
        build=build_mla_decode, graph=_device_free(_mla_parts),
        shape=_mla_shape,
        metric=lambda req: "mla_decode_pct50_searched_k%d" % (
            _mla_shape(req)["keys"]),
        cost=_mla_cost, phases=lambda: ("L0.",)),
    "dsa_decode": Workload(
        build=build_dsa_decode, graph=_device_free(_dsa_parts),
        shape=_dsa_shape,
        metric=lambda req: "dsa_decode_pct50_searched_k%d" % (
            _dsa_shape(req)["keys"]),
        cost=_dsa_cost, phases=lambda: ("L0.",)),
    "kda_decode": Workload(
        build=build_kda_decode, graph=_device_free(_kda_parts),
        shape=_kda_shape,
        metric=lambda req: "kda_decode_pct50_searched_k%d" % (
            _kda_shape(req)["keys"]),
        cost=_kda_cost, phases=lambda: tuple(
            f"{t}." for _, t in _KDA_PATTERN)),
    "moe": Workload(
        build=build_moe, graph=_device_free(_moe_parts), shape=_moe_shape,
        metric=lambda req: "moe_pipe_pct50_searched_t%d" % (
            32 if req.smoke else req.moe_tokens),
        cost=_moe_cost,
        naive=lambda g, wargs, plat: _model("moe_pipeline:naive_order")(
            wargs[0], wargs[1], plat),
        phases=lambda: _model("moe_pipeline:PHASES"),
        incumbents=_moe_incumbents, climb_config=_moe_climbs,
        seed_csv="experiments/moe_search_tpu_r[45]*.csv"),
}

# workload name -> device builder (graph + device-placed buffers + metric +
# workload args) — the search path's entry; serving uses graph_for below
BUILDERS = {name: w.build for name, w in WORKLOADS.items()}


def row_of(name: str) -> Workload:
    """The row for ``name``; an unknown name is a config error."""
    try:
        return WORKLOADS[name]
    except KeyError:
        raise DriverConfigError(f"unknown workload {name!r}") from None


def metric_for(workload: str, args) -> str:
    """The metric name for a workload config — the single source both the
    success path (build_* return) and the backend-init-failure path use, so
    the two always land in the same metric series."""
    return row_of(workload).metric(args)


def workload_cost(workload: str, built):
    """The workload's roofline :class:`~tenzing_tpu.bench.roofline.Cost`
    for the attribution profiler's fraction-of-peak join (``built`` is the
    matching ``build_*`` return).  One iteration's arithmetic + traffic —
    the same accounting experiments/halo_roofline.py reports against."""
    return row_of(workload).cost(built)


def workload_shape(req) -> Dict[str, int]:
    """The request's exact shape parameters, as the builders resolve them
    — THE single source the serving fingerprint keys on (serve/
    fingerprint.py), kept next to the builders so a new shape knob cannot
    silently stay out of the fingerprint.  Pure request arithmetic: no
    jax, no buffers, no backend."""
    return row_of(req.workload).shape(req)


def search_lanes(req) -> int:
    """The search platform's lane count for ``req`` — the same default
    rule :func:`run` applies (8 for full-size halo, else 2, unless
    overridden), exposed so the serving fingerprint's mesh signature and
    the search agree by construction."""
    return req.lanes or row_of(req.workload).lanes(req)


def graph_for(req):
    """``(graph, nbytes)`` for ``req`` **without touching a backend**: the
    choice graph recorded schedules deserialize/verify against, plus a
    buffer-size map for the surrogate featurizer.  The serving path's
    builder (docs/serving.md): resolution and corpus warm-up must work on
    a host with no accelerator at all.

    ``nbytes`` is ``{}`` for the full-size halo config — materializing its
    2 GB grid just to read ``.nbytes`` is not a serving-path cost; the
    featurizer degrades to zero comm-bytes features, consistently at train
    and predict time because both sides use this same map.

    The other workloads DO build their (tens-of-MB) host buffers once per
    fingerprint, deliberately: spmv's choice graph depends on the
    constructed buffers (``x_sizes`` comes from the random band matrix's
    actual remote-column split), so deriving sizes analytically here
    would risk a serving-side graph that silently diverges from the one
    the driver searches — a correctness risk worth more than a transient
    allocation that the resolver's per-fingerprint cache amortizes."""
    return row_of(req.workload).graph(req)


def naive_schedule(workload: str, graph, wargs):
    """The naive incumbent every verdict is a ratio against: the fully
    -synchronous serialization on one lane (the reference's "sequential
    ordering on one stream" baseline, BASELINE.json).  ``wargs`` is the
    workload builder's fourth return.  halo and moe serialize chain by chain
    (models/*_pipeline.naive_order); spmv, attn and a name with no row (the
    benchmark's mesh configurations call with their own) take the first
    decision the SDP offers.  Either way the schedule comes out of the SDP
    machinery, sync ops included, and is held to the soundness verifier
    like any candidate."""
    from tenzing_tpu.core.platform import Platform

    row = WORKLOADS.get(workload)
    naive = row.naive if row is not None else first_decision_schedule
    return naive(graph, wargs, Platform.make_n_lanes(1))
