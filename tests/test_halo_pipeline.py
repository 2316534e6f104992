"""Single-chip halo pipeline: post/wait split, numerics, overlap orderings,
and the Pallas pack/unpack kernel menu."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tenzing_tpu.core.graph import Graph
from tenzing_tpu.core.platform import Platform
from tenzing_tpu.models.halo import DIRECTIONS, HaloArgs, _face_slices, dir_name
from tenzing_tpu.models.halo_pipeline import (
    build_graph,
    host_buffer_names,
    make_pipeline_buffers,
    naive_order,
)
from tenzing_tpu.runtime.executor import TraceExecutor
from tenzing_tpu.solve.dfs import get_all_sequences

ARGS = HaloArgs(nq=2, lx=4, ly=4, lz=4, radius=1)


def _executor(args=ARGS, n_lanes=2):
    bufs, want = make_pipeline_buffers(args, seed=0)
    host_sh = jax.sharding.SingleDeviceSharding(
        jax.devices()[0], memory_kind="pinned_host"
    )
    jbufs = {}
    for k, v in bufs.items():
        if k in host_buffer_names():
            jbufs[k] = jax.device_put(jnp.asarray(v), host_sh)
        else:
            jbufs[k] = jnp.asarray(v)
    return TraceExecutor(Platform.make_n_lanes(n_lanes), jbufs), want


@pytest.mark.needs_pinned_host
def test_naive_order_numerics():
    ex, want = _executor(n_lanes=1)
    out = ex.run(naive_order(ARGS, ex.platform))
    np.testing.assert_allclose(np.asarray(out["U"]), want, rtol=1e-6)


@pytest.mark.needs_pinned_host
def test_searched_schedules_same_answer():
    """Any legal order x lane assignment computes the periodic ghost fill."""
    ex, want = _executor()
    g = build_graph(ARGS)
    states = get_all_sequences(g, ex.platform, max_seqs=4)
    assert states
    for st in states:
        out = ex.run(st.sequence)
        np.testing.assert_allclose(np.asarray(out["U"]), want, rtol=1e-6)


def test_overlap_orderings_exist():
    """The enumerated space must contain schedules with work between a fetch
    post and its await — the overlap freedom the post/wait split exists for
    (VERDICT r1 item 3 exit test)."""
    g = build_graph(ARGS)
    plat = Platform.make_n_lanes(1)
    found = False
    for st in get_all_sequences(g, plat, max_seqs=200):
        names = [op.name() for op in st.sequence.vector()]
        for d in DIRECTIONS:
            nd = dir_name(d)
            i = names.index(f"fetch_{nd}")
            j = names.index(f"await_{nd}")
            between = [
                n
                for n in names[i + 1 : j]
                if not n.startswith(("spill", "fetch", "await"))
            ]
            if between:
                found = True
                break
        if found:
            break
    assert found, "no enumerated schedule overlaps compute with an in-flight fetch"


def test_naive_is_fully_synchronous():
    """The baseline awaits every transfer immediately: no op between fetch and
    await, directions strictly sequential."""
    order = naive_order(ARGS, Platform.make_n_lanes(1))
    names = [op.name() for op in order.vector()]
    for d in DIRECTIONS:
        nd = dir_name(d)
        assert names.index(f"await_{nd}") == names.index(f"fetch_{nd}") + 1


def test_pallas_pack_matches_xla_slice():
    from tenzing_tpu.ops.halo_pallas import pack_face_pallas

    rng = np.random.default_rng(3)
    u = jnp.asarray(rng.random((2, 6, 6, 6), dtype=np.float32))
    for d in DIRECTIONS:
        starts, sizes = _face_slices(ARGS, d, "pack")
        got = pack_face_pallas(u, tuple(starts), tuple(sizes), interpret=True)
        want = jax.lax.dynamic_slice(u, starts, sizes)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want))


def test_pallas_unpack_matches_xla_update():
    from tenzing_tpu.ops.halo_pallas import unpack_face_pallas

    rng = np.random.default_rng(4)
    u = jnp.asarray(rng.random((2, 6, 6, 6), dtype=np.float32))
    for d in DIRECTIONS:
        starts, sizes = _face_slices(ARGS, d, "unpack")
        face = jnp.asarray(rng.random(tuple(sizes), dtype=np.float32))
        got = unpack_face_pallas(u, face, tuple(starts), interpret=True)
        want = jax.lax.dynamic_update_slice(u, face, starts)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want))


def test_batched_pallas_kernels_match_xla():
    """Batched-row prefetching kernels == XLA slice/DUS for every direction."""
    from tenzing_tpu.ops.halo_pallas import (
        pack_face_pallas_batched,
        unpack_face_pallas_batched,
    )

    rng = np.random.default_rng(5)
    u = jnp.asarray(rng.random((2, 6, 6, 6), dtype=np.float32))
    for d in DIRECTIONS:
        starts, sizes = _face_slices(ARGS, d, "pack")
        got = pack_face_pallas_batched(
            u, tuple(starts), tuple(sizes), interpret=True
        )
        want = jax.lax.dynamic_slice(u, starts, sizes)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want))
        ustarts, _ = _face_slices(ARGS, d, "unpack")
        face = jnp.asarray(rng.random(tuple(sizes), dtype=np.float32))
        got = unpack_face_pallas_batched(
            u, face, tuple(ustarts), interpret=True
        )
        want = jax.lax.dynamic_update_slice(u, face, ustarts)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want))


def test_batched_pallas_multi_block_pipeline():
    """A geometry whose rows exceed the per-slot VMEM cap (nb > 1) exercises
    the two-slot prefetch/write-back rotation, including the final-step drain
    of BOTH slots."""
    from tenzing_tpu.models.halo import HaloArgs
    from tenzing_tpu.ops.halo_pallas import (
        _face_bx,
        pack_face_pallas_batched,
        unpack_face_pallas_batched,
    )

    # nq=2 with nb=2 gives total=4 grid steps: the steady-state slot-reuse
    # wait (write-back t-1 drained before refetching into slot b) only
    # executes at t >= 1 prefetches, which total=2 never reaches
    args = HaloArgs(nq=2, lx=64, ly=2, lz=1200, radius=2)
    d = (0, 1, 0)
    bx = _face_bx(args, d)
    starts, sizes = _face_slices(args, d, "pack")
    assert 1 < bx < sizes[1], f"geometry must split into multiple blocks, bx={bx}"
    rng = np.random.default_rng(6)
    shape = args.local_shape()
    pad = (shape[0], shape[1], -(-shape[2] // 8) * 8, -(-shape[3] // 128) * 128)
    u = jnp.asarray(rng.random(pad, dtype=np.float32))
    got = pack_face_pallas_batched(u, tuple(starts), tuple(sizes), interpret=True)
    want = jax.lax.dynamic_slice(u, starts, sizes)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))
    ustarts, _ = _face_slices(args, d, "unpack")
    face = jnp.asarray(rng.random(tuple(sizes), dtype=np.float32))
    got = unpack_face_pallas_batched(u, face, tuple(ustarts), interpret=True)
    want = jax.lax.dynamic_update_slice(u, face, ustarts)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))


def test_flat_pallas_kernels_match_reference():
    """Direct-flat kernels (dense staging emitted/consumed with the relayout
    in VMEM) == XLA slice+flatten / unflatten+DUS on every lane-aligned
    face."""
    from tenzing_tpu.models.halo import HaloArgs
    from tenzing_tpu.models.halo_pipeline import flatten_face
    from tenzing_tpu.ops.halo_pallas import (
        _flat_ok,
        pack_face_flat_pallas,
        unpack_face_flat_pallas,
    )

    from tenzing_tpu.models.halo_pipeline import _padded_shape

    args = HaloArgs(nq=1, lx=8, ly=64, lz=128, radius=2)
    rng = np.random.default_rng(7)
    pad = _padded_shape(args.local_shape())
    u = jnp.asarray(rng.random(pad, dtype=np.float32))
    covered = 0
    for d in DIRECTIONS:
        if not _flat_ok(args, d):
            continue
        covered += 1
        ps, sz = _face_slices(args, d, "pack")
        us, _ = _face_slices(args, d, "unpack")
        want = flatten_face(jax.lax.dynamic_slice(u, ps, sz), sz)
        got = pack_face_flat_pallas(u, tuple(ps), tuple(sz), interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want))
        flat = jnp.asarray(rng.random(want.shape, dtype=np.float32))
        wantu = jax.lax.dynamic_update_slice(u, flat.reshape(tuple(sz)), us)
        gotu = unpack_face_flat_pallas(u, flat, tuple(us), tuple(sz),
                                       interpret=True)
        np.testing.assert_allclose(np.asarray(gotu), np.asarray(wantu))
    assert covered >= 4  # x and y faces; z excluded by the lane gate


def test_flat_gate_excludes_lane_thin_faces():
    """z-faces (trailing dim = radius) fail the sz % 128 gate — Mosaic cannot
    lower the sub-lane-width relayout (probed on v5e) — and stay off the
    flat menu while x/y faces at the flagship geometry get the extra
    entry."""
    from tenzing_tpu.models.halo import HaloArgs
    from tenzing_tpu.ops.halo_pallas import PackChoice, UnpackChoice, _flat_ok

    args = HaloArgs(nq=3, lx=512, ly=512, lz=512, radius=3)
    assert _flat_ok(args, (1, 0, 0)) and _flat_ok(args, (0, 1, 0))
    assert not _flat_ok(args, (0, 0, 1))
    assert any(
        c.name().endswith(".pallasf")
        for c in UnpackChoice(args, (0, 1, 0)).choices()
    )
    assert not any(
        c.name().endswith(".pallasf")
        for c in PackChoice(args, (0, 0, 1)).choices()
    )


def test_batched_variant_on_menu_only_when_it_differs():
    """At the flagship geometry y/z faces batch >1 row per DMA, so the menu
    grows to 3; x-faces degenerate to the per-row kernel (BX=1) and stay
    at 2."""
    from tenzing_tpu.models.halo import HaloArgs
    from tenzing_tpu.ops.halo_pallas import PackChoice, UnpackChoice, _face_bx

    args = HaloArgs(nq=3, lx=512, ly=512, lz=512, radius=3)
    assert _face_bx(args, (1, 0, 0)) == 1
    assert _face_bx(args, (0, 1, 0)) > 1
    assert _face_bx(args, (0, 0, 1)) > 1
    # x: xla + pallas + pallasf (bx=1 keeps pallasb off); y: all four;
    # z: xla + pallas + pallasb (lane gate keeps pallasf off) + window
    # (ISSUE 48: a lane-thin face's own pair, behind the older entries)
    assert len(PackChoice(args, (1, 0, 0)).choices()) == 3
    assert len(PackChoice(args, (0, 1, 0)).choices()) == 4
    assert [c.name() for c in UnpackChoice(args, (0, 0, 1)).choices()] == [
        "unpack_pz.xla", "unpack_pz.pallas", "unpack_pz.pallasb",
        "unpack_pz.window"]
    assert [c.name() for c in PackChoice(args, (0, 0, -1)).choices()] == [
        "pack_mz.xla", "pack_mz.pallas", "pack_mz.pallasb",
        "pack_mz.window"]


@pytest.mark.needs_pinned_host
def test_impl_choice_graph_enumerates_kernel_menu():
    """With impl_choice=True the solver sees ChooseOp decisions for pack/unpack
    and every resolved schedule still computes the right answer."""
    ex, want = _executor()
    g = build_graph(ARGS, impl_choice=True)
    states = get_all_sequences(g, ex.platform, max_seqs=40)
    assert states
    seen_pallas = False
    for st in states:
        names = [op.name() for op in st.sequence.vector()]
        seen_pallas = seen_pallas or any(n.endswith(".pallas") for n in names)
    assert seen_pallas, "kernel menu never resolved to a Pallas variant"
    for st in states[:2]:
        out = ex.run(st.sequence)
        np.testing.assert_allclose(np.asarray(out["U"]), want, rtol=1e-6)


@pytest.mark.needs_pinned_host
def test_single_device_numerics_subprocess():
    """Regression: on a SINGLE device (no xla_force_host_platform_device_count,
    the configuration the real TPU bench runs in), spilling 4D faces with tiny
    trailing dims through pinned_host corrupted the round-trip (partial-stripe
    copies; reproduced on CPU and TPU v5e).  The (rows, 128) staging layout
    must survive — this runs where conftest's 8-device env cannot mask it."""
    import subprocess
    import sys as _sys

    code = """
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.pop("XLA_FLAGS", None)
import jax, jax.numpy as jnp, numpy as np
from tenzing_tpu.core.platform import Platform
from tenzing_tpu.models.halo import HaloArgs
from tenzing_tpu.models.halo_pipeline import (
    host_buffer_names, make_pipeline_buffers, naive_order)
from tenzing_tpu.runtime.executor import TraceExecutor
args = HaloArgs(nq=2, lx=4, ly=4, lz=4, radius=1)
bufs, want = make_pipeline_buffers(args, seed=0)
host_sh = jax.sharding.SingleDeviceSharding(jax.devices()[0], memory_kind="pinned_host")
jbufs = {k: (jax.device_put(jnp.asarray(v), host_sh) if k in host_buffer_names()
             else jnp.asarray(v)) for k, v in bufs.items()}
plat = Platform.make_n_lanes(1)
U = np.asarray(TraceExecutor(plat, jbufs).run(naive_order(args, plat))["U"])
assert (U == want).all(), f"{(U != want).sum()} corrupted elements"
print("SINGLE_DEVICE_OK")
"""
    out = subprocess.run(
        [_sys.executable, "-c", code],
        capture_output=True,
        text=True,
        timeout=240,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env={k: v for k, v in os.environ.items() if k != "XLA_FLAGS"},
    )
    assert "SINGLE_DEVICE_OK" in out.stdout, out.stdout + out.stderr


@pytest.mark.needs_pinned_host
def test_pipeline_benchmarkable_smoke():
    from tenzing_tpu.bench.benchmarker import BenchOpts, EmpiricalBenchmarker

    ex, _ = _executor(n_lanes=1)
    bench = EmpiricalBenchmarker(ex)
    res = bench.benchmark(
        naive_order(ARGS, ex.platform), BenchOpts(n_iters=3, target_secs=0.0005)
    )
    assert res.pct50 > 0.0


@pytest.mark.needs_pinned_host
def test_greedy_overlap_order_legal_disciplined_and_correct():
    """The greedy incumbent (bench.py's anytime seed): every prefix passes the
    sync oracle, every transfer is posted before any await (the discipline the
    reference graph hard-codes, ops_halo_exchange.cu:249-256), packs alternate
    lanes, and the result is numerically right."""
    from tenzing_tpu.core.event_synchronizer import EventSynchronizer
    from tenzing_tpu.core.sequence import Sequence
    from tenzing_tpu.models.halo_pipeline import greedy_overlap_order

    plat = Platform.make_n_lanes(2)
    order = greedy_overlap_order(ARGS, plat)
    g = build_graph(ARGS)
    ops = order.vector()
    for i, op in enumerate(ops):
        assert EventSynchronizer.is_synced(g, Sequence(ops[:i]), op), op.desc()
    names = [op.desc() for op in ops]
    first_await = min(i for i, n in enumerate(names) if n.startswith("await"))
    last_post = max(i for i, n in enumerate(names) if n.startswith(("spill", "fetch")))
    assert last_post < first_await
    lanes = {n.split("@")[1] for n in names if n.startswith("pack") and "@" in n}
    assert len(lanes) == 2
    ex, want = _executor()
    out = ex.run(order)
    np.testing.assert_allclose(np.asarray(out["U"]), want, rtol=1e-6)


@pytest.mark.needs_pinned_host
def test_index_tie_survives_compilation():
    """The INDEX_TIE pack's token edge must survive XLA compilation as a
    DYNAMIC slice start (the select-derived zero on the direction axis).
    Guards against a clamp-analysis improvement folding it to a static slice
    — which would compile every halo schedule to the same unordered program
    (probed: adding the zero on a full-extent axis was folded exactly so)."""
    from tenzing_tpu.core.platform import Platform
    from tenzing_tpu.models.halo import HaloArgs
    from tenzing_tpu.models.halo_pipeline import (
        build_graph,
        host_buffer_names,
        make_pipeline_buffers,
        naive_order,
    )
    from tenzing_tpu.runtime.executor import TraceExecutor

    args = HaloArgs(nq=1, lx=8, ly=8, lz=8, radius=2)
    bufs, _ = make_pipeline_buffers(args, seed=0, with_expected=False)
    jbufs = TraceExecutor.place_host_buffers(bufs, host_buffer_names())
    plat = Platform.make_n_lanes(2)
    ex = TraceExecutor(plat, jbufs)
    seq = naive_order(args, Platform.make_n_lanes(1))
    compiled = ex.compiled_text(seq)
    assert "dynamic-slice" in compiled, (
        "pack token edges folded to static slices — INDEX_TIE ordering lost"
    )
