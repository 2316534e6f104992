"""The mesh exchange's packs take their ordering token by index (ISSUE 29).

``models/halo.py`` ``Pack`` reads one buffer, the shard's whole grid; taking
the token by a value-preserving add made six live versions of the grid an
iteration (36.5 ms on four v5e chips at 448^3 a shard, 20.8 with the token
in the slice's start index: PERF.md, PR 29).  CPU, four virtual devices, a
toy shard: what is checked is the traced and lowered program, never a time.
"""

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tenzing_tpu.bench.driver import naive_schedule
from tenzing_tpu.core.graph import Graph
from tenzing_tpu.core.platform import Platform
from tenzing_tpu.models.halo import (
    DIRECTIONS,
    HaloArgs,
    Pack,
    _face_slices,
    add_to_graph,
    dir_name,
    engine_overlap_order,
    make_halo_buffers,
)
from tenzing_tpu.models.halo_pipeline import (
    PackFlat,
    flatten_face,
    stage_face,
)
from tenzing_tpu.obs.metrics import get_metrics
from tenzing_tpu.runtime.executor import TraceExecutor

# every extent different, so the local grid's type names nothing else
ARGS = HaloArgs(nq=2, lx=8, ly=6, lz=4, radius=2)
MESH = (2, 2, 1)
GRID_TYPE = "tensor<" + "x".join(str(n) for n in ARGS.local_shape()) + "xf32>"
# the reads that still get a value-preserving add: the two received x faces
# (the y and z unpacks take their token by index since PR 32:
# tests/test_halo_window_unpack.py)
X_FACE_BYTES = sum(int(np.prod(_face_slices(ARGS, d, "pack")[1])) * 4
                   for d in DIRECTIONS if d[0] != 0)
SCHEDULES = ["naive", "xla", "rdma"]
DIR_IDS = [dir_name(d) for d in DIRECTIONS]


class ValueTiedPack(Pack):
    """The pack as it was up to PR 28: a plain slice, the token added onto
    its read by the executor."""

    INDEX_TIE = False

    def apply(self, bufs, ctx):
        starts, sizes = _face_slices(self._args, self._d, "pack")
        return {f"buf_{dir_name(self._d)}":
                jax.lax.dynamic_slice(bufs["U"], starts, sizes)}


def _setup(which: str):
    from jax.sharding import Mesh, NamedSharding

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(MESH), ("x", "y", "z"))
    bufs, specs, want = make_halo_buffers(MESH, ARGS, seed=3)
    plat = Platform.make_n_lanes(2, mesh=mesh, specs=specs)
    g = add_to_graph(Graph(), ARGS, xfer_choice=True)
    seq = (naive_schedule("halo_mesh", g, None) if which == "naive"
           else engine_overlap_order(g, plat, which))
    # each buffer sharded as the benchmark's builder shards it
    ex = TraceExecutor(plat, {
        k: jax.device_put(v, NamedSharding(mesh, specs[k]))
        for k, v in bufs.items()})
    return ex, seq, want


def _lowered_repeat_n(ex, seq) -> str:
    return jax.jit(ex._stepped_fn(seq.vector())).lower(
        ex.init_bufs, jnp.int32(1)).as_text()


def _grid_adds(text: str) -> int:
    return sum(1 for l in text.splitlines()
               if "stablehlo.add" in l and l.rstrip().endswith(GRID_TYPE))


def _counters():
    reg = get_metrics()
    return tuple(reg.counter(name).value for name in (
        "executor.index_ties", "executor.value_tied_bytes",
        "halo.window_unpacks", "halo.window_unpacks_turned"))


@pytest.mark.needs_shard_map
@pytest.mark.parametrize("which", SCHEDULES)
def test_repeat_n_program_adds_nothing_onto_the_grid(which):
    """(a) no ``add`` of the local grid's shape in the lowered repeat-n
    program (six up to PR 28, one a pack), and the one-shot result is
    ``make_halo_buffers``' expected grid cell for cell."""
    ex, seq, want = _setup(which)
    assert _grid_adds(_lowered_repeat_n(ex, seq)) == 0
    np.testing.assert_array_equal(np.asarray(ex.run(seq)["U"]), want)


@pytest.mark.needs_shard_map
@pytest.mark.parametrize("which", SCHEDULES)
def test_counters_read_ten_index_ties_and_the_x_faces(which):
    """(e) one traced body of the mesh halo: ten ops take their token by
    index (six packs; since PR 32 the four unpacks of y and z faces, which
    the window kernel writes), sixteen where the six exchanges are
    remote-DMA posts (since PR 44 the token is an operand of their kernel),
    and the value-tied reads are the two received x faces, neither of them
    the grid.  Of the four window unpacks the two z faces' take their face
    turned (PR 47)."""
    ex, seq, _ = _setup(which)
    before = _counters()
    _lowered_repeat_n(ex, seq)
    ties, tied_bytes, window_unpacks, turned = (
        b - a for a, b in zip(before, _counters()))
    assert ties == (16 if which == "rdma" else 10)
    assert tied_bytes == X_FACE_BYTES
    assert (window_unpacks, turned) == (4, 2)


@pytest.mark.needs_shard_map
def test_a_value_tied_pack_shows_in_lowering_and_counter(monkeypatch):
    """(e) the op that declares no index tie gets the executor's value-add
    on its read, the whole grid: six grid adds in the lowered program, and
    ``executor.value_tied_bytes`` says so without a trace."""
    from tenzing_tpu.models import halo

    monkeypatch.setattr(halo, "Pack", ValueTiedPack)
    ex, seq, want = _setup("xla")
    before = _counters()
    text = _lowered_repeat_n(ex, seq)
    ties, tied_bytes, _, _ = (b - a for a, b in zip(before, _counters()))
    grid_bytes = int(np.prod(ARGS.local_shape())) * 4
    assert _grid_adds(text) == 6
    assert ties == 4  # the y and z unpacks
    assert tied_bytes == X_FACE_BYTES + 6 * grid_bytes
    np.testing.assert_array_equal(np.asarray(ex.run(seq)["U"]), want)


@pytest.mark.needs_shard_map
def test_pack_token_edge_survives_compilation_under_shard_map():
    """(b) the compiled one-shot program still slices the grid at a start
    that is not a constant (the token's zero on the face's own axis): were
    it folded, every order of the packs would compile to the same unordered
    program (tests/test_halo_pipeline.py has the one-chip twin).  Since
    PR 44 the x faces alone are XLA's slices (the y and z packs' edge is a
    kernel operand: tests/test_halo_window_pack.py), and naive puts both on
    one lane."""
    import re

    ex, seq, _ = _setup("naive")
    faces = {"{" + ",".join(str(n) for n in _face_slices(ARGS, d, "pack")[1])
             + "}" for d in DIRECTIONS if d[0] != 0}
    tied = 0
    for line in ex.compiled_text(seq).splitlines():
        m = re.search(
            r"\bdynamic-slice\((.*?)\), dynamic_slice_sizes=(\{[\d,]+\})", line)
        if m and m.group(2) in faces:
            starts = m.group(1).split(", ")[1:]
            tied += any(not s.startswith("%constant") for s in starts)
    # the lane's first pack has no token yet (its start is a constant);
    # the other x pack starts where a token says
    assert tied >= 1, "the packs' token edges folded to static slices"


@pytest.mark.needs_shard_map
@pytest.mark.parametrize("which", SCHEDULES)
def test_mesh_halo_is_the_expected_grid_and_the_timed_fence_agrees(which):
    """Under ``shard_map`` on four virtual devices: the one-shot program
    leaves ``make_halo_buffers``' expected grid cell for cell, and the
    repeat-n program's fence after three iterations is, to the last bit,
    its fence for the one-shot program's outputs after none (the
    benchmark's ``timed_fence_gap``: the exchange is idempotent)."""
    ex, seq, want = _setup(which)
    once = ex.run(seq)
    np.testing.assert_array_equal(np.asarray(once["U"]), want)
    stepped = jax.jit(ex._stepped_fn(seq.vector()))

    def fence(bufs, n):
        # fetched before the next dispatch, as the harness does: two runs
        # of a program with collectives are never in flight together
        return float(jax.device_get(stepped(bufs, jnp.int32(n))[0]))

    after_n = fence(ex.init_bufs, 3)
    assert after_n == fence(once, 0)
    # and the fence sees the exchange: the untouched buffers sum to less
    assert fence(ex.init_bufs, 0) != after_n


@pytest.mark.needs_shard_map
def test_unpack_token_edge_is_the_kernels_first_operand():
    """The traced repeat-n program hands every window unpack a scalar
    prefetch operand that is a value of the program (the token's zero),
    never a literal: were it one, every order of the unpacks would trace
    to the same unordered kernel calls.  (That the compiled TPU program
    keeps it: tests/test_tpu_compile.py, ``mesh_halo_loop``.)"""
    from jax.extend import core as jcore

    def subjaxprs(params):
        for v in params.values():
            for x in (v if isinstance(v, (tuple, list)) else (v,)):
                if isinstance(x, jcore.ClosedJaxpr):
                    yield x.jaxpr
                elif isinstance(x, jcore.Jaxpr):
                    yield x

    ex, seq, _ = _setup("xla")
    closed = jax.make_jaxpr(ex._stepped_fn(seq.vector()))(
        ex.init_bufs, jnp.int32(1))
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call" and "halo_window_unpack" \
                    in str(eqn.params.get("name", "")) + str(
                        eqn.params.get("name_and_src_info", "")):
                found.append(eqn.invars[0])
            for sub in subjaxprs(eqn.params):
                walk(sub)

    walk(closed.jaxpr)
    assert len(found) == 4
    assert not any(isinstance(v, jcore.Literal) for v in found)


@pytest.mark.parametrize("cls", [Pack, PackFlat])
def test_pack_traced_outside_the_contract_raises(cls):
    """(c) no ``tok_index_zero``, no happens-before edge: fail loudly."""
    op = cls(ARGS, DIRECTIONS[0])
    u = jnp.zeros(ARGS.local_shape(), jnp.float32)
    with pytest.raises(RuntimeError, match="tok_index_zero"):
        op.apply({"U": u}, SimpleNamespace(tok_index_zero=None))


def _packflat_up_to_pr28(args, d, bufs, ctx):
    """``PackFlat.apply`` as it stood before the slice moved to ``Pack``;
    since ISSUE 48 a lane-thin z face is turned before it is flattened
    (the one staging order of its direction)."""
    starts, sizes = _face_slices(args, d, "pack")
    z = ctx.tok_index_zero
    axis = 1 + [i for i, v in enumerate(d) if v != 0][0]
    starts = tuple(s + z if i == axis else s for i, s in enumerate(starts))
    sl = jax.lax.dynamic_slice(bufs["U"], starts, sizes)
    if d[2]:
        sl = jnp.swapaxes(sl, 2, 3)
    return {f"buf_{dir_name(d)}": flatten_face(sl, sl.shape)}


@pytest.mark.parametrize("d", DIRECTIONS, ids=DIR_IDS)
def test_packflat_traces_as_before(d):
    """(d) the one-chip flagship's pack is the program it was: the same
    jaxpr, equation for equation (``halo512.climb``'s naive runs it), a z
    face with the staging order's one ``swapaxes`` before the flatten."""
    u = jnp.zeros(ARGS.local_shape(), jnp.float32)
    z = jnp.zeros((), jnp.int32)

    def now(u, z):
        return PackFlat(ARGS, d).apply(
            {"U": u}, SimpleNamespace(tok_index_zero=z))

    def then(u, z):
        return _packflat_up_to_pr28(
            ARGS, d, {"U": u}, SimpleNamespace(tok_index_zero=z))

    assert str(jax.make_jaxpr(now)(u, z)) == str(jax.make_jaxpr(then)(u, z))


@pytest.mark.parametrize("d", DIRECTIONS, ids=DIR_IDS)
def test_pack_and_packflat_share_one_slice(d):
    """The mesh pack's face is the flat pack's before staging, to the bit,
    at a token's zero as at a plain one."""
    rng = np.random.default_rng(5)
    u = jnp.asarray(rng.random(ARGS.local_shape(), dtype=np.float32))
    ctx = SimpleNamespace(tok_index_zero=jnp.zeros((), jnp.int32))
    name = f"buf_{dir_name(d)}"
    face = Pack(ARGS, d).apply({"U": u}, ctx)[name]
    flat = PackFlat(ARGS, d).apply({"U": u}, ctx)[name]
    _, sizes = _face_slices(ARGS, d, "pack")
    np.testing.assert_array_equal(np.asarray(stage_face(face, d)),
                                  np.asarray(flat))
    starts, _ = _face_slices(ARGS, d, "pack")
    want = np.asarray(u)[tuple(slice(s, s + n)
                               for s, n in zip(starts, sizes))]
    np.testing.assert_array_equal(np.asarray(face), want)
