"""Ahead-of-time compiles for a described (not attached) TPU v5e.

The TPU compiler is installed in the CPU sandbox and compiles for a chip that
is only *described* (``jax.experimental.topologies``), so what Mosaic or XLA
would refuse on the chip — an unaligned slice, too much VMEM, a program that
does not fit 16 GB of HBM, a host memory kind it cannot place — is refused
here, at no chip time.  Nothing runs: these tests say nothing about results
or times, and a compile that passes is not a chip run.

Covered, at the shapes ``python bench.py`` builds without ``--smoke``:

* every halo kernel of ops/halo_pallas.py on every face it is on the menu
  for (window 6+6, batched 4+4, flat 4+4, the z faces' ``.window`` pair
  2+2), grid ``(3, 518, 520, 640)`` f32;
* the fused and the split (semaphore-output) rdma copy of ops/rdma.py;
* the moe/attention/spmv kernels;
* the SpMV column sweep at the source's 150 000 rows and the whole naive
  program of the benchmark's ``spmv16k`` configuration (one-shot and
  repeat-n, ``host_x`` in pinned host memory);
* two whole halo schedule programs (the naive baseline and the
  ``greedy-alias-6l`` incumbent), both as the single-shot program the
  integrity gate runs and as the repeat-n benchmark program, with the
  pinned_host staging buffers and the 2 GB grid carried through ``fori_loop``,
  and who owns what in the incumbent's loop;
* the models/halo.py mesh exchange on the four described chips, both
  transfer engines (XLA collective-permute, remote DMA with barriers), and
  its window unpack and window pack on the cell's unpadded ``(3, 454, 454,
  454)`` shard.

During such a compile ``jax.default_backend()`` is still ``cpu``, so the
kernels' ``_interpret()`` would pick the interpreter: the tests (never an
option of the program) monkeypatch it and assert ``tpu_custom_call`` in the
compiled text.

The topology is described inside a module-scoped fixture — never at import —
because only one process may load libtpu, and every xdist worker imports every
test file (on-chip-measurement guide, section 2).  Keep these in ONE file.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tenzing_tpu.models.halo import DIRECTIONS, HaloArgs, _face_slices, dir_name

# the north-star cell: python bench.py (halo, 512^3, nQ=3, radius 3)
FLAGSHIP = HaloArgs(nq=3, lx=512, ly=512, lz=512, radius=3)
DIR_IDS = [dir_name(d) for d in DIRECTIONS]
HBM_BYTES = 16 * 2**30  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    """The described v5e 2x2 host, with the persistent compile cache off
    around the module: an entry written for a described chip cannot be read
    back without one, and the next compile would warn."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_chip_kernels(monkeypatch):
    """Route the op classes to the real kernels: ``_interpret()`` reads the
    process's default backend, which stays ``cpu`` during an AOT compile."""
    from tenzing_tpu.ops import halo_pallas, rdma

    monkeypatch.setattr(halo_pallas, "_interpret", lambda: False)
    monkeypatch.setattr(rdma, "_interpret", lambda: False)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)


def _grid(one_chip):
    from tenzing_tpu.models.halo_pipeline import _padded_shape

    shape = _padded_shape(FLAGSHIP.local_shape(), 4)
    assert shape == (3, 518, 520, 640)
    return _sds(shape, jnp.float32, one_chip)


def _both_programs(plat, seq, bufs, host_names, one_chip):
    """One schedule compiled both ways for the described chip: the one-shot
    program the integrity gate runs, then the repeat-n benchmark program."""
    from tenzing_tpu.runtime.executor import TraceExecutor

    ex = TraceExecutor(plat, bufs)

    def host_typed(b):
        # an array committed to pinned_host traces as float32<host>; a
        # ShapeDtypeStruct drops the memory space from its type, so restate
        # it (a no-op placement: the argument already arrives there)
        return {k: jax.device_put(v, jax.memory.Space.Host)
                if k in host_names else v for k, v in b.items()}

    program = ex.program(seq)
    stepped = ex._stepped_fn(seq.vector())
    n = _sds((), jnp.int32, one_chip)
    yield jax.jit(lambda b: program(host_typed(b))).lower(bufs).compile()
    yield jax.jit(lambda b, n: stepped(host_typed(b), n)).lower(
        bufs, n).compile()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_described_device_has_published_peaks(topo):
    """The device kind the chip reports is a row of the roofline table —
    a non-smoke run on it gets its fractions of peak, not an error."""
    from tenzing_tpu.bench.roofline import peaks_for

    dev = topo.devices[0]
    assert dev.platform == "tpu" and len(topo.devices) == 4
    assert peaks_for(dev.device_kind).hbm_bytes == 819e9


# -- halo kernels -------------------------------------------------------------


def _face(d, which):
    starts, _ = _face_slices(FLAGSHIP, d, which)
    _, sizes = _face_slices(FLAGSHIP, d, "pack")
    return tuple(starts), tuple(sizes)


@pytest.mark.parametrize("d", DIRECTIONS, ids=DIR_IDS)
def test_window_pack(one_chip, d):
    from tenzing_tpu.ops.halo_pallas import pack_face_pallas

    starts, sizes = _face(d, "pack")
    _assert_kernel(pack_face_pallas.lower(
        _grid(one_chip), starts, sizes, interpret=False).compile())


@pytest.mark.parametrize("d", DIRECTIONS, ids=DIR_IDS)
def test_window_unpack(one_chip, d):
    from tenzing_tpu.ops.halo_pallas import unpack_face_pallas

    starts, sizes = _face(d, "unpack")
    _assert_kernel(unpack_face_pallas.lower(
        _grid(one_chip), _sds(sizes, jnp.float32, one_chip), starts,
        interpret=False).compile())


# the batched kernels are on the menu where a DMA moves more than one face
# row (the y/z faces); the flat kernels where the trailing dim is lane
# -aligned (the x/y faces) — PackChoice/UnpackChoice gate on the same rules
BATCHED = [d for d in DIRECTIONS if d[0] == 0]
FLAT = [d for d in DIRECTIONS if d[2] == 0]


@pytest.mark.parametrize("d", BATCHED, ids=[dir_name(d) for d in BATCHED])
def test_batched_pack(one_chip, d):
    from tenzing_tpu.ops.halo_pallas import _face_bx, pack_face_pallas_batched

    assert _face_bx(FLAGSHIP, d) > 1
    starts, sizes = _face(d, "pack")
    _assert_kernel(pack_face_pallas_batched.lower(
        _grid(one_chip), starts, sizes, interpret=False).compile())


@pytest.mark.parametrize("d", BATCHED, ids=[dir_name(d) for d in BATCHED])
def test_batched_unpack(one_chip, d):
    from tenzing_tpu.ops.halo_pallas import (
        _face_bx,
        unpack_face_pallas_batched,
    )

    assert _face_bx(FLAGSHIP, d, which="unpack") > 1
    starts, sizes = _face(d, "unpack")
    _assert_kernel(unpack_face_pallas_batched.lower(
        _grid(one_chip), _sds(sizes, jnp.float32, one_chip), starts,
        interpret=False).compile())


def _flat(sizes, one_chip):
    from tenzing_tpu.models.halo_pipeline import _flat_rows

    return _sds((_flat_rows(sizes), 128), jnp.float32, one_chip)


@pytest.mark.parametrize("d", FLAT, ids=[dir_name(d) for d in FLAT])
def test_flat_pack(one_chip, d):
    from tenzing_tpu.ops.halo_pallas import _flat_ok, pack_face_flat_pallas

    assert _flat_ok(FLAGSHIP, d)
    starts, sizes = _face(d, "pack")
    _assert_kernel(pack_face_flat_pallas.lower(
        _grid(one_chip), starts, sizes, interpret=False).compile())


@pytest.mark.parametrize("d", FLAT, ids=[dir_name(d) for d in FLAT])
def test_flat_unpack(one_chip, d):
    from tenzing_tpu.ops.halo_pallas import _flat_ok, unpack_face_flat_pallas

    assert _flat_ok(FLAGSHIP, d)
    starts, sizes = _face(d, "unpack")
    _assert_kernel(unpack_face_flat_pallas.lower(
        _grid(one_chip), _flat(sizes, one_chip), starts, sizes,
        interpret=False).compile())


def test_menus_offer_exactly_the_compiled_kernels():
    """The cases above are the flagship menus: 6+6 window, 4+4 batched,
    4+4 flat — a menu that grows a kernel must grow a compile case (the z
    faces' 2+2 ``.window`` entries: ``test_window_pair_on_the_padded_grid``)."""
    from tenzing_tpu.ops.halo_pallas import PackChoice, UnpackChoice

    def suffixes(choice_cls):
        return sorted(c.name().split(".", 1)[1]
                      for d in DIRECTIONS
                      for c in choice_cls(FLAGSHIP, d).choices())

    want = sorted(["xla"] * 6 + ["pallas"] * 6 + ["pallasb"] * 4
                  + ["pallasf"] * 4 + ["window"] * 2)
    assert suffixes(PackChoice) == want
    assert suffixes(UnpackChoice) == want


# -- rdma ---------------------------------------------------------------------


def _face_buffer(one_chip):
    _, sizes = _face(DIRECTIONS[0], "pack")
    return _flat(sizes, one_chip)


def test_rdma_fused_local_copy(one_chip):
    from tenzing_tpu.ops.rdma import rdma_copy_fused_local

    c = jax.jit(lambda x: rdma_copy_fused_local(x, interpret=False)).lower(
        _face_buffer(one_chip)).compile()
    _assert_kernel(c)


def test_rdma_split_start_wait(one_chip):
    """The post/wait pair passing DMA semaphores between two kernels — a path
    no CPU test reaches (the interpreter has no semaphore outputs)."""
    from tenzing_tpu.ops.rdma import rdma_start_loopback, rdma_wait_loopback

    def copy(x):
        send, recv, y = rdma_start_loopback(x)
        return rdma_wait_loopback(x, send, recv, y)

    c = jax.jit(copy).lower(_face_buffer(one_chip)).compile()
    assert c.as_text().count("tpu_custom_call") >= 2


# -- moe / attention / spmv kernels -------------------------------------------


def test_ffn_batched(one_chip):
    """moe: MoEPipeArgs() — 8 experts, d 512, d_ff 2048; capacity 304 is what
    make_pipe_buffers(seed=0) routes at 8192 tokens in 4 chunks."""
    from tenzing_tpu.ops.ffn_pallas import ffn_pallas_batched

    f32 = jnp.float32
    _assert_kernel(ffn_pallas_batched.lower(
        _sds((8, 304, 512), f32, one_chip),
        _sds((8, 512, 2048), f32, one_chip),
        _sds((8, 2048, 512), f32, one_chip), interpret=False).compile())


ATTN = dict(batch=4, seq=8 * 1024, block=1024, head_dim=128)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_attention_fused(one_chip, dtype):
    """attn: 8k context in 8 blocks of 1024, head dim 128, batch 4."""
    from tenzing_tpu.ops.attention_pallas import attn_fused_pallas

    b, n, d = ATTN["batch"], ATTN["seq"], ATTN["head_dim"]
    qkv = _sds((b, n, d), dtype, one_chip)
    st = _sds((b, n, d), jnp.float32, one_chip)
    _assert_kernel(attn_fused_pallas.lower(
        qkv, qkv, qkv, st, st, st, d ** -0.5, bkv=ATTN["block"],
        interpret=False).compile())


def test_attention_block(one_chip):
    from tenzing_tpu.ops.attention_pallas import attn_block_pallas

    b, n, d = ATTN["batch"], ATTN["seq"], ATTN["head_dim"]
    q = _sds((b, n, d), jnp.float32, one_chip)
    kv = _sds((b, ATTN["block"], d), jnp.float32, one_chip)
    _assert_kernel(attn_block_pallas.lower(
        q, kv, kv, q, q, q, d ** -0.5, interpret=False).compile())


# trinity-attn32k.climb: 16 384 tokens, 32 query heads over 4 K/V heads of
# 128, bfloat16, query blocks of 4096; (first row of the query block, first
# key of its visible range, keys in it, window) of the full layer's last
# block and of a window layer's third
CELL_ATTN = dict(n=16 * 1024, heads=32, kv_heads=4, d=128, rows=4096)
FINISHING = {"full": (12288, 0, 16384, None),
             "window": (8192, 6144, 6144, 2048)}


@pytest.mark.parametrize("kind", list(FINISHING))
def test_attention_fused_finishes_its_rows_of_o(one_chip, kind):
    """The fused kernel that writes O (ISSUE 34) at the attention cell's
    shapes: Mosaic takes it with O an aliased operand left in place (no
    fetch of it) and the output's tiles offset by ``q0 // 512``."""
    from tenzing_tpu.ops.attention_pallas import attn_fused_pallas

    c = CELL_ATTN
    q0, k0, keys, window = FINISHING[kind]
    bf = jnp.bfloat16
    q = _sds((c["heads"], c["rows"], c["d"]), bf, one_chip)
    kv = _sds((c["kv_heads"], keys, c["d"]), bf, one_chip)
    o = _sds((c["heads"], c["n"], c["d"]), bf, one_chip)
    compiled = attn_fused_pallas.lower(
        q, kv, kv, None, None, None, c["d"] ** -0.5, bkv=1024,
        q_pos=q0 - k0, causal=True, window=window, interpret=False,
        finish=True, o=o, o_row0=q0).compile()
    _assert_kernel(compiled)
    text = compiled.as_text()
    call = next(l for l in text.splitlines() if "tpu_custom_call" in l)
    assert " = bf16[32,16384,128]" in call  # one output: O, no state
    assert "output_to_operand_aliasing={{}: (4, {})}" in call


@pytest.mark.parametrize("kind", list(FINISHING))
def test_attention_fused_takes_whole_operands(one_chip, kind):
    """The same calls handed the layer's whole Q, K and V (ISSUE 37):
    Mosaic takes the query tiles offset by ``q0 // 512`` and the K/V tiles
    by ``k0 // 1024`` in the index maps, the ordering token's zero on the
    scalar-prefetched positions, and the compiled program holds the kernel
    and no slice of an operand."""
    from tenzing_tpu.ops.attention_pallas import attn_fused_pallas

    c = CELL_ATTN
    q0, k0, keys, window = FINISHING[kind]
    bf = jnp.bfloat16
    q = _sds((c["heads"], c["n"], c["d"]), bf, one_chip)
    kv = _sds((c["kv_heads"], c["n"], c["d"]), bf, one_chip)
    compiled = attn_fused_pallas.lower(
        q, kv, kv, None, None, None, c["d"] ** -0.5, bkv=1024,
        q_pos=q0 - k0, causal=True, window=window, interpret=False,
        finish=True, o=q, o_row0=q0, q_row0=q0, rows=c["rows"], k_row0=k0,
        keys=keys, tok=_sds((), jnp.int32, one_chip)).compile()
    _assert_kernel(compiled)
    text = compiled.as_text()
    assert "slice" not in text
    call = next(l for l in text.splitlines() if "tpu_custom_call" in l)
    assert "output_to_operand_aliasing={{}: (4, {})}" in call


_LOOP_TEXTS = {}  # a whole program's compiled text, once a module run


def _attention_period_text(one_chip, monkeypatch, which):
    """The compiled text of the repeat-n program of
    ``trinity-attn32k.climb``'s start point (every query block on the fused
    kernel, two lanes) or naive (chains of ``attn_fold`` kernels)."""
    from benchmarks.builders.attn_period import unfused_prefer
    from tenzing_tpu.bench.workloads import attn_fused_prefer
    from tenzing_tpu.core.platform import Platform
    from tenzing_tpu.models.ring_attention import (
        RingAttnArgs,
        blocked_buffer_shapes,
        period_graph,
    )
    from tenzing_tpu.runtime.executor import TraceExecutor
    from tenzing_tpu.solve.local import drive, phase_policy

    if ("attn", which) in _LOOP_TEXTS:
        return _LOOP_TEXTS["attn", which]
    # the kernels pick the interpreter by the process's default backend,
    # which stays ``cpu`` during an AOT compile
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    c = CELL_ATTN
    layers = [(f"L{i}", RingAttnArgs(
        n_devices=c["n"] // 2048, seq_local=2048, head_dim=c["d"],
        dtype="bfloat16", heads=c["heads"], kv_heads=c["kv_heads"],
        causal=True, window=w, q_block=c["rows"]))
        for i, w in enumerate((2048, 2048, 2048, None))]
    graph = period_graph(layers, impl_choice=True, fused_choice=True)
    bufs = {name: _sds(shape, jnp.dtype(dtype), one_chip)
            for tag, a in layers
            for name, (shape, dtype) in blocked_buffer_shapes(a, tag).items()}
    phases = [tag + "." for tag, _ in layers]

    plat = Platform.make_n_lanes(2 if which == "start" else 1)
    seq, _ = drive(graph, plat, phase_policy(
        plat, phases,
        attn_fused_prefer if which == "start" else unfused_prefer))
    ex = TraceExecutor(plat, bufs)
    compiled = jax.jit(ex._stepped_fn(seq.vector())).lower(
        bufs, _sds((), jnp.int32, one_chip)).compile()
    _LOOP_TEXTS["attn", which] = compiled.as_text()
    return _LOOP_TEXTS["attn", which]


@pytest.mark.parametrize("which,kernels", [("start", 16), ("naive", 53)])
def test_attention_period_loop_writes_o_in_place(one_chip, monkeypatch,
                                                 which, kernels):
    """The repeat-n program of ``trinity-attn32k.climb``'s start point
    (every query block on the fused kernel) and of its naive (chains of
    ``attn_fold`` kernels) as the TPU compiler leaves them.  Inside the
    start point's ``while`` body a layer's O is the result of its four
    ``attn_fused`` calls and of nothing else: no copy of it, no
    concatenate, no division, and no float32 state leaves a kernel.
    Nor is a row of Q or a key range of K or V sliced out for a kernel
    (ISSUE 37): the kernels read the layer's buffers as they lie.
    Naive's chains keep their state and finish each block's rows with an
    update of O in place."""
    from tenzing_tpu.obs.attrib.hlo import loop_ops_of_shape

    text = _attention_period_text(one_chip, monkeypatch, which)
    assert text.count("tpu_custom_call") == kernels
    whole_o = loop_ops_of_shape(text, "bf16[32,16384,128]")
    state = loop_ops_of_shape(text, "f32[32,4096,128]")
    if which == "start":
        assert len(whole_o) == 16
        assert all(o.opcode == "custom-call"
                   and o.name.startswith("attn_fused") for o in whole_o)
        assert not state
        assert "divide" not in text
        # Q's row blocks and the key ranges of K and V a vertex sees
        assert not loop_ops_of_shape(text, "bf16[32,4096,128]")
        for keys in (4096, 6144, 8192, 12288):
            assert not loop_ops_of_shape(text, f"bf16[4,{keys},128]")
        assert "dynamic-slice" not in text
    else:
        assert len(whole_o) == 16  # a block's rows put into its layer's O
        assert all("dynamic-update-slice" in o.fused
                   or o.opcode == "dynamic-update-slice" for o in whole_o)
        assert state


def test_attention_start_point_names_its_sixteen_kernels(one_chip,
                                                         monkeypatch):
    """ISSUE 38: in the start point's repeat-n program the 16
    ``attn_fused`` custom calls carry 16 distinct vertex names, each its
    vertex's ``apply``, and no instruction of the loop body that moves
    data is unscoped but the loop's own (its counter and the carry's
    copies)."""
    from tenzing_tpu.obs.attrib.hlo import UNSCOPED, loop_ops_by_scope

    ops = loop_ops_by_scope(
        _attention_period_text(one_chip, monkeypatch, "start"))
    kernels = [o for o in ops if o.opcode == "custom-call"]
    assert len(kernels) == 16
    assert len({o.vertex for o in kernels}) == 16
    assert all(o.part == "apply" and o.vertex.endswith(".fused")
               and o.name.startswith("attn_fused") for o in kernels)
    unscoped = [o for o in ops if o.vertex == UNSCOPED]
    assert unscoped  # the counter's add at the least
    # what is nobody's is the loop's: scalars of the counter, or copies
    assert all(o.bytes <= 8 or o.opcode in ("copy", "copy-start",
                                            "copy-done") for o in unscoped), [
        (o.name, o.opcode, o.result) for o in unscoped]
    # a vertex takes its token by index: scalars under its tie (a lane's
    # first vertex of a layer has its tie folded into its neighbour's)
    ties = {o.vertex for o in ops if o.part == "tie"}
    assert ties <= {o.vertex for o in kernels} and len(ties) >= 12
    assert all(o.bytes <= 8 for o in ops if o.part in ("tie", "join"))


# dsv3-mla-decode.climb: 16 sequences of 8k to 128k cached tokens (the
# configuration's lengths), 128 heads on a 576-wide latent cache in pages of
# 2048 keys, held as columns; four layers
def _mla_cell():
    import json

    from tenzing_tpu.models.latent_attention import LatentDecodeArgs
    from tenzing_tpu.models.latent_attention_reference import yarn_scale

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmarks", "configs",
                           "dsv3-mla-decode.json")) as f:
        shapes = json.load(f)["shapes"]
    return LatentDecodeArgs(
        lens=tuple(sorted(shapes["lens"])), scale=yarn_scale(),
        page=shapes["page_tokens"], groups=shapes["groups"],
        fold_pages=shapes["fold_pages"])


def _latent_decode_compiled(one_chip, form, group):
    """``(LatentDecodeArgs, compiled)``: the paged kernel ``form`` at the
    decode cell's shapes, its group ``group`` (``mla_fold``: the group's
    first link, g3's third)."""
    from tenzing_tpu.models.latent_attention import decode_plan
    from tenzing_tpu.ops.attention_pallas import (
        mla_decode_pallas,
        mla_fold_pallas,
    )

    a = _mla_cell()
    grp = decode_plan(a)[group]
    assert grp.tiles == ((5, 5, 5, 6), (27, 34, 45, 64))[group > 0]
    bf = jnp.bfloat16
    operands = (
        _sds((a.batch, a.heads, a.width), bf, one_chip),
        _sds((a.pool_pages, a.width, a.page), bf, one_chip),
        _sds((a.batch, a.width, a.page), bf, one_chip),
        _sds((a.batch,), jnp.int32, one_chip),
        _sds((a.batch, a.max_pages), jnp.int32, one_chip))
    common = dict(v_dim=a.rank, lead0=grp.lead0, interpret=False)
    if form == "mla_decode":
        o = _sds((a.batch, a.heads, a.rank), bf, one_chip)
        compiled = mla_decode_pallas.lower(
            *operands, o, a.scale, tiles=grp.tiles, **common).compile()
    else:
        st = _sds((grp.rows, a.heads, a.rank), jnp.float32, one_chip)
        k_pos, tiles = grp.links[(0, 2)[group > 0]]
        assert tiles == ((5, 5, 5, 6), (0, 2, 13, 16))[group > 0]
        compiled = mla_fold_pallas.lower(
            *operands, st, st, st, a.scale, k_pos=k_pos, tiles=tiles,
            **common).compile()
    return a, compiled


@pytest.mark.parametrize("group", [0, 3])
@pytest.mark.parametrize("form", ["mla_decode", "mla_fold"])
def test_latent_decode_kernels(one_chip, form, group):
    """The paged kernel at the decode cell's shapes, its shortest group and
    its longest (a grid of 21 and of 170 (sequence, page) steps: the pages
    there are; a link of the chain 21 and 31, g3's third with no step for
    its shortest sequence): Mosaic takes K tiles of ``(576, 2048)`` (the
    contraction 576 wide, V the first 512 rows), two scalar operands (the
    walk is immediates of the index maps), and O or the handed state aliased
    in place;
    the pools arrive in the runtime's own layout, so the compiled program
    holds the kernel and no copy of a pool."""
    a, compiled = _latent_decode_compiled(one_chip, form, group)
    _assert_kernel(compiled)
    text = compiled.as_text()
    assert form in text  # the name the device trace shows
    pool = f"bf16[{a.pool_pages},{a.width},{a.page}]"
    moved = [l for l in text.splitlines()
             if " copy(" in l and l.split(" = ")[1].startswith(pool)]
    assert not moved
    # well under a pool: no temporary stands in for one
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("which,kernels", [("start", 16), ("naive", 32)])
def test_latent_decode_loop_moves_no_pool(one_chip, monkeypatch, which,
                                          kernels):
    """The repeat-n program of ``dsv3-mla-decode.climb``'s start point
    (every group on ``mla_decode``) and of its naive (chains of
    ``mla_fold``) as the TPU compiler leaves them: inside the ``while``
    body nothing touches a sealed pool but the kernels, an open pool only
    takes its 16 one-column updates in place a layer, and the program's
    temporaries stay far under one pool (0.63 GB)."""
    from benchmarks.builders.mla_decode import unfused_prefer
    from tenzing_tpu.bench.workloads import attn_fused_prefer
    from tenzing_tpu.core.platform import Platform
    from tenzing_tpu.models.latent_attention import (
        buffer_shapes,
        decode_graph,
    )
    from tenzing_tpu.obs.attrib.hlo import loop_ops_of_shape
    from tenzing_tpu.runtime.executor import TraceExecutor
    from tenzing_tpu.solve.local import drive, phase_policy

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    a = _mla_cell()
    tags = [f"L{i}" for i in range(4)]
    bufs = {name: _sds(shape, jnp.dtype(dtype), one_chip)
            for name, (shape, dtype) in buffer_shapes(a, tags).items()}
    graph = decode_graph(a, tags)
    plat = Platform.make_n_lanes(2 if which == "start" else 1)
    seq, _ = drive(graph, plat, phase_policy(
        plat, [t + "." for t in tags],
        attn_fused_prefer if which == "start" else unfused_prefer))
    ex = TraceExecutor(plat, bufs)
    compiled = jax.jit(ex._stepped_fn(seq.vector())).lower(
        bufs, _sds((), jnp.int32, one_chip)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == kernels
    sealed = loop_ops_of_shape(
        text, f"bf16[{a.pool_pages},{a.width},{a.page}]")
    assert not sealed  # read by the kernels, produced by nothing
    opened = loop_ops_of_shape(text, f"bf16[{a.batch},{a.width},{a.page}]")
    assert len(opened) == 4 * a.batch
    assert all(o.opcode == "dynamic-update-slice" for o in opened)
    assert compiled.memory_analysis().temp_size_in_bytes < 400 << 20


# kimi-linear-kda-decode.climb: 128 sequences (the configuration's lengths),
# three KDA layers of 32 heads on a 128 x 128 float32 state and one latent
# layer of 32 heads on dsv3-mla-decode's cache layout
def _kda_cell():
    import json

    from tenzing_tpu.models.delta_attention import DeltaDecodeArgs
    from tenzing_tpu.models.latent_attention import LatentDecodeArgs

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmarks", "configs",
                           "kimi-linear-kda-decode.json")) as f:
        config = json.load(f)
    shapes, lin = config["shapes"], config["linear_attn_config"]
    lens = tuple(sorted(shapes["lens"]))
    kda = DeltaDecodeArgs(
        batch=len(lens), heads=lin["num_heads"], d=lin["head_dim"],
        taps=lin["short_conv_kernel_size"], groups=shapes["kda_groups"],
        eps=config["rms_norm_eps"])
    mla = LatentDecodeArgs(
        lens=lens, heads=config["num_attention_heads"], scale=192 ** -0.5,
        page=shapes["page_tokens"], groups=shapes["groups"],
        fold_pages=shapes["fold_pages"])
    pattern = [(k, f"L{i}") for i, k in enumerate(config["pattern"])]
    return kda, mla, pattern


def test_kda_step_kernel(one_chip):
    """``kda_step`` at the cell's shapes, its second group: Mosaic takes a
    grid step of one sequence's 32 states (2 MB in, 2 MB out, one (128,
    128) transpose for the heads' columns, the walk over the heads
    unrolled), ``Snew``, ``Cvnew`` and ``o`` aliased in place."""
    from tenzing_tpu.models.delta_attention import KdaFused, buffer_shapes
    from tenzing_tpu.ops.kda_pallas import kda_step_pallas

    kda, _, _ = _kda_cell()
    shapes = buffer_shapes(kda, ("L0",))
    operands = [_sds(*shapes[f"{k}.L0"][:1], jnp.dtype(shapes[f"{k}.L0"][1]),
                     one_chip) for k in KdaFused.READS + KdaFused.WRITES]
    assert operands[9].shape == (kda.batch, 32, 128, 128)
    compiled = kda_step_pallas.lower(
        *operands, lead0=kda.rows, rows=kda.rows, eps=kda.eps,
        interpret=False).compile()
    _assert_kernel(compiled)
    assert "kda_step" in compiled.as_text()  # the name the trace shows
    # no temporary stands in for a state
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20


@pytest.mark.parametrize("which", ["start", "naive"])
def test_hybrid_decode_loop_moves_each_state_once(one_chip, monkeypatch,
                                                  which):
    """The repeat-n program of ``kimi-linear-kda-decode.climb``'s start
    point (every (layer, group) on its fused kernel) and of its naive (the
    XLA chains) as the TPU compiler leaves them: inside the ``while`` body
    the start point touches a layer's ``(128, 32, 128, 128)`` state with its
    ``kda_step`` kernels alone (no copy, no slice), the latent layer's
    sealed pool with nothing but ``mla_decode``, and the whole program fits
    the chip beside its arguments."""
    from benchmarks.builders.kda_decode import unfused_prefer
    from tenzing_tpu.bench.workloads import attn_fused_prefer
    from tenzing_tpu.core.platform import Platform
    from tenzing_tpu.models import delta_attention, latent_attention
    from tenzing_tpu.obs.attrib.hlo import loop_ops_of_shape
    from tenzing_tpu.runtime.executor import TraceExecutor
    from tenzing_tpu.solve.local import drive, phase_policy

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    kda, mla, pattern = _kda_cell()
    tags = {kind: [t for k, t in pattern if k == kind]
            for kind in ("kda", "mla")}
    shapes = {**delta_attention.buffer_shapes(kda, tags["kda"]),
              **latent_attention.buffer_shapes(mla, tags["mla"])}
    bufs = {name: _sds(shape, jnp.dtype(dtype), one_chip)
            for name, (shape, dtype) in shapes.items()}
    graph = delta_attention.hybrid_decode_graph(kda, mla, pattern)
    plat = Platform.make_n_lanes(2 if which == "start" else 1)
    seq, _ = drive(graph, plat, phase_policy(
        plat, [t + "." for _, t in pattern],
        attn_fused_prefer if which == "start" else unfused_prefer))
    ex = TraceExecutor(plat, bufs)
    compiled = jax.jit(ex._stepped_fn(seq.vector())).lower(
        bufs, _sds((), jnp.int32, one_chip)).compile()
    text = compiled.as_text()
    state = loop_ops_of_shape(
        text, f"f32[{kda.batch},{kda.heads},{kda.d},{kda.d}]")
    sealed = loop_ops_of_shape(
        text, f"bf16[{mla.pool_pages},{mla.width},{mla.page}]")
    assert not sealed
    mem = compiled.memory_analysis()
    if which == "start":
        assert text.count("tpu_custom_call") == 3 * kda.groups + mla.groups
        assert len(state) == 3 * kda.groups
        assert all(o.opcode == "custom-call" for o in state), [
            (o.name, o.opcode) for o in state]
        assert mem.temp_size_in_bytes < 1 << 30
    else:
        # the chain writes its group's rows of Snew in place
        assert all(o.opcode in ("fusion", "dynamic-update-slice")
                   for o in state), [(o.name, o.opcode) for o in state]
    total = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
             + mem.output_size_in_bytes)
    assert total < HBM_BYTES


# dsv32-dsa-decode.climb: dsv3-mla-decode's sixteen sequences; 64 index heads
# over a paged index-key cache of 128-wide keys, the 2048 largest scores a
# sequence, their latent rows gathered from row-major pools of 640-wide rows
def _dsa_cell():
    import json

    from tenzing_tpu.models.latent_attention import LatentDecodeArgs
    from tenzing_tpu.models.latent_attention_reference import yarn_scale
    from tenzing_tpu.models.sparse_attention import SparseDecodeArgs

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmarks", "configs",
                           "dsv32-dsa-decode.json")) as f:
        config = json.load(f)
    shapes = config["shapes"]
    return SparseDecodeArgs(
        LatentDecodeArgs(lens=tuple(sorted(shapes["lens"])),
                         scale=yarn_scale(), page=shapes["page_tokens"],
                         groups=shapes["groups"]),
        index_heads=config["index_n_heads"],
        index_dim=config["index_head_dim"], topk=shapes["index_topk"])


@pytest.mark.parametrize("group", [0, 1])
def test_sparse_decode_kernels(one_chip, group):
    """The sparse cell's two kernels at its shapes, its shorter group and
    its longer: ``dsa_index`` on the paged walk (a grid of 51 and of 233
    steps, K tiles of ``(128, 2048)``, a ``(1, 1, 2048)`` block of the one
    row of scores a sequence, aliased in place) and ``mla_decode_rows`` over
    the group's gathered rows (a step a sequence: its ``(2048, 640)`` rows
    and its open page as blocks, the positions whole on the scalar core,
    the open page's slots moved as 32-bit words).  Neither moves a pool."""
    from tenzing_tpu.models.sparse_attention import dsa_plan
    from tenzing_tpu.ops.attention_pallas import (
        dsa_index_pallas,
        mla_decode_rows_pallas,
    )

    args = _dsa_cell()
    a = args.latent
    grp, _ = dsa_plan(args)[group]
    assert grp.tiles == ((5, 5, 5, 6, 6, 7, 8, 9),
                         (11, 14, 17, 21, 27, 34, 45, 64))[group]
    assert args.picked == (2048,) * 16
    bf = jnp.bfloat16
    index = dsa_index_pallas.lower(
        _sds((a.batch, args.index_heads, args.index_dim), bf, one_chip),
        _sds((a.batch, args.index_heads), jnp.float32, one_chip),
        _sds((a.pool_pages, args.index_dim, a.page), bf, one_chip),
        _sds((a.batch, args.index_dim, a.page), bf, one_chip),
        _sds((a.batch,), jnp.int32, one_chip),
        _sds((a.batch, a.max_pages), jnp.int32, one_chip),
        _sds((a.batch, 1, a.max_pages * a.page), jnp.float32, one_chip),
        lead0=grp.lead0, tiles=grp.tiles, interpret=False).compile()
    read = mla_decode_rows_pallas.lower(
        _sds((a.batch, a.heads, a.width), bf, one_chip),
        _sds((grp.rows, args.topk, args.row), bf, one_chip),
        _sds((a.batch, a.page, args.row), bf, one_chip),
        _sds((a.batch, args.topk), jnp.int32, one_chip),
        _sds((a.batch,), jnp.int32, one_chip),
        _sds((a.batch,), jnp.int32, one_chip),
        _sds((a.batch, a.heads, a.rank), bf, one_chip), a.scale,
        v_dim=a.rank, lead0=grp.lead0, interpret=False).compile()
    for compiled, name in ((index, "dsa_index"), (read, "mla_decode_rows")):
        _assert_kernel(compiled)
        text = compiled.as_text()
        assert name in text  # the name the device trace shows
        big = (f"bf16[{a.pool_pages},", f"bf16[{grp.rows},{args.topk},",
               f"bf16[{a.batch},{args.index_dim},{a.page}]")
        moved = [l for l in text.splitlines() if " copy(" in l
                 and l.split(" = ")[1].startswith(big)]
        assert not moved  # no pool, no rows (what is not donated here, is)
        assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20


def test_one_row_of_the_pool_is_no_dma(one_chip):
    """Why the read's rows come by XLA's gather (PERF.md section 6, PR 41):
    Mosaic takes no slice of a tiled HBM operand finer than its tile, and
    the latent pool arrives tiled ``(8,128)(2,1)``, so a DMA of one
    token's row does not compile, and the finest, 8 rows, does.  When this
    fails, a kernel can fetch its selected rows itself (ROADMAP S7c)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def fetch(rows: int):
        def kernel(at, pool, o_ref, got, sem):
            first = pl.multiple_of(at[0] // rows * rows, rows)
            copy = pltpu.make_async_copy(pool.at[0, pl.ds(first, rows), :],
                                         got, sem)
            copy.start()
            copy.wait()
            o_ref[...] = got[...]

        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(1,),
                in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=pl.BlockSpec((rows, 640), lambda i, at: (0, 0)),
                scratch_shapes=[pltpu.VMEM((rows, 640), jnp.bfloat16),
                                pltpu.SemaphoreType.DMA]),
            out_shape=jax.ShapeDtypeStruct((rows, 640), jnp.bfloat16))

    operands = (_sds((1,), jnp.int32, one_chip),
                _sds((4, 2048, 640), jnp.bfloat16, one_chip))
    _assert_kernel(jax.jit(fetch(8)).lower(*operands).compile())
    with pytest.raises(Exception, match="aligned to tiling"):
        jax.jit(fetch(1)).lower(*operands).compile()


def test_sparse_decode_loop_moves_no_pool(one_chip, monkeypatch):
    """The repeat-n program of ``dsv32-dsa-decode.climb``'s start point as
    the TPU compiler leaves it: 8 ``dsa_index`` and 8 ``mla_decode_rows``;
    inside the ``while`` body nothing touches a sealed pool of either cache
    but the index kernels and the gathers (a row-major pool is gathered
    from as it lies: no copy of it), one gather a read (8) and no
    transposed tile, an open pool only takes its 16 one-row or one-column
    updates in place a layer, and the program's temporaries stay under one
    latent pool (0.70 GB)."""
    from benchmarks.builders.dsa_decode import start_prefer
    from tenzing_tpu.core.platform import Platform
    from tenzing_tpu.models.sparse_attention import buffer_shapes, dsa_graph
    from tenzing_tpu.obs.attrib.hlo import loop_ops_of_shape
    from tenzing_tpu.runtime.executor import TraceExecutor
    from tenzing_tpu.solve.local import drive, phase_policy

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    args = _dsa_cell()
    a = args.latent
    tags = [f"L{i}" for i in range(4)]
    bufs = {name: _sds(shape, jnp.dtype(dtype), one_chip)
            for name, (shape, dtype) in buffer_shapes(args, tags).items()}
    graph = dsa_graph(args, tags)
    plat = Platform.make_n_lanes(2)
    seq, _ = drive(graph, plat, phase_policy(
        plat, [t + "." for t in tags], start_prefer))
    ex = TraceExecutor(plat, bufs)
    compiled = jax.jit(ex._stepped_fn(seq.vector())).lower(
        bufs, _sds((), jnp.int32, one_chip)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 16
    assert text.count("mla_decode_rows") >= 8 and "dsa_index" in text
    rows = loop_ops_of_shape(
        text, f"bf16[{a.batch // a.groups * args.topk},{args.row}]")
    assert sum(o.opcode == "fusion" for o in rows) == 8  # the gathers
    assert not loop_ops_of_shape(
        text, f"bf16[{a.batch},{a.width},{args.topk}]")  # no column tile
    for sealed in (f"bf16[{a.pool_pages},{a.page},{args.row}]",
                   f"bf16[{a.pool_pages},{args.index_dim},{a.page}]"):
        assert not loop_ops_of_shape(text, sealed)  # produced by nothing
    opened = loop_ops_of_shape(text, f"bf16[{a.batch},{a.page},{args.row}]")
    assert sum(o.opcode == "dynamic-update-slice"
               for o in opened) == 4 * a.batch
    # and at most a move a layer between the compiler's memory spaces
    moves = [o.opcode for o in opened if o.opcode != "dynamic-update-slice"]
    assert set(moves) <= {"copy-start", "copy-done", "custom-call"}
    assert moves.count("copy-start") <= 4
    keys = [o for o in loop_ops_of_shape(
        text, f"bf16[{a.batch},{args.index_dim},{a.page}]")
        if o.opcode == "dynamic-update-slice"]
    assert len(keys) == 4 * a.batch
    assert compiled.memory_analysis().temp_size_in_bytes < 700 << 20


def test_spmv_ell(one_chip):
    """spmv: the 150000-row local ELL slab (width 26, make_spmv_buffers seed
    0; transposed ``(w, m)`` as the buffers hold it) against the largest x
    the kernel is offered for (``supports``: 4096 —
    at the default m both x vectors are larger and the menu prunes it)."""
    from tenzing_tpu.ops.spmv_pallas import (
        LANES,
        MAX_X_BLOCKS,
        ell_spmv_pallas,
        supports,
    )

    n = LANES * MAX_X_BLOCKS
    assert supports(n) and not supports(n + 1)
    _assert_kernel(ell_spmv_pallas.lower(
        _sds((26, 150_000), jnp.float32, one_chip),
        _sds((26, 150_000), jnp.int32, one_chip),
        _sds((n,), jnp.float32, one_chip), interpret=False).compile())


# -- the SpMV product and its whole naive program ------------------------------
#
# What these guard: for ``sum(vals * x[cols], axis=1)`` on a row-major
# ``(m, w)`` slab XLA flattens the slab to ``[m*w]`` for the gather and
# reshapes back, two physical relayouts (w = 23..26 is no lane multiple) whose
# code emission takes 2.4 s of host time at 16 384 rows and 72 s at 150 000
# (PERF.md, PR 26).  The column sweep has neither, and its loop body is
# compiled once whatever m is.


def _assert_swept(compiled, slabs):
    """No flat ``[r*w]`` array for any ``(w, r)`` in ``slabs`` (slab width,
    rows swept), and every gather of r entries of x sits in the body of a
    ``while`` (the sweep over the slab's rows)."""
    text = compiled.as_text()
    assert " while(" in text
    for w, r in slabs:
        assert f"[{r * w}]" not in text, f"a flat [{r}*{w}] array"
        gathers = [ln for ln in text.splitlines() if " gather(" in ln
                   and f"f32[{r}]" in ln.split(" gather(")[0]]
        assert gathers
        assert all("/while/body/" in ln for ln in gathers)


def test_spmv_sweep_150k(one_chip):
    """The bare ``SpMVOp`` at the source's own size, m = 150 000, w = 26:
    compiles inside 10 s here (0.7 s read; the row-major form: 72 s on the
    chip's host, PERF.md PR 24 — a coarse guard with 15x of room)."""
    import time

    from tenzing_tpu.models.spmv import SpMVOp

    m, w = 150_000, 26
    op = SpMVOp("k", "x", "y", "vals", "cols", "rows")
    t0 = time.perf_counter()
    compiled = jax.jit(lambda vals, cols, rows, x: op.apply(
        {"vals": vals, "cols": cols, "rows": rows, "x": x}, None)["y"]).lower(
        _sds((w, m), jnp.float32, one_chip), _sds((w, m), jnp.int32, one_chip),
        _sds((m,), jnp.int32, one_chip),
        _sds((m,), jnp.float32, one_chip)).compile()
    secs = time.perf_counter() - t0
    _assert_swept(compiled, [(w, m)])
    assert secs < 10.0, f"the 150000-row product took {secs:.1f} s to compile"


def test_whole_spmv16k_program(topo, one_chip):
    """The naive schedule of the benchmark's ``spmv16k`` configuration
    (m = 16 384, nnz = 10 m, band m/4, host-staged x exchange, kernel menu):
    the one-shot and the repeat-n program compile with ``host_x`` in pinned
    host memory, sweep both products over their row ranges and hold no flat
    slab."""
    from jax.sharding import SingleDeviceSharding

    from tenzing_tpu.bench.driver import naive_schedule
    from tenzing_tpu.core.graph import Graph
    from tenzing_tpu.core.platform import Platform
    from tenzing_tpu.models.spmv import (
        SpMVCompound,
        make_spmv_buffers,
        spmv_host_buffer_names,
    )

    m = 16_384
    np_bufs, _ = make_spmv_buffers(m=m, nnz_per_row=10, bw=m // 4, seed=0)
    n_rem = int(np_bufs["x_remote"].shape[0])
    slabs = [(np_bufs[f"A_{h}_vals"].shape[0], np_bufs[f"A_{h}_rows"].shape[0])
             for h in ("loc", "rem")]
    assert all(np_bufs[k].shape[1] == m for k in ("A_loc_vals", "A_rem_cols"))
    assert all(r < m for _, r in slabs)  # a band: a quarter of each half empty
    host_names = set(spmv_host_buffer_names(n_rem))
    host = SingleDeviceSharding(topo.devices[0], memory_kind="pinned_host")
    bufs = {k: _sds(v.shape, v.dtype, host if k in host_names else one_chip)
            for k, v in np_bufs.items()}

    def mk():
        return SpMVCompound(impl_choice=True, exchange="host",
                            x_sizes={"x_local": m, "x_remote": n_rem})

    graph = Graph()
    graph.start_then(mk())
    graph.then_finish(mk())
    seq = naive_schedule("spmv", graph, m)
    for compiled in _both_programs(Platform.make_n_lanes(2), seq, bufs,
                                   host_names, one_chip):
        _assert_swept(compiled, slabs)
        assert compiled.memory_analysis().host_output_size_in_bytes > 0


# -- whole halo schedule programs ---------------------------------------------


def _pipeline_shapes(args):
    """name -> (shape, is_host) of models/halo_pipeline.make_pipeline_buffers
    without allocating the grid."""
    from tenzing_tpu.models.halo_pipeline import _flat_rows, _padded_shape

    out = {"U": (_padded_shape(args.local_shape(), 4), False)}
    for d in DIRECTIONS:
        _, sizes = _face_slices(args, d, "pack")
        flat = (_flat_rows(sizes), 128)
        for prefix in ("buf", "host", "recv"):
            out[f"{prefix}_{dir_name(d)}"] = (flat, prefix == "host")
    return out


def test_pipeline_shapes_match_the_builder():
    """The shape table the program compiles use is the builder's, checked
    where allocating is cheap."""
    from tenzing_tpu.models.halo_pipeline import (
        host_buffer_names,
        make_pipeline_buffers,
    )

    small = HaloArgs(nq=3, lx=16, ly=16, lz=16, radius=3)
    bufs, _ = make_pipeline_buffers(small, with_expected=False)
    want = _pipeline_shapes(small)
    assert {k: v.shape for k, v in bufs.items()} == \
        {k: s for k, (s, _) in want.items()}
    assert sorted(k for k, (_, h) in want.items() if h) == \
        sorted(host_buffer_names())


def _halo_schedule(which):
    from tenzing_tpu.bench.driver import halo_alias_prefer
    from tenzing_tpu.core.platform import Platform
    from tenzing_tpu.models.halo_pipeline import (
        HALO_PHASES,
        build_graph,
        naive_order,
    )
    from tenzing_tpu.solve.local import drive, phase_policy

    if which == "naive":
        plat = Platform.make_n_lanes(1)
        return plat, naive_order(FLAGSHIP, plat)
    # greedy-alias-6l, as bench/driver.py builds it: all-rdma transfers,
    # aliased Pallas unpacks, the z faces through the window pair, on the
    # kernel + engine choice graph
    plat = Platform.make_n_lanes(6)
    g = build_graph(FLAGSHIP, impl_choice=True, xfer_choice=True)
    seq, _ = drive(g, plat, phase_policy(plat, HALO_PHASES,
                                         halo_alias_prefer))
    return plat, seq


@pytest.mark.parametrize("which,n_kernels", [("naive", 0), ("alias", 20)])
def test_whole_halo_program(topo, one_chip, on_chip_kernels, which,
                            n_kernels):
    """One whole schedule at 512^3: the single-shot program and the repeat-n
    benchmark program compile, keep their kernels (alias: 6 aliased unpacks +
    6 rdma posts + 6 rdma waits + the z faces' 2 window packs, ISSUE 48) and
    fit the chip's memory."""
    from jax.sharding import SingleDeviceSharding

    host = SingleDeviceSharding(topo.devices[0], memory_kind="pinned_host")
    shapes = _pipeline_shapes(FLAGSHIP)
    bufs = {k: _sds(s, jnp.float32, host if is_host else one_chip)
            for k, (s, is_host) in shapes.items()}
    host_names = {k for k, (_, is_host) in shapes.items() if is_host}
    plat, seq = _halo_schedule(which)
    for compiled in _both_programs(plat, seq, bufs, host_names, one_chip):
        assert compiled.as_text().count("tpu_custom_call") == n_kernels
        m = compiled.memory_analysis()
        assert (m.argument_size_in_bytes + m.output_size_in_bytes
                + m.temp_size_in_bytes) < HBM_BYTES
        assert m.host_output_size_in_bytes > 0  # the staging set is host's


Z_FACES = [d for d in DIRECTIONS if d[2] != 0]


@pytest.mark.parametrize("d", Z_FACES, ids=[dir_name(d) for d in Z_FACES])
def test_window_pair_on_the_padded_grid(one_chip, on_chip_kernels, d):
    """ISSUE 48: the one-chip menu's ``pack_<d>.window`` and
    ``unpack_<d>.window`` on the flagship's tile-padded ``(3, 518, 520,
    640)``.  Mosaic takes both window kernels there as they stand (a block
    of ``(3, ., 520, 128)``: the whole y axis, tile column 0 or 4 of 5).
    The face between kernel and staging buffer is ``(3, 512, 3, 512)`` at 4
    sublanes for its 3, a reshape of the ``(18432, 128)`` buffer: nothing
    of the padded ``f32[3,512,512,3]`` is made on either side."""
    from types import SimpleNamespace

    from tenzing_tpu.ops.halo_pallas import PackWindow, UnpackWindow

    name = dir_name(d)
    ctx = lambda z: SimpleNamespace(tok_index_zero=z)
    flat = _sds((18432, 128), jnp.float32, one_chip)
    zero = _sds((), jnp.int32, one_chip)
    packed = jax.jit(lambda u, z: PackWindow(FLAGSHIP, d).apply(
        {"U": u}, ctx(z))).lower(_grid(one_chip), zero).compile()
    unpacked = jax.jit(
        lambda u, r, z: UnpackWindow(FLAGSHIP, d).apply(
            {"U": u, f"recv_{name}": r}, ctx(z)),
        donate_argnums=0).lower(_grid(one_chip), flat, zero).compile()
    for compiled in (packed, unpacked):
        _assert_kernel(compiled)
        text = compiled.as_text()
        assert "f32[3,512,3,512]{3,2,1,0:T(4,128)" in text
        assert "f32[3,512,512,3]" not in text
    assert "f32[18432,128]" in packed.as_text()
    # in place: the grid is the only large buffer the unpack's program holds
    assert unpacked.memory_analysis().temp_size_in_bytes < 64 * 2**20


def test_start_point_loop_owns_its_z_faces(topo, one_chip, on_chip_kernels):
    """ISSUE 48, step 0: the repeat-n loop of the climb's start point at
    512^3, by the executor's scopes.  Each z pack is one
    ``halo_window_pack`` and one reshape of its own, each z unpack one
    reshape and one ``halo_window_unpack`` aliased on the grid, every other
    instruction of theirs a scalar (the index tie, the join).  Nothing in
    the body but the six aliased unpack kernels produces a grid; no
    instruction of it is a ``copy`` of one, an add onto one, or makes a
    z-minor ``(.., 512, 3)`` face (the parent's body made four, 403 MB
    each)."""
    import re

    from jax.sharding import SingleDeviceSharding

    from tenzing_tpu.obs.attrib.hlo import loop_ops_by_scope
    from tenzing_tpu.runtime.executor import TraceExecutor

    host = SingleDeviceSharding(topo.devices[0], memory_kind="pinned_host")
    shapes = _pipeline_shapes(FLAGSHIP)
    bufs = {k: _sds(s, jnp.float32, host if is_host else one_chip)
            for k, (s, is_host) in shapes.items()}
    host_names = {k for k, (_, is_host) in shapes.items() if is_host}
    plat, seq = _halo_schedule("alias")
    *_, compiled = _both_programs(plat, seq, bufs, host_names, one_chip)
    ops = loop_ops_by_scope(compiled.as_text())
    large = [o for o in ops if o.bytes >= 2**20]
    for d in Z_FACES:
        n = dir_name(d)
        assert [(o.opcode, o.name.split(".")[0]) for o in large
                if o.vertex == f"pack_{n}.window"] == [
            ("custom-call", "halo_window_pack"), ("reshape", "reshape")]
        assert [(o.opcode, o.name.split(".")[0]) for o in large
                if o.vertex == f"unpack_{n}.window"] == [
            ("reshape", "reshape"), ("custom-call", "halo_window_unpack")]
    grids = [o for o in ops if "f32[3,518,520,640]" in o.result]
    assert [o.opcode for o in grids] == ["custom-call"] * 6
    assert sorted(o.vertex for o in grids) == sorted(
        op.name() for op in seq.vector() if op.name().startswith("unpack_"))
    assert not [o for o in ops if re.search(r",512,3\]", o.result)]


# -- the mesh exchange on four chips ------------------------------------------


def _mesh_halo(topo, cells):
    """``(executor, shapes, graph, platform, args)`` of models/halo.py on a
    2x2x1 mesh of the four described chips, ``cells``^3 a shard."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from tenzing_tpu.core.graph import Graph
    from tenzing_tpu.core.platform import Platform
    from tenzing_tpu.models.halo import add_to_graph
    from tenzing_tpu.runtime.executor import TraceExecutor

    args = HaloArgs(nq=3, lx=cells, ly=cells, lz=cells, radius=3)
    mx, my, mz = 2, 2, 1
    mesh = Mesh(np.array(topo.devices).reshape(mx, my, mz), ("x", "y", "z"))
    spec = P(None, "x", "y", "z")
    sharded = NamedSharding(mesh, spec)

    def tiled(local):
        return _sds((local[0], mx * local[1], my * local[2], mz * local[3]),
                    jnp.float32, sharded)

    bufs = {"U": tiled(args.local_shape())}
    for d in DIRECTIONS:
        _, sizes = _face_slices(args, d, "pack")
        bufs[f"buf_{dir_name(d)}"] = tiled(sizes)
        bufs[f"recv_{dir_name(d)}"] = tiled(sizes)
    plat = Platform.make_n_lanes(2, mesh=mesh, specs={k: spec for k in bufs})
    graph = add_to_graph(Graph(), args, xfer_choice=True)
    return TraceExecutor(plat, bufs), bufs, graph, plat, args


@pytest.mark.parametrize("engine,marker", [
    ("xla", "collective-permute"),
    ("rdma", "tpu_custom_call"),
])
def test_mesh_halo_exchange(topo, on_chip_kernels, engine, marker):
    """models/halo.py on a 2x2x1 mesh of the four described chips, 256^3
    cells per shard (what ``chip_smoke.py --chips 4`` runs): the XLA
    collective-permute engine and the remote-DMA engine with its barrier
    semaphore and collective_id."""
    from tenzing_tpu.models.halo import engine_overlap_order

    ex, bufs, graph, plat, _ = _mesh_halo(topo, 256)
    seq = engine_overlap_order(graph, plat, engine)
    assert sum(op.name().endswith("." + engine) for op in seq.vector()) == 6
    compiled = jax.jit(ex.program(seq)).lower(bufs).compile()
    assert marker in compiled.as_text()
    m = compiled.memory_analysis()  # bytes on each device
    assert (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes) < HBM_BYTES


MESH_CELL = HaloArgs(nq=3, lx=448, ly=448, lz=448, radius=3)
THIN = [d for d in DIRECTIONS if d[0] == 0]


# a thin face as the shell's own shape, and a z face turned as ``Unpack``
# hands it over since PR 47
UNPACK_FORMS = [(d, False) for d in THIN] + [
    (d, True) for d in THIN if d[2] != 0]


@pytest.mark.parametrize("d,turned", UNPACK_FORMS, ids=[
    dir_name(d) + ("-turned" if t else "") for d, t in UNPACK_FORMS])
def test_window_unpack_on_the_unpadded_grid(one_chip, d, turned):
    """``unpack_face_window`` on ``halo512-mesh4``'s shard, ``(3, 454, 454,
    454)`` as the cell allocates it: Mosaic takes the blocks that run past
    the grid's end (sublanes [448, 456), lanes [384, 512) of 454) and, for
    a z face that comes turned as ``(3, 448, 3, 448)``, the turn of a
    ``(128, 448)`` scratch on the XLU and the select under a lane mask.
    The turned face is the kernel's operand at 4 sublanes for its 3:
    nothing of the padded ``f32[3,448,448,3]{3,2,1,0}`` is made ..."""
    from tenzing_tpu.ops.halo_pallas import unpack_face_window

    starts, sizes = _face_slices(MESH_CELL, d, "unpack")
    if turned:
        sizes = (sizes[0], sizes[1], sizes[3], sizes[2])
    compiled = jax.jit(
        lambda u, f, z: unpack_face_window(u, f, tuple(starts), z,
                                           turned=turned)
    ).lower(_sds(MESH_CELL.local_shape(), jnp.float32, one_chip),
            _sds(sizes, jnp.float32, one_chip),
            _sds((), jnp.int32, one_chip)).compile()
    _assert_kernel(compiled)
    if turned:
        text = compiled.as_text()
        assert "f32[3,448,3,448]{3,2,1,0:T(4,128)" in text
        assert "f32[3,448,448,3]" not in text


@pytest.mark.parametrize("d", THIN, ids=[dir_name(d) for d in THIN])
def test_window_pack_on_the_unpadded_grid(one_chip, d):
    """``pack_face_window`` on the same shard: Mosaic takes the read-only
    blocks and, for a z face, the turn of each ``(448, 128)`` block on the
    XLU.  A z face leaves the kernel as ``(3, 448, 3, 448)``, 4 sublanes
    for its 3 and not 128 lanes, and reaches the builder's shape by a
    bitcast: nothing of the padded ``f32[3,448,448,3]{3,2,1,0}`` is made."""
    from tenzing_tpu.ops.halo_pallas import pack_face_window

    starts, sizes = _face_slices(MESH_CELL, d, "pack")
    compiled = jax.jit(
        lambda u, z: pack_face_window(u, tuple(starts), tuple(sizes), z)
    ).lower(_sds(MESH_CELL.local_shape(), jnp.float32, one_chip),
            _sds((), jnp.int32, one_chip)).compile()
    _assert_kernel(compiled)
    text = compiled.as_text()
    assert "f32[3,448,3,448]{3,2,1,0:T(4,128)" in text
    assert "f32[3,448,448,3]{3,2,1,0" not in text


@pytest.mark.parametrize("d", THIN, ids=[dir_name(d) for d in THIN])
def test_manual_window_dma_is_refused_on_the_unpadded_grid(one_chip, d):
    """... and refuses the one-chip twin's kernel there: every slice of a
    manual DMA window, a whole axis included, has to be a multiple of the
    tile, and Mosaic sees the buffer at its physical 456 x 512.  Why the
    mesh halo's kernel pipelines by ``BlockSpec`` (ops/halo_pallas.py)."""
    from tenzing_tpu.ops.halo_pallas import unpack_face_pallas_batched

    starts, sizes = _face_slices(MESH_CELL, d, "unpack")
    with pytest.raises(Exception, match="must be aligned to tiling"):
        jax.jit(
            lambda u, f: unpack_face_pallas_batched(u, f, tuple(starts))
        ).lower(_sds(MESH_CELL.local_shape(), jnp.float32, one_chip),
                _sds(sizes, jnp.float32, one_chip)).compile()


# the repeat-n programs at 448^3 as PR 47 leaves them, compiled for the same
# described chips: whole-grid ``copy`` operations in the ``while`` body, and
# temporaries a chip in bytes (its parent, 877d6fa, as PR 44 left them: naive
# 0 and 2_002_004_992, xla 0 and 2_002_940_416, rdma 0 and 2_585_990_144; the
# two padded z faces a collective-permute's result was relayouted to, 308 MB
# each, are gone; the remote DMA still delivers its face padded, and the 11 MB
# turned copies of the two beside it are the 193_536 bytes rdma is up: on the
# chip that program read 7.693 -> 7.635 ms an iteration, so it keeps the turned
# form too: PERF.md, PR 47)
LOOP_AT_PR47 = {"naive": (0, 1_385_507_840), "xla": (0, 1_385_540_096),
                "rdma": (0, 2_586_183_680)}
# a received z face as the builder shapes it, z minor: 3 -> 128 lanes, 308 MB
PADDED_Z_FACE = r"f32\[3,448,448,3\]\{3,"


def _mesh_halo_loop(topo, which):
    """``(compiled text, temporaries, args)`` of the repeat-n program of
    ``halo512-mesh4.mcts`` (448^3 a shard): naive, or every exchange on one
    engine posted before any is awaited."""
    from tenzing_tpu.bench.driver import naive_schedule
    from tenzing_tpu.models.halo import engine_overlap_order

    if ("mesh", which) not in _LOOP_TEXTS:
        ex, bufs, graph, plat, args = _mesh_halo(topo, 448)
        seq = (naive_schedule("halo_mesh", graph, None) if which == "naive"
               else engine_overlap_order(graph, plat, which))
        n = _sds((), jnp.int32, jax.sharding.NamedSharding(
            plat.mesh, jax.sharding.PartitionSpec()))
        compiled = jax.jit(ex._stepped_fn(seq.vector())).lower(
            bufs, n).compile()
        _LOOP_TEXTS["mesh", which] = (
            compiled.as_text(),
            compiled.memory_analysis().temp_size_in_bytes, args)
    return _LOOP_TEXTS["mesh", which]


@pytest.mark.parametrize("which", ["naive", "xla", "rdma"])
def test_mesh_halo_loop_adds_nothing_onto_the_grid(topo, on_chip_kernels,
                                                   which):
    """The repeat-n program of ``halo512-mesh4.mcts`` (448^3 a shard) as the
    TPU compiler leaves it.  No operation inside the ``while`` body adds
    onto a shard's whole grid (up to PR 28 the six packs' ordering tokens
    did, a pass over 1.27 GB each).  Since PR 32 the only
    ``dynamic-update-slice`` of the grid are the two x faces': the y and z
    shells are written by four ``halo_window_unpack`` kernels.  Since PR 44
    the only ``dynamic-slice`` of it are the two x faces' too: the y and z
    edges are read by four ``halo_window_pack`` kernels, and no fusion
    anywhere slices a y or z face out of the grid.  Each of the eight
    kernels, and each remote-DMA post, is handed its token's zero as an
    operand that is not a constant.  Every consumer of the grid that cares about its layout is
    now a Pallas one, so the body copies the whole grid in no schedule
    (the ``xla`` overlap program did twice, layout assignment's relayouts
    for its fused thin *packs*: PERF.md, PR 29 and PR 44).  Since PR 47 a
    z face enters its unpack turned, so where the exchange is a
    collective-permute no instruction of the body produces or consumes a
    received z face in the padded z-minor layout (the parent relayouted
    each to it for the kernel to read once); a remote DMA still delivers
    one.  The temporaries are not above what PR 47 read."""
    import re

    from tenzing_tpu.obs.attrib.hlo import computations, loop_ops_of_shape

    text, temp, args = _mesh_halo_loop(topo, which)
    grid = "f32[" + ",".join(str(e) for e in args.local_shape()) + "]"
    ops = loop_ops_of_shape(text, grid)
    assert sum(o.opcode == "dynamic-update-slice" for o in ops) == 2
    for d in THIN:  # no update or slice of a y- or z-face shape is left
        sizes = _face_slices(args, d, "unpack")[1]
        face = "f32[" + ",".join(str(e) for e in sizes) + "]"
        assert not re.search(
            r"dynamic-update-slice\([^)]*" + re.escape(face), text), face
        assert "dynamic_slice_sizes={" + ",".join(
            str(e) for e in sizes) + "}" not in text, face
    kernels = [o for o in ops if o.name.startswith("halo_window_unpack")]
    assert len(kernels) == 4 and all(o.opcode == "custom-call"
                                     for o in kernels)
    packs = sorted(set(re.findall(r"%(halo_window_pack[\w.\-]*) = ", text)))
    posts = sorted(set(re.findall(r"%(rdma_shift_post[\w.\-]*) = ", text)))
    assert len(packs) == 4 and len(posts) == (6 if which == "rdma" else 0)
    for name in [o.name for o in kernels] + packs + posts:
        first = re.search(re.escape(name) + r" = .*? custom-call\(%?([\w.\-]+)",
                          text).group(1)
        assert not first.startswith("constant"), (name, first)
    assert not [o for o in ops if "add" in o.fused or o.opcode == "add"], ops
    comps = computations(text)
    padded = []
    for body in re.findall(r"\swhile\(.*\bbody=%?([\w.\-]+)", text):
        for line in comps[body]:
            called = re.search(r"calls=%?([\w.\-]+)", line)
            padded += [ln for ln in [line] + (
                comps[called.group(1)] if called else [])
                if re.search(PADDED_Z_FACE, ln)]
    assert bool(padded) is (which == "rdma"), padded[:2]
    copies, temp_bytes = LOOP_AT_PR47[which]
    assert sum(o.opcode == "copy" for o in ops) <= copies
    assert temp <= temp_bytes


@pytest.mark.parametrize("which", ["naive", "xla", "rdma"])
def test_mesh_halo_loop_names_its_vertices(topo, on_chip_kernels, which):
    """ISSUE 38: who owns what in the mesh halo's repeat-n loop, read from
    the compiled text with no chip.  The window unpacks, the x faces'
    updates, the collective-permutes and the remote-DMA kernels name their
    vertices, and since PR 44 so do the y and z packs: each owns one
    ``halo_window_pack`` call (and, before a remote DMA, the z face's
    relayout to the padded layout that kernel wants).  Before a
    collective-permute an x pack still has no instruction of its own: XLA
    fuses its slice into the value tie of the exchange that reads it (the
    fusion goes to the exchange's ``tie`` and lists the pack's ``apply`` as
    mixed); a remote-DMA post has no value tie (PR 44: its token is a
    kernel operand), so there the two x slices are one fusion of the
    packs' own.  No y or z pack is mixed into anything.  The thin faces'
    relayout copies around a collective-permute inherit the exchange's
    name: a y face's there and back, a z face's there only since PR 47,
    because its way back is now the small relayout to the turned form the
    unpack's kernel takes (``(3, 448, 3, 448)``, 11 MB), a fusion of the
    unpack's own that lists the exchange as mixed; after a remote DMA,
    which delivers the face padded, that relayout is a ``copy`` under the
    await's name.  What XLA leaves
    nameless: the memory-space moves (``copy-start``/``-done``,
    ``slice-start``/``-done``, its ``ConcatBitcast``) and a few face
    copies; no copy of the whole grid is left in any schedule."""
    from tenzing_tpu.obs.attrib.hlo import UNSCOPED, loop_ops_by_scope

    text, _, args = _mesh_halo_loop(topo, which)
    ops = loop_ops_by_scope(text)
    engine = "xla" if which == "naive" else which
    names = [dir_name(d) for d in DIRECTIONS]
    thin = [dir_name(d) for d in THIN]
    owner = lambda o: f"{o.vertex}/{o.part}"
    grid_bytes = 4 * int(np.prod(args.local_shape()))

    windows = [o for o in ops if o.name.startswith("halo_window_unpack")]
    assert sorted(owner(o) for o in windows) == sorted(
        f"unpack_{n}/apply" for n in thin)
    updates = [o for o in ops if o.opcode == "dynamic-update-slice"]
    assert sorted(owner(o) for o in updates) == [
        "unpack_mx/apply", "unpack_px/apply"]
    # a y or z pack is one kernel call of its own ...
    kernels = [o for o in ops if o.name.startswith("halo_window_pack")]
    assert sorted(owner(o) for o in kernels) == sorted(
        f"pack_{n}/apply" for n in thin)
    own = [o for o in ops if o.vertex.startswith("pack_") and o.bytes > 8
           and o not in kernels]
    packed = [o for o in ops if o.opcode == "fusion" and o.bytes > 8
              and any(m.startswith("pack_") for m in o.mixed)]
    if engine == "xla":
        # ... and an x pack's slice sits in a fusion of its exchange's
        # value tie
        assert not own, own
        assert all(o.part == "tie" and o.vertex.startswith("exchange_")
                   and o.vertex.endswith("x.xla") for o in packed)
        assert sorted(m[len("pack_"):-len("/apply")] for o in packed
                      for m in o.mixed if m.startswith("pack_")) == [
                          "mx", "px"]
    else:
        # ... a remote-DMA post takes its token by index, so the x packs'
        # slices are a fusion of their own, and a z face is relayouted to
        # the padded layout the post's kernel wants
        assert sorted((o.opcode, o.vertex) for o in own) == [
            ("copy", "pack_mz"), ("copy", "pack_pz"), ("fusion", "pack_px")]
        assert [list(o.mixed) for o in packed] == [["pack_mx/apply"]]
        assert not [o for o in ops if o.vertex.startswith("exchange_")
                    and o.part == "tie" and o.bytes > 8]
    if engine == "xla":
        starts = [o for o in ops if o.opcode == "collective-permute-start"]
        assert sorted(owner(o) for o in starts) == sorted(
            f"exchange_{n}.xla/apply" for n in names)
    else:
        posts = [o for o in ops if o.name.startswith("rdma_shift_post")]
        waits = [o for o in ops if o.name.startswith("rdma_shift_wait")]
        assert sorted(owner(o) for o in posts) == sorted(
            f"exchange_{n}.rdma/apply" for n in names)
        assert sorted(owner(o) for o in waits) == sorted(
            f"await_{n}/apply" for n in names)
    copies = [o for o in ops if o.opcode == "copy" and o.bytes > 8]
    assert not [o for o in copies if o.bytes == grid_bytes]
    z_thin = [n for n in thin if n.endswith("z")]
    turns = [o for o in ops if o.bytes > 8 and o.opcode != "custom-call"
             and o.vertex in [f"{v}_{n}" for n in z_thin
                              for v in ("unpack", "await")]]
    if engine == "xla":  # the z faces' small relayout is the unpacks' own
        assert sorted((o.opcode, owner(o), o.mixed) for o in turns) == sorted(
            ("fusion", f"unpack_{n}/apply", (f"exchange_{n}.xla/apply",))
            for n in z_thin)
        assert all("f32[3,448,3,448]{3,2,1,0:T(4,128)" in o.result
                   for o in turns)
    else:
        assert sorted((o.opcode, owner(o)) for o in turns) == sorted(
            ("copy", f"await_{n}/apply") for n in z_thin)
        assert all("f32[3,448,448,3]{2,3,1,0:T(4,128)" in o.result
                   for o in turns)
    if which == "naive":  # a y face's relayout there and back, a z face's there
        assert sorted(o.vertex for o in copies) == sorted(
            [f"exchange_{n}.xla" for n in thin]
            + [f"exchange_{n}.xla" for n in thin if n.endswith("y")])
    nameless = {o.opcode for o in ops
                if o.vertex == UNSCOPED and o.bytes > 8}
    assert nameless <= {"copy", "copy-start", "copy-done", "slice-start",
                        "slice-done", "custom-call"}, nameless


# -- the expert layer on four chips --------------------------------------------


def test_moonlight_expert_layer(topo):
    """models/moe.py at Moonlight-16B-A3B's published widths, expert-parallel
    over the four described chips (what ``moonlight-ep4.mcts`` runs): the
    repeat-n program of the post-all-before-await-any schedule compiles,
    holds its eight all-to-alls, and leaves room for the three sets of
    buffers ``correct`` keeps beside it."""
    from jax.sharding import Mesh, NamedSharding

    from tenzing_tpu.core.graph import Graph
    from tenzing_tpu.core.platform import Platform
    from tenzing_tpu.models.moe import (
        PHASES,
        MoEArgs,
        MoELayer,
        buffer_layout,
    )
    from tenzing_tpu.runtime.executor import TraceExecutor
    from tenzing_tpu.solve.greedy import greedy_phase_order

    args = MoEArgs(n_ep=4, tokens_per_shard=8192, d_model=2048, d_ff=1408,
                   n_chunks=4, dtype="bfloat16", experts_per_shard=16,
                   top_k=6, gated=True, shared_ff=2816, capacity_factor=1.5,
                   scoring="sigmoid", routed_scale=2.446)
    assert args.fixed_capacity() == 288
    mesh = Mesh(np.array(topo.devices), ("ep",))
    layout = buffer_layout(args, 288)
    specs = {name: spec for name, (_, _, spec) in layout.items()}
    bufs = {name: _sds(shape, jnp.dtype(dt), NamedSharding(mesh, spec))
            for name, (shape, dt, spec) in layout.items()}
    plat = Platform.make_n_lanes(2, mesh=mesh, specs=specs)
    layer = MoELayer(args)
    g = Graph()
    g.start_then(layer)
    g.then_finish(layer)
    seq = greedy_phase_order(g, plat, PHASES)
    stepped = TraceExecutor(plat, bufs)._stepped_fn(seq.vector())
    compiled = jax.jit(stepped).lower(
        bufs, jax.ShapeDtypeStruct((), jnp.int32)).compile()
    assert compiled.as_text().count(" all-to-all(") == 8
    m = compiled.memory_analysis()  # bytes on each device
    assert 1.5e9 < m.argument_size_in_bytes < 1.8e9
    assert 3 * m.argument_size_in_bytes + m.temp_size_in_bytes < (
        HBM_BYTES - 3e9)


# -- the shortcut-connected decode step on four chips ---------------------------

SCMOE_CONFIG = "benchmarks/configs/longcat-lite-scmoe-decode.json"


def _scmoe_cell(topo):
    """``(args, mesh, shapes with shardings, specs, graph)`` of
    ``longcat-lite-scmoe-decode.climb`` on the four described chips, from
    the configuration's file (the ring exchanges' staging buffers too)."""
    import json
    from pathlib import Path

    from jax.sharding import Mesh, NamedSharding

    from tenzing_tpu.models import shortcut_moe
    from tenzing_tpu.models.latent_attention import LatentDecodeArgs
    from tenzing_tpu.models.moe import MoEArgs

    cfg = json.loads((Path(__file__).resolve().parent.parent
                      / SCMOE_CONFIG).read_text())
    s = cfg["shapes"]
    mla = LatentDecodeArgs(
        lens=tuple(s["lens"]), heads=cfg["num_attention_heads"],
        rank=cfg["kv_lora_rank"], rope=cfg["qk_rope_head_dim"],
        nope=cfg["qk_nope_head_dim"], v_dim=cfg["v_head_dim"],
        scale=0.10923, page=s["page_tokens"], groups=s["groups"],
        fold_pages=s["fold_pages"], dtype=s["dtype"])
    moe = MoEArgs(
        n_ep=s["ranks"], tokens_per_shard=len(s["lens"]),
        d_model=cfg["hidden_size"], d_ff=cfg["expert_ffn_hidden_size"],
        n_chunks=1, dtype=s["dtype"],
        experts_per_shard=s["experts_per_shard"], top_k=cfg["moe_topk"],
        gated=True, capacity_factor=s["capacity_factor"], scoring="softmax",
        routed_scale=cfg["routed_scaling_factor"],
        zero_experts=cfg["zero_expert_num"], gate_in_iteration=True)
    args = shortcut_moe.ScMoEArgs(
        mla=mla, moe=moe, blocks=cfg["layers"], q_rank=cfg["q_lora_rank"],
        ffn=cfg["ffn_hidden_size"])
    cap = moe.fixed_capacity()
    assert cap == 16
    mesh = Mesh(np.array(topo.devices), ("ep",))
    layout = {**shortcut_moe.data_layout(args, cap),
              **shortcut_moe.ring_staging_layout(args, cap)}
    specs = {name: spec for name, (_, _, spec) in layout.items()}
    bufs = {name: _sds(shape, jnp.dtype(dt), NamedSharding(mesh, spec))
            for name, (shape, dt, spec) in layout.items()}
    graph = shortcut_moe.scmoe_decode_graph(args, synth=True,
                                            synth_relax=True)
    return args, mesh, bufs, specs, graph


@pytest.mark.parametrize("which", ["start", "naive", "ring"])
def test_scmoe_decode_step_on_four_chips(topo, monkeypatch, which):
    """``models/shortcut_moe.py`` at LongCat-Flash-Lite's published widths
    over the four described chips (what ``longcat-lite-scmoe-decode.climb``
    runs): the repeat-n program of the start point (the shortcut discipline,
    every latent group on ``mla_decode``), of naive (written order, chains
    of ``mla_fold``) and of the start point on the ring exchanges compiles
    as ONE ``shard_map`` program that holds the paged Mosaic kernels, the
    XLA products and the exchange together, and leaves room for the three
    sets of buffers ``correct`` keeps beside it."""
    import sys
    from pathlib import Path

    from tenzing_tpu.core.platform import Platform
    from tenzing_tpu.models import shortcut_moe
    from tenzing_tpu.runtime.executor import TraceExecutor
    from tenzing_tpu.solve.local import drive, phase_policy

    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    from benchmarks.builders.scmoe_decode import NAIVE, START, prefer_of

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    args, mesh, bufs, specs, graph = _scmoe_cell(topo)
    lanes = 1 if which == "naive" else 2
    plat = Platform.make_n_lanes(lanes, mesh=mesh, specs=specs)
    order = shortcut_moe.WRITTEN if which == "naive" else \
        shortcut_moe.SHORTCUT
    prefer = {"start": START, "naive": NAIVE,
              "ring": (".ring.c1",) + START}[which]
    seq, _ = drive(graph, plat, phase_policy(
        plat, shortcut_moe.phases(args, order), prefer_of(prefer)))
    stepped = TraceExecutor(plat, bufs)._stepped_fn(seq.vector())
    compiled = jax.jit(stepped).lower(
        bufs, jax.ShapeDtypeStruct((), jnp.int32)).compile()
    text = compiled.as_text()
    exchanges = 2 * args.blocks
    if which == "ring":
        assert " all-to-all(" not in text
        assert text.count(" collective-permute-start(") == 3 * exchanges
    else:
        assert text.count(" all-to-all(") == exchanges
    kernels = text.count("tpu_custom_call")
    assert kernels >= 2 * args.blocks * args.mla.groups
    m = compiled.memory_analysis()  # bytes on each device
    assert 4.2e9 < m.argument_size_in_bytes < 5.0e9
    assert 3 * m.argument_size_in_bytes + m.temp_size_in_bytes < HBM_BYTES


# -- the mixers of a Mamba-2 hybrid's period on one chip ----------------------------

MIXERS_CONFIG = "benchmarks/configs/nemotron3-nano-mixers-prefill.json"


def _mixers_cell():
    """``(Mamba2Args, RingAttnArgs, pattern, phases)`` of
    ``nemotron3-nano-mixers-prefill.climb`` at the published widths, as its
    builder makes them."""
    import json

    from benchmarks.builders.mixers_prefill import step_args
    from benchmarks.harness.cell import load_module

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, MIXERS_CONFIG)) as f:
        config = json.load(f)
    ref = load_module("references", config["reference"])
    z = ref.sizes(config)
    return (*step_args(z), z["pattern"],
            [f"{t}." for _, t in ref.tags(config)])


def test_ssd_scan_kernel(one_chip):
    """``ssd_scan`` at the cell's shapes (16 384 packed tokens, twelve
    prompts, 64 heads of 64 on a 64 x 128 state, 8 groups, chunks of 128):
    Mosaic takes a grid step of one group's eight heads with a prompt
    boundary inside the chunk (masks from the prefetched ids, no
    immediate), the two transposes a step, the resident block of final
    states."""
    from tenzing_tpu.ops.ssd_pallas import ssd_chunk_scan

    m = _mixers_cell()[0]
    assert (m.tokens, m.prompts, m.chunks, m.boundary_chunks) == (
        16384, 12, 128, 8)
    compiled = ssd_chunk_scan.lower(
        _sds((m.tokens, m.conv_width), jnp.bfloat16, one_chip),
        _sds((m.tokens, m.heads), jnp.float32, one_chip),
        _sds((m.heads,), jnp.float32, one_chip),
        _sds((m.heads,), jnp.float32, one_chip),
        _sds((m.tokens,), jnp.int32, one_chip),
        _sds((m.prompts,), jnp.int32, one_chip),
        **m.dims, interpret=False).compile()
    _assert_kernel(compiled)
    assert "ssd_scan" in compiled.as_text()  # the name the trace shows
    # x, B and C are read where they lie: no slice of the convolved rows
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20


@pytest.mark.parametrize("which", ["start", "naive"])
def test_mixers_prefill_step_fits_the_chip(one_chip, monkeypatch, which):
    """The repeat-n program of the cell's start point (three ``ssd_scan``
    and a packed ``attn_fused`` a query block) and of its naive (the XLA
    chains, the ``attn_fold`` chains) at the published widths: both compile
    for the described chip, and three sets of the step's buffers (the
    run's, the probe's, a one-shot program's outputs) fit beside the loop's
    temporaries and beside the one-shot program's own."""
    import re

    from benchmarks.builders.mixers_prefill import unfused_prefer
    from tenzing_tpu.bench.workloads import attn_fused_prefer
    from tenzing_tpu.core.platform import Platform
    from tenzing_tpu.models import mixers_prefill
    from tenzing_tpu.runtime.executor import TraceExecutor
    from tenzing_tpu.solve.local import drive, phase_policy

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mamba, attn, pattern, phases = _mixers_cell()
    bufs = {name: _sds(shape, jnp.dtype(dtype), one_chip)
            for name, (shape, dtype) in mixers_prefill.buffer_shapes(
                mamba, attn, pattern).items()}
    graph = mixers_prefill.mixers_prefill_graph(mamba, attn, pattern)
    plat = Platform.make_n_lanes(2 if which == "start" else 1)
    seq, _ = drive(graph, plat, phase_policy(
        plat, phases,
        attn_fused_prefer if which == "start" else unfused_prefer))
    ex, n = TraceExecutor(plat, bufs), _sds((), jnp.int32, one_chip)
    compiled = jax.jit(ex._stepped_fn(seq.vector())).lower(bufs, n).compile()
    text = compiled.as_text()
    scans = len(re.findall(r"ssd_scan\.\d+ = ", text))
    if which == "start":
        blocks = mamba.tokens // attn.q_block
        assert scans == 3
        assert text.count("tpu_custom_call") == 3 + blocks
    else:
        assert scans == 0 and re.search(r"attn_fold\.\d+ = ", text)
    mem = compiled.memory_analysis()
    print(which, mem.argument_size_in_bytes, mem.temp_size_in_bytes)
    assert 3.5e9 < mem.argument_size_in_bytes < 4.0e9
    assert 3 * mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
    # the cell's one-shot program is that loop run once, handing back its
    # carry: the run's buffers, the probe's, its outputs, its temporaries
    once = jax.jit(ex._looped_fn(seq.vector())).lower(
        bufs, n).compile().memory_analysis()
    assert (2 * once.argument_size_in_bytes + once.output_size_in_bytes
            + once.temp_size_in_bytes) < HBM_BYTES


# -- the attention cell's programs, as they were -------------------------------------

#: sha256 (first 20 hex digits) of the compiled repeat-n programs of
#: ``trinity-attn32k.climb``'s start point and naive without what names a
#: source file or line (:func:`_bare_digest`), read from the commit before
#: ISSUE 50 and from the change alike: the attention's ``segments`` left a
#: one-prompt caller's program as it was to the byte.  A change that means
#: to move one of them reads the new digest off the failing assertion.
TRINITY_PROGRAMS = {
    "start": "06ede39a3e5ae5227213",
    "naive": "eb8ea28be98344f8a5cd",
}


def _bare_digest(text: str) -> str:
    """sha256 prefix of a compiled text without what names a source file,
    line or stack frame."""
    import hashlib
    import re

    bare = re.sub(r", metadata=\{[^}]*\}", "", text)
    bare = re.sub(r"^(FileNames|FunctionNames|FileLocations|StackFrames)"
                  r"\n|^\d+ (\"|\{).*\n", "", bare, flags=re.M)
    bare = re.sub(r",? ?stack_frame_id=\d+", "", bare)
    return hashlib.sha256(bare.encode()).hexdigest()[:20]


@pytest.mark.parametrize("which", list(TRINITY_PROGRAMS))
def test_trinity_s_programs_are_as_they_were(one_chip, monkeypatch, which):
    # a Mosaic kernel's serialized body carries its Python frames, and a
    # line that moved would read as another kernel: lowered with none
    prev = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", 0)
    _LOOP_TEXTS.pop(("attn", which), None)
    try:
        text = _attention_period_text(one_chip, monkeypatch, which)
    finally:
        jax.config.update("jax_traceback_in_locations_limit", prev)
        _LOOP_TEXTS.pop(("attn", which), None)
    assert _bare_digest(text) == TRINITY_PROGRAMS[which]


#: the same of ``dsv3-mla-decode.climb``'s two paged kernels at its shortest
#: group: ``models/latent_attention.py`` and ``models/sparse_attention.py``
#: build their plans from ``ops/attention_pallas.py``'s ``_Plan``, which
#: ISSUE 50 gave a field (``segs``, 0 for them), so the four decode cells
#: run code of a changed file; read from the commit before and from the
#: change alike.
DECODE_KERNELS = {
    "mla_decode": "5f3cb0d62013a5b333bb",
    "mla_fold": "3af7d34bcff93333f76b",
}


@pytest.mark.parametrize("form", list(DECODE_KERNELS))
def test_decode_kernels_are_as_they_were(one_chip, form):
    prev = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", 0)
    # another test's trace of the same call would hand back its frames
    jax.clear_caches()
    try:
        _, compiled = _latent_decode_compiled(one_chip, form, 0)
    finally:
        jax.config.update("jax_traceback_in_locations_limit", prev)
    assert _bare_digest(compiled.as_text()) == DECODE_KERNELS[form]
