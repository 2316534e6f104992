"""Every device operation of a schedule's program carries the name of the
vertex that made it (ISSUE 38): the executor's ``jax.named_scope``s
(obs/scopes.py) in the lowered module and the compiled text, the readers
that cut a compiled loop (obs/attrib/hlo.py ``loop_ops_by_scope``) and a
profile's device time (obs/attrib/xplane.py ``device_by_vertex``) by them,
and a compiled program's sizes on its ``executor.first_call``."""

import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax._src import monitoring

from tenzing_tpu.bench import workloads as W
from tenzing_tpu.bench.driver import DriverRequest
from tenzing_tpu.core.operation import unbound
from tenzing_tpu.core.platform import Platform
from tenzing_tpu.obs import scopes
from tenzing_tpu.obs.attrib import hlo, xplane
from tenzing_tpu.obs.metrics import MetricsRegistry, get_metrics, set_metrics
from tenzing_tpu.obs.tracer import Tracer, set_tracer
from tenzing_tpu.ops.comm_ops import AwaitTransfer, CommStart, MultiAwait
from tenzing_tpu.runtime.executor import TraceExecutor

DATA = Path(__file__).parent / "data"
TOYS = ("halo", "spmv", "moe", "attn", "mla_decode", "dsa_decode",
        "kda_decode")
OWN_TRACE = (CommStart, AwaitTransfer, MultiAwait)  # ops/comm_ops.py


def _is_sync(op) -> bool:
    return getattr(op, "is_sync", lambda: False)()


@pytest.fixture(scope="module")
def toys():
    """``{workload: (executor, graph, naive, lowered text, compiled text)}``
    of the seven smoke graphs, each built and compiled once."""
    built = {}

    def get(wl):
        if wl not in built:
            g, bufs, _, wargs = W.row_of(wl).build(
                DriverRequest(workload=wl, smoke=True))
            naive = W.naive_schedule(wl, g, wargs)
            ex = TraceExecutor(Platform.make_n_lanes(2), bufs)
            low = jax.jit(ex._stepped_fn(naive.vector())).lower(
                ex.init_bufs, jnp.int32(1))
            built[wl] = (ex, g, naive, low.as_text(debug_info=True),
                         low.compile().as_text())
        return built[wl]

    return get


def _vertex_scopes(text: str) -> set:
    """Every ``tz.<vertex>[/part]`` of a text, sync and fence left out."""
    found = set()
    for m in re.finditer(r"tz\.[^/\"\s)]+(?:/(?:tie|apply|join))?", text):
        if scopes.owner_of(m.group(0))[0] != scopes.EXECUTOR:
            found.add(m.group(0))
    return found


# -- the executor names what it traces ---------------------------------------

@pytest.mark.parametrize("wl", TOYS)
def test_every_vertex_is_scoped_with_its_parts(toys, wl):
    _, _, naive, lowered, compiled = toys(wl)
    seen = 0
    for op in naive.vector():
        if _is_sync(op):
            continue
        scope = scopes.vertex_scope(op.name())
        if isinstance(unbound(op), OWN_TRACE):
            # a trace of its own: the vertex's scope, no executor's apply
            assert scope in lowered and scope in compiled, op.name()
            seen += 1
        elif op.writes():
            for part in scopes.PARTS:
                assert f"{scope}/{part}" in lowered, (op.name(), part)
                assert f"{scope}/{part}" in compiled, (op.name(), part)
            seen += 1
        else:
            assert scope not in lowered  # start, finish: nothing emitted
    assert seen >= 4


@pytest.mark.parametrize("wl", TOYS)
def test_fence_and_a_value_tie_carry_their_scopes(toys, wl):
    _, _, naive, lowered, compiled = toys(wl)
    for text in (lowered, compiled):
        assert "tz.fence/reduce_sum" in text
    tied = [op for op in naive.vector() if not _is_sync(op) and op.reads()
            and not getattr(unbound(op), "INDEX_TIE", False)]
    assert tied
    assert any(f"{scopes.vertex_scope(op.name())}/tie/add" in lowered
               for op in tied)
    # the fence lies outside the loop, the vertices inside it
    fence = [o for o in hlo.loop_ops_by_scope(compiled)
             if o.part == scopes.FENCE]
    assert not fence


@pytest.mark.parametrize("wl", TOYS)
def test_sync_hooks_are_scoped_where_they_emit(toys, wl):
    _, _, naive, lowered, _ = toys(wl)
    kinds = {getattr(op, "KIND", "") for op in naive.vector() if _is_sync(op)}
    for kind in kinds & {"wait_event", "event_sync", "lane_sync",
                         "lane_wait"}:
        assert scopes.sync_scope(kind) in lowered, kind
    assert scopes.sync_scope("event_record") not in lowered  # emits nothing


@pytest.mark.parametrize("wl", TOYS)
def test_another_order_of_the_same_ops_has_the_same_scopes(toys, wl):
    from tenzing_tpu.solve.local import drive, phase_policy

    ex, graph, _, _, _ = toys(wl)
    plat = Platform.make_n_lanes(2)
    first, _ = drive(graph, plat, phase_policy(plat, ("",)))
    names = sorted(op.name() for op in first.vector() if not _is_sync(op))
    rank = {n: len(names) - i for i, n in enumerate(names)}
    second, _ = drive(graph, plat, phase_policy(
        plat, ("",), priority=lambda name: rank.get(name, 0)))
    assert sorted(op.name() for op in second.vector()
                  if not _is_sync(op)) == names
    texts = [jax.jit(ex._stepped_fn(seq.vector())).lower(
        ex.init_bufs, jnp.int32(1)).as_text(debug_info=True)
        for seq in (first, second)]
    if [o.name() for o in first.vector()] != [
            o.name() for o in second.vector()]:
        assert texts[0] != texts[1]
    assert _vertex_scopes(texts[0]) == _vertex_scopes(texts[1])
    assert len(_vertex_scopes(texts[0])) >= 8


@pytest.mark.parametrize("wl", TOYS)
def test_scopes_are_no_part_of_a_jaxpr(toys, wl):
    """Names are metadata: the jaxpr digests the kernel tests pin
    (tests/test_attn_window_gqa.py, tests/test_mla_decode.py,
    tests/test_fused.py) stand unedited because a jaxpr's text holds no
    name stack."""
    ex, _, naive, _, _ = toys(wl)
    jaxpr = jax.make_jaxpr(ex._stepped_fn(naive.vector()))(
        ex.init_bufs, jnp.int32(1))
    assert scopes.SCOPE not in str(jaxpr)


def test_a_fused_region_nests_its_members_under_its_own_scope(toys):
    """A fused region is one vertex: tie, the one kernel and join under the
    region's name, each member's name inside the kernel's body."""
    from tenzing_tpu.runtime.fused import FusedExecutor

    ex, _, naive, _, _ = toys("attn")
    plan = FusedExecutor(ex, min_tile_bytes=0).plan(naive)
    assert plan.regions
    fused = plan.fused_order
    region = next(op for op in fused.vector()
                  if getattr(unbound(op), "KIND", "") == "fused_region")
    text = jax.jit(ex._stepped_fn(fused.vector())).lower(
        ex.init_bufs, jnp.int32(1)).as_text(debug_info=True)
    scope = scopes.vertex_scope(region.name())
    for part in scopes.PARTS:
        assert f"{scope}/{part}" in text
    for member in unbound(region).members():
        inner = scopes.vertex_scope(member.name())
        assert re.search(re.escape(f"{scope}/apply/") + r"[^\"]*"
                         + re.escape(inner), text), member.name()
        assert scopes.owner_of(f"jit(f)/{scope}/apply/x/{inner}/mul") == (
            region.name(), "apply")


# -- the grammar ---------------------------------------------------------------

@pytest.mark.parametrize("op_name, owner", [
    ("jit(stepped)/while/body/tz.pack_px/apply/dynamic_slice",
     ("pack_px", "apply")),
    ("jit(stepped)/jit(main)/shmap_body/while/body/tz.L0.q1.fused/tie/add",
     ("L0.q1.fused", "tie")),
    ("jit(stepped)/while/body/tz.unpack_my/join/select_n",
     ("unpack_my", "join")),
    # a trace of the op's own (ops/comm_ops.py): the vertex's apply
    ("jit(stepped)/while/body/tz.await_x/slice", ("await_x", "apply")),
    ("jit(stepped)/while/body/tz.fetch_x", ("fetch_x", "apply")),
    # the outermost vertex owns what nests under it
    ("jit(f)/while/body/tz.region0/apply/tz.pack_px/apply/mul",
     ("region0", "apply")),
    ("jit(stepped)/tz.fence/reduce_sum", ("executor", "fence")),
    ("jit(stepped)/while/body/tz.sync.wait_event/add",
     ("executor", "sync.wait_event")),
    ("jit(stepped)/while/body/add", None),
    ("", None),
])
def test_owner_of(op_name, owner):
    assert scopes.owner_of(op_name) == owner


@pytest.mark.parametrize("name, scope", [
    ("pack_px", "tz.pack_px"),
    ("L0.q1.fused", "tz.L0.q1.fused"),
    ("a/b", "tz.a_b"),
    ('say "x"', "tz.say__x_"),
    ("fold+fold@lane:1", "tz.fold+fold@lane:1"),
])
def test_vertex_scope_is_safe_for_a_name_stack(name, scope):
    assert scopes.vertex_scope(name) == scope
    assert scopes.owner_of(f"jit(f)/{scope}/apply/mul")[0] == scope[3:]


@pytest.mark.parametrize("result, nbytes", [
    ("bf16[8,128]{1,0}", 2048),
    ("f32[3,454,454,454]{3,2,1,0:T(8,128)}", 4 * 3 * 454 ** 3),
    ("(f32[3,4]{1,0}, s32[], pred[7])", 48 + 4 + 7),
    ("c64[2]", 16),
    ("f8e4m3fn[16]", 16),
    ("s4[16]", 8),
    ("u32[2]{0:T(128)}", 8),
    ("token[]", 0),
    ("f32[]", 4),
])
def test_type_bytes(result, nbytes):
    assert hlo.type_bytes(result) == nbytes


# -- owners from compiled text ---------------------------------------------------

def test_loop_ops_by_scope_on_a_toy_program(toys):
    _, _, naive, _, compiled = toys("spmv")
    ops = hlo.loop_ops_by_scope(compiled)
    assert ops and all(o.opcode not in hlo._FREE for o in ops)
    vertices = {o.vertex for o in ops}
    named = {op.name() for op in naive.vector()
             if not _is_sync(op) and op.writes()}
    assert named <= vertices
    assert hlo.UNSCOPED in vertices  # the loop's counter at the least
    assert all(o.part in scopes.PARTS for o in ops
               if o.vertex not in (hlo.UNSCOPED, scopes.EXECUTOR))
    assert all(o.part == "" and not o.mixed for o in ops
               if o.vertex == hlo.UNSCOPED and o.opcode != "fusion")
    # the nested loop of the SpMV's row sweep is walked, not listed
    assert not [o for o in ops if o.opcode == "while"]
    assert [o for o in ops if o.opcode == "fusion" and o.mixed]
    by_name = hlo.scopes_of_text(compiled)
    assert all(scopes.owner_of(by_name[o.name]) is not None
               for o in ops if o.vertex != hlo.UNSCOPED
               and o.opcode != "fusion")


def test_loop_ops_by_scope_on_recorded_tpu_text():
    """An excerpt of the compiled text of ``halo512-mesh4.mcts``'s ``xla``
    overlap schedule as it ran on four v5e chips (448^3 a shard;
    ``op_scopes_on_chip.py --keep``, PR 38, call C; the loop's
    data-moving instructions and their fused computations, the kernels'
    payloads cut): the two pack slices and two value ties XLA fused into
    one operation go to its root's exchange and list the others, and the
    two relayout copies of the whole grid, the loop's largest operations,
    are nobody's."""
    text = (DATA / "op_scopes_tpu_excerpt.hlo.txt").read_text()
    assert len(text) < 50_000
    ops = {o.name: o for o in hlo.loop_ops_by_scope(text)}
    expected = json.loads((DATA / "op_scopes_tpu_excerpt.json").read_text())
    assert len(ops) == expected["n_ops"]
    for name, (vertex, part, mixed, nbytes) in expected["ops"].items():
        o = ops[name]
        assert (o.vertex, o.part, list(o.mixed), o.bytes) == (
            vertex, part, mixed, nbytes), name
    grid = 4 * 3 * 454 ** 3
    whole = [o for o in ops.values() if o.bytes == grid
             and o.opcode == "copy"]
    assert len(whole) == 2
    assert all(o.vertex == hlo.UNSCOPED and not o.mixed for o in whole)
    fused = ops["broadcast_add_fusion.14"]
    assert (fused.vertex, fused.part) == ("exchange_py.xla", "tie")
    assert fused.mixed == ("exchange_my.xla/tie", "pack_my/apply",
                           "pack_py/apply")
    # the window kernels name their unpack vertices
    windows = [o for o in ops.values()
               if o.name.startswith("halo_window_unpack")]
    assert sorted(o.vertex for o in windows) == [
        "unpack_my", "unpack_mz", "unpack_py", "unpack_pz"]


def test_an_instruction_that_spans_lines_keeps_its_owner():
    """A kernel's ``frontend_attributes`` put a newline before its
    ``metadata`` (the remote-DMA kernels under ``shard_map``)."""
    text = """HloModule m

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]{0}) parameter(0)
  %x = f32[8]{0} get-tuple-element(%p), index=1
  %rdma_shift_post.1 = f32[8]{0} custom-call(%x), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={
"mesh_axes":"[\\"x\\"]"
}}, metadata={op_name="jit(stepped)/shard_map/while/body/tz.exchange_px.rdma/rdma_shift_post/pallas_call"}, backend_config={}
  %copy.2 = f32[8]{0} copy(%rdma_shift_post.1)
  ROOT %t = (s32[], f32[8]{0}) tuple(%i, %copy.2)
}

%cond (p: (s32[], f32[8])) -> pred[] {
  %p.1 = (s32[], f32[8]{0}) parameter(0)
  ROOT %lt = pred[] compare(%i.1, %n), direction=LT
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %w = (s32[], f32[8]{0}) while(%init), condition=%cond, body=%body
}
"""
    ops = {o.name: o for o in hlo.loop_ops_by_scope(text)}
    post = ops["rdma_shift_post.1"]
    assert (post.vertex, post.part, post.bytes) == (
        "exchange_px.rdma", "apply", 32)
    assert ops["copy.2"].vertex == hlo.UNSCOPED
    assert set(ops) == {"rdma_shift_post.1", "copy.2", "lt"}
    assert hlo.scopes_of_text(text) == {
        "rdma_shift_post.1": "jit(stepped)/shard_map/while/body/"
        "tz.exchange_px.rdma/rdma_shift_post/pallas_call"}


# -- device time by vertex ---------------------------------------------------------

def _hand_made() -> dict:
    """Two vertices inside a nested ``while``, an unscoped copy, a sync add
    and the fence; three- and four-element events."""
    loop = "jit(stepped)/while/body/"
    return {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ["tz:bench.dispatch", 0, 1000], ["tz:executor.enqueue", 0, 50],
            ["tz:executor.fence_wait", 50, 1000]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [["jit_stepped", 90, 960]]},
            {"name": "XLA Ops", "events": [
                ["%while.1", 100, 900, "jit(stepped)/while"],
                ["%fusion.1", 110, 200, loop + "tz.a/apply/mul"],
                ["%fusion.2", 200, 230, loop + "tz.a/join/add"],
                ["%copy.3", 240, 300],
                ["%while.9", 300, 500, loop + "tz.b/apply/while"],
                ["%fusion.4", 320, 480, loop + "tz.b/apply/while/body/mul"],
                ["%fusion.5", 500, 520, loop + "tz.b/tie/add"],
                ["%add.6", 520, 530, loop + "tz.sync.wait_event/add"],
                ["%reduce.7", 910, 950, "jit(stepped)/tz.fence/reduce_sum"],
            ]}]}]}


def test_device_by_vertex_on_a_hand_made_trace():
    red = xplane.reduce_trace(_hand_made())
    by = red["device_by_vertex"]
    assert by["vertices"] == [
        ["b", {"apply": pytest.approx(200e-9), "tie": pytest.approx(20e-9)}],
        ["a", {"apply": pytest.approx(90e-9), "join": pytest.approx(30e-9)}]]
    assert dict(by["executor"]) == pytest.approx(
        {"fence": 40e-9, "join": 30e-9, "tie": 20e-9,
         "sync.wait_event": 10e-9})
    # the outer loop keeps only its self time; the inner one is b's
    assert dict(by["unscoped"]) == pytest.approx(
        {"while": (800 - 410) * 1e-9, "copy": 60e-9})
    assert by["apply_s"] + by["executor_s"] + by["unscoped_s"] == (
        pytest.approx(red["busy_s"]))
    text = xplane.render(red)
    assert "by the schedule's vertex" in text
    assert re.search(r"0\.0000\s+0\.0000\s+0\.0000\s+b\n", text)
    assert "unscoped copy" in text and "sync.wait_event" in text


def test_device_by_vertex_without_scopes_says_so():
    trace = _hand_made()
    for ev in trace["planes"][1]["lines"][1]["events"]:
        del ev[3:]
    red = xplane.reduce_trace(trace)
    by = red["device_by_vertex"]
    assert by["vertices"] == [] and by["executor"] == []
    assert by["unscoped_s"] == pytest.approx(red["busy_s"])
    assert "no tz. scope" in xplane.render(red)


@pytest.mark.parametrize("name", ["halo512_climb_v5e.json",
                                  "spmv16k_dfs_v5e.json"])
def test_three_element_traces_still_load(name):
    """The traces recorded before the scopes (PR 25) hold three-element
    events: they reduce as they did, every device second unscoped."""
    red = xplane.reduce_trace(json.loads((DATA / name).read_text()))
    by = red["device_by_vertex"]
    assert by["vertices"] == []
    assert sum(s for _, s in by["unscoped"]) == pytest.approx(
        by["unscoped_s"])
    assert by["unscoped_s"] > 0


def test_device_by_vertex_on_a_trace_recorded_on_the_chip():
    """The first operations of one dispatch of ``trinity-attn32k.climb``'s
    start point on a TPU v5e (op_scopes_on_chip.py ``--keep``): the fused
    kernels are their vertices' ``apply``."""
    trace = json.loads((DATA / "op_scopes_attn_start_v5e.json").read_text())
    red = xplane.reduce_trace(trace)
    by = red["device_by_vertex"]
    fused = [v for v, _ in by["vertices"] if v.endswith(".fused")]
    assert len(fused) == 16
    parts = dict(by["vertices"])
    assert all(parts[v]["apply"] > 1e-3 for v in fused)  # over a ms each
    assert by["apply_s"] > 0.9 * red["busy_s"]
    assert by["apply_s"] + by["executor_s"] + by["unscoped_s"] == (
        pytest.approx(red["busy_s"], rel=1e-6))


def test_events_take_their_scope_from_the_compiled_text(toys):
    """``--hlo``: an event with no scope of its own is named by the
    instruction it is called after."""
    _, _, _, _, compiled = toys("spmv")
    by_name = hlo.scopes_of_text(compiled)
    owned = {n for n, s in by_name.items() if scopes.owner_of(s)}
    assert owned >= {o.name for o in hlo.loop_ops_by_scope(compiled)
                     if o.vertex != hlo.UNSCOPED and o.opcode != "fusion"}
    name = sorted(owned)[0]
    assert xplane.instruction_name(f"%{name} = f32[] fusion(...)") == name
    assert xplane.instruction_name(name) == name
    assert xplane.op_kind("%copy.106 = f32[2]{0} copy(%x)") == "copy"


# -- the real program's timeline -----------------------------------------------------

def test_traced_timeline_cuts_one_dispatch_by_vertex(toys, monkeypatch):
    """One ``OpRecord`` a schedule position from the profile of the real
    repeat-n program: a vertex's tie + apply + join an iteration, a sync
    op 0, ready for ``analyze`` as a stepped timeline is."""
    from tenzing_tpu.obs.attrib import analyze, traced_timeline

    ex0, _, naive, _, _ = toys("spmv")
    ex = TraceExecutor(ex0.platform, ex0.init_bufs)
    loop = "jit(stepped)/while/body/"
    timed = [op for op in naive.vector() if not _is_sync(op)
             and op.writes()]
    events, at = [["%while.1", 0, 100_000, "jit(stepped)/while"]], 10
    for i, op in enumerate(timed):
        scope = loop + scopes.vertex_scope(op.name())
        for part, ns in (("tie", 100), ("apply", 1000 * (i + 1)),
                         ("join", 50)):
            events.append([f"%fusion.{len(events)}", at, at + ns,
                           f"{scope}/{part}/add"])
            at += ns
    seen = {}

    def fake_load(trace_dir, by_name=None):
        seen["scopes"] = by_name
        return {"planes": [{"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [["jit_stepped", 0, 100_000]]},
            {"name": "XLA Ops", "events": events}]}]}

    monkeypatch.setattr(xplane, "load_xplane", fake_load)
    monkeypatch.setattr(jax.profiler, "start_trace", lambda *a, **k: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    tl = traced_timeline(ex, naive, n=4)
    assert tl.source == "traced" and tl.repeats == 4
    assert [r.positions for r in tl.records] == [
        (p,) for p in range(len(naive.vector()))]
    by_name = {r.name: r for r in tl.records}
    for i, op in enumerate(timed):
        assert by_name[op.name()].dur_us == pytest.approx(
            (1000 * (i + 1) + 150) / 4 / 1e3)
    assert all(r.dur_us == 0 for r in tl.records if r.kind == "sync")
    # the names were the executable's: the compiled text's own
    assert any(scopes.owner_of(s) for s in seen["scopes"].values())
    at = analyze(naive.vector(), tl, measured_us=30.0)
    assert at.sum_of_parts_us == pytest.approx(
        sum(r.dur_us for r in tl.records))


def test_traced_timeline_refuses_a_profile_without_device_operations(toys):
    """The CPU backend's profile has no ``XLA Ops`` line: the stepped mode
    is the per-op clock there."""
    from tenzing_tpu.obs.attrib import traced_timeline

    ex0, _, naive, _, _ = toys("spmv")
    ex = TraceExecutor(ex0.platform, ex0.init_bufs)
    with pytest.raises(RuntimeError, match="stepped_timeline"):
        traced_timeline(ex, naive, n=2)


def test_compiled_n_is_the_program_that_ran(toys):
    ex0, _, naive, _, _ = toys("spmv")
    for build in ("precompile", "prepare_n"):
        ex = TraceExecutor(ex0.platform, ex0.init_bufs)
        if build == "precompile":
            ex.precompile(naive)
        else:
            ex.prepare_n(naive)(1)
        ops = hlo.loop_ops_by_scope(ex.compiled_n(naive).as_text())
        assert {o.vertex for o in ops} >= {"spmv_local.xla", "y_add"}


# -- a compiled program's sizes on its first call -------------------------------------

@pytest.fixture
def recording():
    prev_t = set_tracer(Tracer(enabled=True))
    prev_m = set_metrics(MetricsRegistry())
    try:
        yield
    finally:
        set_tracer(prev_t)
        set_metrics(prev_m)


def _first_calls():
    from tenzing_tpu.obs.tracer import get_tracer

    return [s for s in get_tracer().spans()
            if s.name == "executor.first_call"]


SIZES = ("temp_bytes", "argument_bytes", "output_bytes", "alias_bytes",
         "generated_code_bytes")


def test_precompile_sets_sizes_and_the_gauge(toys, recording):
    ex0, _, naive, _, _ = toys("spmv")
    ex = TraceExecutor(ex0.platform, ex0.init_bufs)
    assert ex.precompile(naive)
    (span,) = _first_calls()
    assert span.attrs["aot"] is True
    for attr in SIZES:
        assert isinstance(span.attrs[attr], int), attr
    assert span.attrs["temp_bytes"] > 0
    assert span.attrs["argument_bytes"] > 0
    gauge = get_metrics().gauge("executor.program_temp_bytes_max")
    assert gauge.value == span.attrs["temp_bytes"]
    # the gauge keeps the largest: a smaller program does not lower it
    ex._note_temp_bytes(1)
    assert gauge.value == span.attrs["temp_bytes"]
    ex._note_temp_bytes(2 * span.attrs["temp_bytes"])
    assert gauge.value == 2 * span.attrs["temp_bytes"]


def test_a_lazy_first_call_reads_its_sizes_without_a_second_compile(
        toys, recording):
    """``prepare_n``'s first call jits lazily; the ``Compiled`` it ran is
    found again where the call left it, not compiled anew."""
    ex0, _, naive, _, _ = toys("spmv")
    ex = TraceExecutor(ex0.platform, ex0.init_bufs)
    compiles = []
    listen = lambda event, secs, **kw: compiles.append(event)
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        ex.prepare_n(naive)(2)
    finally:
        monitoring.unregister_event_duration_listener(listen)
    assert compiles.count("/jax/core/compile/backend_compile_duration") == 1
    (span,) = _first_calls()
    assert "aot" not in span.attrs and span.attrs["temp_bytes"] > 0
    assert get_metrics().gauge(
        "executor.program_temp_bytes_max").value == span.attrs["temp_bytes"]


def test_a_one_shot_first_call_has_sizes_and_leaves_the_gauge(
        toys, recording):
    ex0, _, naive, _, _ = toys("spmv")
    ex = TraceExecutor(ex0.platform, ex0.init_bufs)
    ex.run(naive)
    (span,) = _first_calls()
    for attr in SIZES:
        assert attr in span.attrs
    assert get_metrics().gauge("executor.program_temp_bytes_max").value == 0


def test_sizes_that_cannot_be_read_fail_no_first_call(
        toys, recording, monkeypatch):
    from tenzing_tpu.runtime import executor as executor_mod

    def refuse(*args):
        raise ValueError("no executable to be found")

    monkeypatch.setattr(executor_mod, "_compiled_of", refuse)
    ex0, _, naive, _, _ = toys("spmv")
    ex = TraceExecutor(ex0.platform, ex0.init_bufs)
    ex.prepare_n(naive)(1)
    (span,) = _first_calls()
    assert span.attrs["sizes_error"].startswith("ValueError: no executable")
    assert "temp_bytes" not in span.attrs and ex.compile_count == 1


def test_the_benchmark_reads_the_gauge(recording):
    """``benchmarks/layer_metrics/program_temp_peak_gb.py``: ``None`` on a
    program that set no gauge (the parent), GB once one did."""
    from benchmarks.harness.cell import load_module

    reader = load_module("layer_metrics", "program_temp_peak_gb")
    assert reader.read({}) is None
    get_metrics().gauge("executor.program_temp_bytes_max").set(540_000_000)
    assert reader.read({}) == pytest.approx(0.54)
