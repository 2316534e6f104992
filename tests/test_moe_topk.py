"""The generalised expert layer (models/moe.py: several experts a shard,
top-k sigmoid selection, gated experts, a shared expert, fixed capacity)
at small widths on four virtual devices, against the plain reference
(models/moe_reference.py) and the benchmark's self-contained copy
(benchmarks/references/moe_topk_ep.py)."""

import dataclasses
import random
from pathlib import Path

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, NamedSharding  # noqa: E402

from tenzing_tpu.core.graph import Graph  # noqa: E402
from tenzing_tpu.core.platform import Platform  # noqa: E402
from tenzing_tpu.core.state import State  # noqa: E402
from tenzing_tpu.models import moe_reference  # noqa: E402
from tenzing_tpu.models.moe import (  # noqa: E402
    PHASES,
    MoEArgs,
    MoELayer,
    make_moe_buffers,
    mesh_moe_buffers,
    slot_tables,
)
from tenzing_tpu.obs.metrics import MetricsRegistry, set_metrics  # noqa: E402
from tenzing_tpu.runtime.executor import TraceExecutor  # noqa: E402
from tenzing_tpu.solve.greedy import greedy_phase_order  # noqa: E402
from tenzing_tpu.verify import ScheduleVerifier  # noqa: E402

pytestmark = pytest.mark.needs_shard_map

ROOT = Path(__file__).resolve().parent.parent
# 8 experts over 4 shards, top-2, one shared expert, 2 chunks
ARGS = MoEArgs(n_ep=4, tokens_per_shard=32, d_model=16, d_ff=24, n_chunks=2,
               experts_per_shard=2, top_k=2, gated=True, shared_ff=40,
               capacity_factor=2.5, scoring="sigmoid", routed_scale=2.446)


@pytest.fixture
def registry():
    """A fresh metrics registry for the test, the process's own restored."""
    reg = MetricsRegistry()
    prev = set_metrics(reg)
    yield reg
    set_metrics(prev)


def _graph(args):
    g = Graph()
    g.start_then(MoELayer(args))
    g.then_finish(MoELayer(args))
    return g


def _mesh():
    return Mesh(np.array(jax.devices()[:4]), ("ep",))


@pytest.fixture(scope="module")
def built():
    bufs, specs, want = make_moe_buffers(ARGS, seed=5)
    plat = Platform.make_n_lanes(2, mesh=_mesh(), specs=specs)
    ex = TraceExecutor(plat, {k: jnp.asarray(v) for k, v in bufs.items()})
    return bufs, plat, ex, want, _graph(ARGS)


def _drive(graph, plat, pick):
    st = State(graph)
    while not st.is_terminal():
        st = st.apply(pick(st.get_decisions(plat)))
    return st.sequence


def _schedules(graph, plat):
    rng = random.Random(11)
    return {
        "naive": _drive(graph, Platform.make_n_lanes(1), lambda ds: ds[0]),
        "phases": greedy_phase_order(graph, plat, PHASES),
        "random0": _drive(graph, plat, rng.choice),
        "random1": _drive(graph, plat, rng.choice),
    }


@pytest.mark.parametrize("which", ["naive", "phases", "random0", "random1"])
def test_schedule_matches_plain_reference(built, which):
    """Naive, the post-all-before-await-any order and two random schedules
    the verifier certifies give the reference's y."""
    _, plat, ex, want, graph = built
    order = _schedules(graph, plat)[which]
    assert ScheduleVerifier(graph)(order).ok
    out = ex.run(order)
    np.testing.assert_allclose(np.asarray(out["Y"]), want, rtol=2e-4,
                               atol=2e-5)


def test_phase_order_posts_every_dispatch_before_it_awaits_any(built):
    _, plat, _, _, graph = built
    names = [op.name() for op in greedy_phase_order(graph, plat, PHASES)]
    posts = [i for i, n in enumerate(names) if n.startswith("a2a_disp")]
    awaits = [i for i, n in enumerate(names) if n.startswith("await_disp")]
    assert len(posts) == len(awaits) == ARGS.n_chunks
    assert max(posts) < min(awaits)
    # the chains that cross no chip run while the dispatches are in flight
    free = [i for i, n in enumerate(names) if n.startswith(("gate", "shared"))]
    assert max(posts) < min(free) and max(free) < min(awaits)


def test_gate_and_shared_expert_depend_on_no_exchange():
    g = MoELayer(ARGS).graph()
    by_name = {v.name(): v for v in g.vertices()}

    def upstream(v, seen=None):
        seen = set() if seen is None else seen
        for p in g.preds(v):
            if p.name() not in seen:
                seen.add(p.name())
                upstream(p, seen)
        return seen

    for name in ("gate_0", "shared_1"):
        assert not any(n.startswith(("a2a", "await"))
                       for n in upstream(by_name[name]))
    assert {"gate_0", "shared_0", "await_comb_0"} <= upstream(
        by_name["combine_0"])


def test_shares_of_the_shards_add_up_to_the_uncut_reference(built):
    """What each shard's experts give, the shared expert counted once, is
    the whole layer (the model-configs guide's tie of share to model)."""
    bufs, _, _, want, _ = built
    f = lambda a: jnp.asarray(a)
    shared = (f(bufs["Ws1"]), f(bufs["Ws3"]), f(bufs["Ws2"]))
    bias = jnp.zeros((ARGS.n_experts,), jnp.float32)
    common = (f(bufs["X"]), f(bufs["Wg"]), bias, f(bufs["W1"]),
              f(bufs["W3"]), f(bufs["W2"]), shared, ARGS.top_k,
              ARGS.routed_scale)
    e_l = ARGS.experts_per_shard
    total = sum(
        moe_reference.moe_layer(
            *common, experts=range(s * e_l, (s + 1) * e_l),
            with_shared=(s == 0))
        for s in range(ARGS.n_ep))
    np.testing.assert_allclose(np.asarray(total), want, rtol=1e-5, atol=1e-6)


def test_slots_hold_every_selection_once():
    sel = np.array([[0, 3], [3, 1], [0, 1], [2, 3]], np.int32)
    args = dataclasses.replace(ARGS, n_ep=2, tokens_per_shard=4, n_chunks=1)
    tb = {k: np.asarray(v) for k, v in slot_tables(sel, args, cap=3).items()}
    # expert 3 (peer 1, second of its two): tokens 0, 1, 3 in token order
    assert tb["disp_idx_0"][0, 1, 3:6].tolist() == [0, 1, 3]
    assert (tb["slot_tk_0"] >= 0).sum() == sel.size
    flat_tok = tb["disp_idx_0"].reshape(-1)
    for t in range(4):
        for k in range(2):
            slot = tb["comb_idx_0"][t, k]
            assert flat_tok[slot] == t and slot // 3 == sel[t, k]


def test_a_token_over_capacity_raises_at_setup(registry):
    with pytest.raises(ValueError, match="beyond the capacity"):
        make_moe_buffers(dataclasses.replace(ARGS, capacity_factor=0.5),
                         seed=5)
    assert registry.counter("moe.dropped_slots").value > 0


def test_routing_counters(registry):
    make_moe_buffers(ARGS, seed=5)
    n_sel = ARGS.n_ep * ARGS.tokens_per_shard * ARGS.top_k
    assert registry.counter("moe.routed_slots").value == n_sel
    assert registry.counter("moe.dropped_slots").value == 0
    cap = ARGS.fixed_capacity()
    assert registry.counter("moe.capacity_slots").value == (
        ARGS.n_ep * ARGS.n_chunks * ARGS.n_experts * cap)
    assert 0 < registry.counter("moe.max_expert_load").value <= cap


def test_layer_made_on_the_mesh_equals_the_host_made_one(built):
    """``mesh_moe_buffers`` (tables negotiated shard by shard on the
    devices) builds the tables ``make_moe_buffers`` builds on the host."""
    from tenzing_tpu.obs.tracer import get_tracer

    bufs, plat, _, _, _ = built
    mesh = plat.mesh
    data = {k: jax.device_put(jnp.asarray(bufs[k]),
                              NamedSharding(mesh, plat.spec(k)))
            for k in ("X", "Wg", "W1", "W2", "W3", "Ws1", "Ws2", "Ws3")}
    tr = get_tracer()
    was = tr.enabled
    tr.enabled = True
    try:
        made, specs = mesh_moe_buffers(ARGS, mesh, data)
        assert any(s.name == "moe.route" for s in tr.spans())
    finally:
        tr.enabled = was
    assert set(made) == set(bufs)
    for name in bufs:
        assert made[name].shape == bufs[name].shape, name
        assert made[name].sharding.is_equivalent_to(
            NamedSharding(mesh, specs[name]), made[name].ndim), name
        if name.startswith(("disp_idx", "slot_tk", "comb_idx", "topk")):
            np.testing.assert_array_equal(np.asarray(made[name]), bufs[name])


# -- the benchmark's configuration, reference and builder at toy shapes -------


@pytest.fixture(scope="module")
def bench_config():
    import json

    from benchmarks.harness.cell import toy_shapes

    return toy_shapes(json.loads(
        (ROOT / "benchmarks" / "configs" / "moonlight-ep4.json").read_text()))


@pytest.fixture(scope="module")
def bench_ref():
    from benchmarks.harness.cell import load_module

    return load_module("references", "moe_topk_ep")


def test_repository_reference_and_benchmark_copy_agree(bench_config,
                                                       bench_ref):
    """The benchmark's self-contained reference (sharded, every expert over
    every token) and models/moe_reference.py (one array, a loop with a
    mask) give the same layer on the same data."""
    z = bench_ref.sizes(bench_config)
    data = {k: jnp.asarray(np.asarray(v))
            for k, v in bench_ref.make_data(bench_config, 7).items()}
    want = moe_reference.moe_layer(
        data["X"], data["Wg"], data["gate_bias"], data["W1"], data["W3"],
        data["W2"], (data["Ws1"], data["Ws3"], data["Ws2"]), z["top_k"],
        z["scale"])
    got = bench_ref.sound(bench_config, 7)[bench_ref.OUTPUT]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-6)


def test_data_follows_the_seed_and_lies_rank_by_rank(bench_config, bench_ref):
    a = bench_ref.make_data(bench_config, 7)
    b = bench_ref.make_data(bench_config, 7)
    c = bench_ref.make_data(bench_config, 8)
    assert np.array_equal(np.asarray(a["X"]), np.asarray(b["X"]))
    assert not np.array_equal(np.asarray(a["X"]), np.asarray(c["X"]))
    assert len({s.device for s in a["W1"].addressable_shards}) == 4
    assert a["W1"].addressable_shards[0].data.shape[0] == 2


def _bad(compared):
    return [c["name"] for c in compared if c["value"] > c["limit"]]


def test_sound_passes_and_the_low_precision_control_fails(bench_config,
                                                          bench_ref):
    assert _bad(bench_ref.check(bench_config, 7,
                                bench_ref.sound(bench_config, 7))) == []
    bad = _bad(bench_ref.check(bench_config, 7,
                               bench_ref.control(bench_config, 7)))
    assert "moe_y_rms_gap" in bad


def test_one_token_combined_from_a_wrong_slot_is_refused(bench_config,
                                                         bench_ref):
    out = bench_ref.sound(bench_config, 7)
    y = np.array(out[bench_ref.OUTPUT])
    y[5], y[6] = y[6].copy(), y[5].copy()
    swapped = {bench_ref.OUTPUT: jax.device_put(
        jnp.asarray(y), out[bench_ref.OUTPUT].sharding)}
    assert "moe_y_widest_token_gap" in _bad(
        bench_ref.check(bench_config, 7, swapped))


def test_builder_runs_naive_and_phases_against_the_benchmark_reference(
        bench_config, bench_ref, registry):
    """The cell's builder at toy shapes on four of the virtual devices: the
    program's naive and phase-ordered schedules pass the comparison that
    decides ``correct``, dropped slots among its numbers."""
    from benchmarks.harness.cell import load_module

    built = load_module("builders", "moe_mesh").build(
        bench_config, 7, jax.devices()[:4], bench_ref)
    assert built.cost["flops"] > 0 and built.cost["hbm_bytes"] > 0
    for order in (built.naive, greedy_phase_order(
            built.graph, built.hints["platform"], built.hints["phases"])):
        compared = built.check(built.executor.run(order))
        assert _bad(compared) == []
        assert {"moe.dropped_slots", "moe_y_rms_gap",
                "chips_without_a_shard"} <= {c["name"] for c in compared}


def test_per_layer_readers_read_what_the_program_counts(registry):
    from benchmarks.harness.cell import load_module

    fill = load_module("layer_metrics", "slot_fill_share")
    assert fill.read({}) is None  # a program without the counters
    registry.counter("moe.capacity_slots").inc(200)
    registry.counter("moe.routed_slots").inc(150)
    assert fill.read({}) == 75.0
    record = {"trace": {"finalist_modules": [["m", 0.010], ["m", 0.040]],
                        "finalist_n": [10, 40],
                        "window": {"busy_s": 2.0, "device_ops": [
                            ["fusion", 1.0], ["all_to_all", 0.25],
                            ["all-to-all-done", 0.25]]}},
              "peaks": {"bf16_flops": 200e12}, "cost": {"flops": 1e11}}
    mxu = load_module("layer_metrics", "iter_mxu_roofline")
    assert mxu.read(record) == pytest.approx(50.0)
    assert mxu.read({**record, "cost": {"flops": 0.0}}) is None
    assert mxu.read({**record, "trace": None}) is None
    a2a = load_module("layer_metrics", "alltoall_device_share")
    assert a2a.read(record) == pytest.approx(25.0)
    record["trace"]["window"]["device_ops"] = [["fusion", 1.0]]
    assert a2a.read(record) is None


def test_cost_counts_useful_work_of_one_chip():
    from benchmarks.harness.moe_costs import moe_layer_cost

    c = moe_layer_cost(8192, 2048, 1408, 6, 2816, 64, 16)
    assert c["flops"] == pytest.approx(1.136e12, rel=2e-3)
    assert 1.2e9 < c["hbm_bytes"] < 2.6e9
