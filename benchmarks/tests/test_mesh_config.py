"""The sharded halo configuration at its rehearsal shapes, on four virtual
devices: the plain reference accepts its own exchange and refuses its
low-precision control, one wrong ghost cell and an output that lies on one
device; its data is what one process draws unsharded, laid out rank by rank;
the program's one-shot output equals the reference's for naive and for both
engine-overlap schedules; and the mix measures one such schedule for each
engine before the tree search."""

import json
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import cell as cell_mod
from benchmarks.harness.stack import Deadline

ROOT = Path(__file__).resolve().parent.parent.parent
SEED = 2**31 + 23


@pytest.fixture(scope="module")
def config():
    doc = json.loads(
        (ROOT / "benchmarks/configs/halo512-mesh4.json").read_text())
    return cell_mod.toy_shapes(doc)


@pytest.fixture(scope="module")
def ref(config):
    return cell_mod.load_module("references", config["reference"])


@pytest.fixture(scope="module")
def built(config, ref):
    builder = cell_mod.load_module("builders", config["builder"])
    return builder.build(config, SEED, jax.devices()[:4], ref)


def test_sound_passes_and_control_is_refused(config, ref):
    good = ref.check(config, SEED, ref.sound(config, SEED))
    assert [(c["name"], c["value"]) for c in good] == [
        ("halo_mismatched_cells", 0), ("chips_without_a_shard", 0)]
    bad = {c["name"]: c["value"]
           for c in ref.check(config, SEED, ref.control(config, SEED))}
    # nearly every ghost cell changes when it travels as bfloat16
    nq, n, r, grid = ref.sizes(config)
    ghosts = 4 * 6 * nq * r * n * n
    assert 0.7 * ghosts < bad["halo_mismatched_cells"] <= ghosts
    assert bad["chips_without_a_shard"] == 0


def test_data_is_what_one_process_draws_unsharded(config, ref):
    nq, n, r, grid = ref.sizes(config)
    whole = np.asarray(jax.random.uniform(
        jax.random.key(jnp.uint32(SEED & 0xFFFFFFFF)),
        (nq,) + tuple(m * n for m in grid), jnp.float32))
    data = ref.make_data(config, SEED)
    assert len({s.device for s in data.addressable_shards}) == 4
    got = np.asarray(data)
    w = n + 2 * r
    want = np.zeros_like(got)
    for i in range(grid[0]):
        for j in range(grid[1]):
            for k in range(grid[2]):
                want[:, i * w + r:i * w + r + n, j * w + r:j * w + r + n,
                     k * w + r:k * w + r + n] = whole[
                         :, i * n:(i + 1) * n, j * n:(j + 1) * n,
                         k * n:(k + 1) * n]
    np.testing.assert_array_equal(got, want)


def test_one_wrong_ghost_cell_on_one_shard_is_refused(config, ref):
    nq, n, r, grid = ref.sizes(config)
    out = ref.sound(config, SEED)[ref.OUTPUT]
    w = n + 2 * r
    # a low-y ghost cell of rank (1, 1, 0)
    wrong = out.at[1, w + r + 2, w + 1, r + 3].add(1.0)
    wrong = jax.device_put(wrong, out.sharding)
    bad = {c["name"]: c["value"]
           for c in ref.check(config, SEED, {ref.OUTPUT: wrong})}
    assert bad == {"halo_mismatched_cells": 1, "chips_without_a_shard": 0}


def test_an_output_gathered_onto_one_device_is_refused(config, ref):
    out = ref.sound(config, SEED)[ref.OUTPUT]
    gathered = jax.device_put(out, jax.devices()[0])
    bad = {c["name"]: c["value"]
           for c in ref.check(config, SEED, {ref.OUTPUT: gathered})}
    assert bad == {"halo_mismatched_cells": 0, "chips_without_a_shard": 3}


def test_program_equals_reference_for_naive_and_both_engines(config, ref,
                                                             built):
    from tenzing_tpu.models.halo import engine_overlap_order

    want = np.asarray(ref.sound(config, SEED)[ref.OUTPUT])
    plat = built.hints["platform"]
    orders = {"naive": built.naive}
    for e in built.hints["engines"]:
        orders[e] = engine_overlap_order(built.graph, plat, e)
    assert set(orders) == {"naive", "xla", "rdma"}
    for label, order in orders.items():
        out = built.executor.run(order)
        np.testing.assert_array_equal(np.asarray(out["U"]), want,
                                      err_msg=label)
        assert all(c["value"] == 0 for c in built.check(out)), label
        names = [op.desc() for op in order.vector()
                 if op.desc().startswith("exchange_")]
        assert len(names) == 6
        if label != "naive":
            assert all(nm.endswith("." + label) for nm in names), names


def test_mix_measures_one_overlap_schedule_an_engine_then_searches(built):
    """The adapter's first two candidates: every exchange on ``xla``, then
    every exchange on ``rdma``; the third comes from the tree search."""
    seen = []

    class Bench:
        def benchmark(self, order, opts=None):
            seen.append((order, opts))
            if len(seen) == 3:
                raise Deadline()
            return SimpleNamespace(pct50=1.0, pct10=1.0, pct90=1.0,
                                   pct01=1.0, pct99=1.0, stddev=0.0)

    mix = json.loads((ROOT / "benchmarks/mixes/mcts-engines.json").read_text())
    plain = json.loads((ROOT / "benchmarks/mixes/mcts.json").read_text())
    assert mix["params"] == plain["params"]  # letter for letter
    solver = cell_mod.load_module("solvers", mix["solver"])
    ctx = SimpleNamespace(graph=built.graph, bench=Bench(), verifier=None,
                          prefetcher=None, hints=built.hints, seed=SEED)
    with pytest.raises(Deadline):
        solver.run(ctx, mix["params"])
    assert len(seen) == 3
    for (order, opts), engine in zip(seen, ("xla", "rdma")):
        names = [op.desc() for op in order.vector()
                 if op.desc().startswith("exchange_")]
        assert len(names) == 6 and all(n.endswith("." + engine)
                                       for n in names)
        assert (opts.n_iters, opts.target_secs) == (6, 0.01)
