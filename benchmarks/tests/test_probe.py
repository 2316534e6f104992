"""The probe of ``timed_fence_gap`` is drawn shard by shard: each floating
buffer's fill comes out of one program already under the buffer's own
sharding, and is, to the last bit, what the one-device construction drew
(the expression the harness held up to PR 26, kept in
``mesh_memory_on_chip.py`` as the oracle)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from benchmarks.harness import cell as cell_mod
from mesh_memory_on_chip import old_probe_buffers  # the oracle

SEED = 2**31 + 11


def one_device_bufs():
    rng = np.random.default_rng(0)
    return cell_mod.committed({
        "big": jnp.asarray(rng.random((3, 700, 600)), jnp.float32),
        "small": jnp.asarray(rng.random((40, 128)), jnp.float32),
        "half": jnp.asarray(rng.random((17, 9)), jnp.bfloat16),
        "index": jnp.asarray(rng.integers(0, 99, (64,)), jnp.int32)})


def sharded_bufs():
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2, 1),
                ("x", "y", "z"))
    sharded = NamedSharding(mesh, P(None, "x", "y", "z"))
    rng = np.random.default_rng(1)
    return {
        "U": jax.device_put(jnp.asarray(rng.random((3, 28, 28, 14)),
                                        jnp.float32), sharded),
        "big": jax.device_put(jnp.asarray(rng.random((3, 256, 256, 8)),
                                          jnp.float32), sharded),
        "recv_px": jnp.zeros((3, 6, 16, 8), jnp.float32, device=sharded),
        "index": jax.device_put(jnp.arange(8, dtype=jnp.int32),
                                NamedSharding(mesh, P()))}


@pytest.mark.parametrize("make", [one_device_bufs, sharded_bufs])
def test_probe_is_the_one_device_construction_to_the_last_bit(make):
    bufs = make()
    new = cell_mod.probe_buffers(bufs, SEED)
    old = old_probe_buffers(bufs, SEED)
    assert sorted(new) == sorted(old) == sorted(bufs)
    for name, v in bufs.items():
        assert new[name].sharding.is_equivalent_to(v.sharding, v.ndim)
        assert new[name].dtype == v.dtype and new[name].shape == v.shape
        np.testing.assert_array_equal(np.asarray(new[name], np.float32),
                                      np.asarray(old[name], np.float32))
    assert new["index"] is bufs["index"]  # passed through untouched
    big = np.asarray(new["big"])
    assert big.min() == -2 and big.max() == 2  # over 2**20 elements
    small = np.asarray(new["recv_px" if "recv_px" in new else "small"])
    assert small.min() == 0 and small.max() == 4


@pytest.mark.parametrize("make", [one_device_bufs, sharded_bufs])
def test_every_fill_leaves_its_program_under_the_buffers_sharding(make):
    key = jax.random.key(0)
    for v in make().values():
        if not jnp.issubdtype(v.dtype, jnp.floating):
            continue
        compiled = cell_mod.probe_fill(v.shape, v.dtype, -2, v.sharding)
        assert compiled.output_shardings.is_equivalent_to(v.sharding, v.ndim)
        out = compiled(key)
        assert {s.device for s in out.addressable_shards} == \
            v.sharding.device_set
        assert all(s.data.shape == v.sharding.shard_shape(v.shape)
                   for s in out.addressable_shards)


def test_probe_of_a_pinned_host_buffer_is_placed_as_the_buffer():
    dev = jax.devices()[0]
    kinds = {m.kind for m in dev.addressable_memories()}
    if "pinned_host" not in kinds:
        pytest.skip("no pinned_host memory on this backend")
    host = jax.sharding.SingleDeviceSharding(dev, memory_kind="pinned_host")
    bufs = {"host": jax.device_put(jnp.ones((8, 128), jnp.float32), host)}
    new = cell_mod.probe_buffers(bufs, SEED)
    assert new["host"].sharding.memory_kind == "pinned_host"
    np.testing.assert_array_equal(
        np.asarray(new["host"]), np.asarray(old_probe_buffers(bufs,
                                                              SEED)["host"]))
