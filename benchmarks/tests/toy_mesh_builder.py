"""A toy sharded configuration for the four-virtual-device rehearsal: the
``models/halo.py`` exchange on a 2x2x1 mesh at 8^3 cells per shard, built as
``__graft_entry__.halo_mesh_on_chips`` builds it.  Not a cell: it shows that
the harness takes a configuration across chips as data (``chips`` from the
cell, ``devices`` handed to the builder, nothing in the harness assuming one
device).  The expected grid is the program's own here, which a real
configuration's reference may not be."""

from types import SimpleNamespace

import numpy as np


def build(config, seed, devices, reference):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding

    from tenzing_tpu.bench.driver import naive_schedule
    from tenzing_tpu.core.graph import Graph
    from tenzing_tpu.core.platform import Platform
    from tenzing_tpu.models.halo import (
        HaloArgs,
        add_to_graph,
        make_halo_buffers,
    )
    from tenzing_tpu.runtime.executor import TraceExecutor

    assert len(devices) == 4, devices
    s = config["shapes"]
    mesh_shape = (2, 2, 1)
    mesh = Mesh(np.array(devices).reshape(mesh_shape), ("x", "y", "z"))
    n = int(s["cells_per_rank"])
    hargs = HaloArgs(nq=int(s["nq"]), lx=n, ly=n, lz=n,
                     radius=int(s["radius"]))
    bufs, specs, want = make_halo_buffers(mesh_shape, hargs,
                                          seed=seed % (2**31))
    plat = Platform.make_n_lanes(2, mesh=mesh, specs=specs)
    placed = {k: jax.device_put(v, NamedSharding(mesh, specs[k]))
              for k, v in bufs.items()}
    graph = add_to_graph(Graph(), hargs)
    want = jnp.asarray(want)

    def check(out):
        owners = {sh.device for sh in out["U"].addressable_shards}
        bad = int(jnp.sum(out["U"] != want))
        return [{"name": "toy_mismatched_cells", "value": bad, "limit": 0},
                {"name": "toy_chips_without_a_shard",
                 "value": len(devices) - len(owners), "limit": 0}]

    return SimpleNamespace(
        graph=graph, executor=TraceExecutor(plat, placed),
        naive=naive_schedule("toy", graph, None),
        hints={"platform": plat}, check=check,
        precompile_check=lambda out: None,
        cost={"flops": 0.0, "hbm_bytes": 1.0})
