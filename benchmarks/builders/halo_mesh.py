"""Builder of the halo exchange over a grid of ranks (``models/halo.py``),
one rank a chip, as ``__graft_entry__.halo_mesh_on_chips`` builds it.

The grid is made by the plain reference from the seed, every rank's local
grid on its own device (the program's own ``make_halo_buffers`` builds the
global grid, and four more arrays of its size, on the host); the face
buffers are the program's shapes, zero under the same sharding.  Executor
and solver share one platform: the source's two streams on the rank grid.
Each exchange's engine is a searched decision (``xfer_choice``); pack and
unpack are XLA slices.  No phases in the hints: a tree search plays uniform
playouts.
"""

from __future__ import annotations

from types import SimpleNamespace

from benchmarks.harness import costs


def build(config: dict, seed: int, devices, reference) -> SimpleNamespace:
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from tenzing_tpu.bench.driver import naive_schedule
    from tenzing_tpu.core.graph import Graph
    from tenzing_tpu.core.platform import Platform
    from tenzing_tpu.models.halo import (
        DIRECTIONS,
        HaloArgs,
        _face_slices,
        add_to_graph,
        dir_name,
    )
    from tenzing_tpu.runtime.executor import TraceExecutor

    s = config["shapes"]
    grid = tuple(int(m) for m in s["mesh"])
    if len(devices) != int(s["ranks"]) or int(np.prod(grid)) != len(devices):
        raise ValueError(f"{s['ranks']} ranks on a {grid} grid, handed "
                         f"{len(devices)} device(s)")
    mesh = Mesh(np.array(devices).reshape(grid), ("x", "y", "z"))
    n = int(s["cells_per_shard"])
    hargs = HaloArgs(nq=int(s["nq"]), lx=n, ly=n, lz=n,
                     radius=int(s["radius"]), dtype=s["dtype"])
    spec = P(None, "x", "y", "z")
    sharded = NamedSharding(mesh, spec)
    bufs = {"U": reference.make_data(config, seed)}
    if not bufs["U"].sharding.is_equivalent_to(sharded, 4):
        raise ValueError("the reference's grid does not lie rank by rank on "
                         "the devices handed to the builder")
    for d in DIRECTIONS:
        _, sz = _face_slices(hargs, d, "pack")
        shape = (sz[0],) + tuple(m * e for m, e in zip(grid, sz[1:]))
        for kind in ("buf", "recv"):
            bufs[f"{kind}_{dir_name(d)}"] = jnp.zeros(shape, hargs.dtype,
                                                      device=sharded)
    lanes = config["lanes"]
    if lanes["executor"] != lanes["solver"]:
        raise ValueError("executor and solver share one platform here")
    platform = Platform.make_n_lanes(int(lanes["executor"]), mesh=mesh,
                                     specs={name: spec for name in bufs})
    graph = add_to_graph(Graph(), hargs, xfer_choice=True)
    return SimpleNamespace(
        graph=graph, executor=TraceExecutor(platform, bufs),
        # the generic path: the first decision the SDP offers, on one lane
        naive=naive_schedule("halo_mesh", graph, None),
        hints={"platform": platform, "engines": list(s["engines"])},
        check=lambda out: reference.check(config, seed, out),
        precompile_check=lambda out: reference.precompile(
            config, out[reference.OUTPUT]),
        # one chip's share of the traffic, against one chip's peaks
        cost=costs.halo_cost(hargs.nq, n, n, n, hargs.radius,
                             hargs.itemsize()))
