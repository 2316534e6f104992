"""The mesh control of a change to the one-chip halo (ISSUE 48), the mirror
of ``halo_onechip_text.py``: sha256 of the lowered repeat-n text of
``halo512-mesh4.mcts``'s graph (``models/halo.py``, both exchange engines on
the menu) under naive and the two engine overlaps.  CPU, four virtual
devices, a toy shard no extent of which is a multiple of its tile, nothing
runs:

    python experiments/halo_mesh_text.py [--root CHECKOUT]

Run it on the parent's checkout (``git archive <parent> | tar -x -C DIR``)
and on this one and compare the lines: equal digests, equal programs.
"""

import argparse
import hashlib
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=HERE,
                    help="the checkout whose program is lowered")
    args = ap.parse_args()
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, os.path.abspath(args.root))
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding

    from tenzing_tpu.bench.driver import naive_schedule
    from tenzing_tpu.core.graph import Graph
    from tenzing_tpu.core.platform import Platform
    from tenzing_tpu.models.halo import (
        HaloArgs,
        add_to_graph,
        engine_overlap_order,
        make_halo_buffers,
    )
    from tenzing_tpu.runtime.executor import TraceExecutor

    hargs = HaloArgs(nq=3, lx=16, ly=24, lz=136, radius=3)
    shape = (2, 2, 1)
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(shape), ("x", "y", "z"))
    bufs, specs, _ = make_halo_buffers(shape, hargs, seed=3)
    plat = Platform.make_n_lanes(2, mesh=mesh, specs=specs)
    graph = add_to_graph(Graph(), hargs, xfer_choice=True)
    ex = TraceExecutor(plat, {
        k: jax.device_put(v, NamedSharding(mesh, specs[k]))
        for k, v in bufs.items()})
    orders = {"naive": naive_schedule("halo_mesh", graph, None)}
    for engine in ("xla", "rdma"):
        orders[f"overlap.{engine}"] = engine_overlap_order(graph, plat, engine)
    for label, order in orders.items():
        text = jax.jit(ex._stepped_fn(order.vector())).lower(
            ex.init_bufs, jnp.int32(1)).as_text()
        print(label, len(order.vector()), "ops",
              hashlib.sha256(text.encode()).hexdigest())
    return 0


if __name__ == "__main__":
    sys.exit(main())
