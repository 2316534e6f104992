"""Builder of one expert layer, expert-parallel over a mesh of ranks
(``models/moe.py``), one rank a chip.

The data are made by the plain reference from the seed, every rank's part
on its own device; the program negotiates its slot tables from them at
set-up (``mesh_moe_buffers``: nothing of the global size passes through the
host) and refuses a layer that would drop a token.  Executor and solver
share one platform.  Naive is the generic path: the first decision the SDP
offers, on one lane.  The hints carry the phase list of the
post-all-before-await-any discipline, so a tree search's playouts are the
informed ones.  XLA only: no kernel menu, no op chunking, no synthesized
all-to-all (switches of ``MoELayer`` that stay off).
"""

from __future__ import annotations

from types import SimpleNamespace

from benchmarks.harness.moe_costs import moe_layer_cost


def build(config: dict, seed: int, devices, reference) -> SimpleNamespace:
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding

    from tenzing_tpu.bench.driver import naive_schedule
    from tenzing_tpu.core.graph import Graph
    from tenzing_tpu.core.platform import Platform
    from tenzing_tpu.models.moe import (
        AXIS,
        PHASES,
        MoEArgs,
        MoELayer,
        layer_specs,
        mesh_moe_buffers,
    )
    from tenzing_tpu.obs.metrics import get_metrics
    from tenzing_tpu.runtime.executor import TraceExecutor

    s, z = config["shapes"], reference.sizes(config)
    if len(devices) != z["ranks"]:
        raise ValueError(f"{z['ranks']} ranks, handed {len(devices)} "
                         "device(s)")
    margs = MoEArgs(
        n_ep=z["ranks"], tokens_per_shard=z["tokens"], d_model=z["d"],
        d_ff=z["f"], n_chunks=int(s["n_chunks"]), dtype=z["dtype"],
        experts_per_shard=int(s["experts_per_shard"]), top_k=z["top_k"],
        gated=True, shared_ff=z["fs"],
        capacity_factor=float(s["capacity_factor"]), scoring="sigmoid",
        routed_scale=z["scale"])
    mesh = Mesh(np.array(devices), (AXIS,))
    data = reference.make_data(config, seed)
    for name, spec in layer_specs(margs).items():
        if name in data and not data[name].sharding.is_equivalent_to(
                NamedSharding(mesh, spec), data[name].ndim):
            raise ValueError(f"the reference's {name} does not lie rank by "
                             "rank on the devices handed to the builder")
    bufs, specs = mesh_moe_buffers(margs, mesh, data)
    lanes = config["lanes"]
    if lanes["executor"] != lanes["solver"]:
        raise ValueError("executor and solver share one platform here")
    platform = Platform.make_n_lanes(int(lanes["executor"]), mesh=mesh,
                                     specs=specs)
    layer = MoELayer(margs)
    graph = Graph()
    graph.start_then(layer)
    graph.then_finish(layer)
    dropped = get_metrics().counter("moe.dropped_slots")

    def check(out):
        return reference.check(config, seed, out) + [
            {"name": "moe.dropped_slots", "value": dropped.value, "limit": 0}]

    return SimpleNamespace(
        graph=graph, executor=TraceExecutor(platform, bufs),
        naive=naive_schedule("moe_mesh", graph, None),
        hints={"platform": platform, "phases": list(PHASES)},
        check=check,
        precompile_check=lambda out: reference.precompile(
            config, out[reference.OUTPUT]),
        # one chip's share of the work, against one chip's peaks
        cost=moe_layer_cost(z["tokens"], z["d"], z["f"], z["top_k"], z["fs"],
                            z["experts"], int(s["experts_per_shard"]),
                            jnp.dtype(z["dtype"]).itemsize))
