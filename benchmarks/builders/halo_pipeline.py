"""Builder of the one-rank halo-exchange pipeline (``models/halo_pipeline``).

The grid is made on the device by the plain reference from the seed (the
program's own ``make_pipeline_buffers`` builds 2 GB on the host); the staging
buffers take the program's shapes.  Lanes, phases and the climb's prefer
function are the driver's for this configuration (``bench/driver.py``
``search_lanes``, the second halo climb).
"""

from __future__ import annotations

from types import SimpleNamespace

from benchmarks.harness import costs


def build(config: dict, seed: int, devices, reference) -> SimpleNamespace:
    import jax.numpy as jnp

    from tenzing_tpu.bench.driver import halo_alias_prefer, naive_schedule
    from tenzing_tpu.core.platform import Platform
    from tenzing_tpu.models.halo import DIRECTIONS, HaloArgs, _face_slices, \
        dir_name
    from tenzing_tpu.models.halo_pipeline import (
        HALO_PHASES,
        _flat_rows,
        _padded_shape,
        build_graph,
        host_buffer_names,
    )
    from tenzing_tpu.runtime.executor import TraceExecutor

    s = config["shapes"]
    n = int(s["cells_per_rank"])
    hargs = HaloArgs(nq=int(s["nq"]), lx=n, ly=n, lz=n,
                     radius=int(s["radius"]), dtype=s["dtype"])
    bufs = {"U": reference.make_data(
        config, seed, padded=_padded_shape(hargs.local_shape(),
                                           hargs.itemsize()))}
    for d in DIRECTIONS:
        _, sz = _face_slices(hargs, d, "pack")
        for kind in ("buf", "host", "recv"):
            bufs[f"{kind}_{dir_name(d)}"] = jnp.zeros(
                (_flat_rows(sz), 128), hargs.dtype)
    bufs = TraceExecutor.place_host_buffers(bufs, host_buffer_names())
    menus = bool(s["menus"])
    graph = build_graph(hargs, impl_choice=menus, xfer_choice=menus)
    lanes = config["lanes"]
    executor = TraceExecutor(Platform.make_n_lanes(int(lanes["executor"])),
                             bufs)
    return SimpleNamespace(
        graph=graph, executor=executor,
        naive=naive_schedule("halo", graph, hargs),
        hints={"platform": Platform.make_n_lanes(int(lanes["solver"])),
               "phases": HALO_PHASES, "prefer": halo_alias_prefer},
        check=lambda out: reference.check(config, seed, out),
        precompile_check=lambda out: reference.precompile(
            config, out[reference.OUTPUT]),
        cost=costs.halo_cost(hargs.nq, n, n, n, hargs.radius,
                             hargs.itemsize()))
