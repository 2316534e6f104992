"""Builder of the SpMV iteration with the host-staged x exchange
(``models/spmv.SpMVCompound(exchange="host")``).

The matrix and x are the plain reference's, made from the seed; the program
only changes their form (coordinate -> CSR -> split local/remote ELL slabs).
"""

from __future__ import annotations

from types import SimpleNamespace

from benchmarks.harness import costs


def build(config: dict, seed: int, devices, reference) -> SimpleNamespace:
    from tenzing_tpu.bench.driver import naive_schedule
    from tenzing_tpu.core.graph import Graph
    from tenzing_tpu.core.platform import Platform
    from tenzing_tpu.models.spmv import (
        CooMat,
        SpMVCompound,
        make_spmv_buffers,
        spmv_host_buffer_names,
    )
    from tenzing_tpu.runtime.executor import TraceExecutor

    data = reference.make_data(config, seed)
    m = data["m"]
    a = CooMat(m, m, data["rows"], data["cols"], data["vals"]).to_csr()
    bufs, _ = make_spmv_buffers(m=m, matrix=a)
    bufs["x_local"] = data["x"]  # the reference's x, not the program's
    n_rem = int(bufs["x_remote"].shape[0])
    jbufs = TraceExecutor.place_host_buffers(
        bufs, spmv_host_buffer_names(n_rem))
    x_sizes = {"x_local": m, "x_remote": n_rem}

    def mk():
        return SpMVCompound(impl_choice=bool(config["shapes"]["menus"]),
                            x_sizes=x_sizes, exchange="host")

    graph = Graph()
    graph.start_then(mk())
    graph.then_finish(mk())
    lanes = config["lanes"]
    executor = TraceExecutor(Platform.make_n_lanes(int(lanes["executor"])),
                             jbufs)
    return SimpleNamespace(
        graph=graph, executor=executor,
        naive=naive_schedule("spmv", graph, m),
        hints={"platform": Platform.make_n_lanes(int(lanes["solver"]))},
        check=lambda out: reference.check(config, seed, out, data=data),
        precompile_check=lambda out: reference.precompile(
            config, out[reference.OUTPUT]),
        cost=costs.spmv_cost(m, len(data["vals"])))
