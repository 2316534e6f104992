"""The one-chip control of a change to the mesh halo (ISSUE 44, ISSUE 47):
sha256 of the lowered repeat-n text of ``halo512.climb``'s graph
(``models/halo_pipeline.py`` with both menus) under five schedules: naive,
the greedy and the paired ``rdma`` incumbents, two walks of the kernel menu
(``halo_alias_prefer`` at 3 and 6 lanes).  CPU, a toy grid, nothing runs:

    JAX_PLATFORMS=cpu python experiments/halo_onechip_text.py [--root CHECKOUT]

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
    sys.path.insert(0, os.path.abspath(args.root))
    import jax
    import jax.numpy as jnp

    from tenzing_tpu.bench.driver import halo_alias_prefer, naive_schedule
    from tenzing_tpu.core.platform import Platform
    from tenzing_tpu.models.halo import HaloArgs
    from tenzing_tpu.models.halo_pipeline import (
        HALO_PHASES,
        build_graph,
        greedy_overlap_order,
        host_buffer_names,
        make_pipeline_buffers,
        paired_overlap_order,
    )
    from tenzing_tpu.runtime.executor import TraceExecutor
    from tenzing_tpu.solve.local import drive, phase_policy

    # lz a multiple of 128, so the flat kernels are on the y faces' menu
    hargs = HaloArgs(nq=3, lx=8, ly=8, lz=128, radius=3)
    bufs, _ = make_pipeline_buffers(hargs, seed=0, with_expected=False)
    ex = TraceExecutor(Platform.make_n_lanes(8), TraceExecutor.place_host_buffers(
        bufs, host_buffer_names()))
    graph = build_graph(hargs, impl_choice=True, xfer_choice=True)
    orders = {
        "naive": naive_schedule("halo", graph, hargs),
        "greedy-rdma-3l": greedy_overlap_order(
            hargs, Platform.make_n_lanes(3), engine="rdma"),
        "paired-mixed-6l": paired_overlap_order(
            hargs, Platform.make_n_lanes(6), engine="mixed"),
    }
    for lanes in (3, 6):
        plat = Platform.make_n_lanes(lanes)
        orders[f"menu-alias-{lanes}l"] = drive(graph, plat, phase_policy(
            plat, HALO_PHASES, halo_alias_prefer))[0]
    for label, order in orders.items():
        text = jax.jit(ex._stepped_fn(order.vector())).lower(
            ex.init_bufs, jnp.int32(1)).as_text()
        print(label, len(order.vector()), "ops",
              hashlib.sha256(text.encode()).hexdigest())
    return 0


if __name__ == "__main__":
    sys.exit(main())
