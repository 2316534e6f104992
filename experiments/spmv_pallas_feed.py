"""What the ``(w, m)`` slab costs the two SpMV paths that are in no cell.

    chiprun -- python experiments/spmv_pallas_feed.py --tree change \
        --out chiprun_out/spmv_pallas_feed_change.jsonl

Written against the library's public names only, so the same file runs in a
checkout of the parent commit (``--tree`` only labels the rows).  For each
size m at which the menu keeps the Pallas choice (x no longer than 4 096) the
library's own workload (``make_spmv_buffers(m=m)``, as ``bench.py --workload
spmv`` builds it) is run two ways:

* ``menu``: the all-compute iteration (``exchange="local"``, one lane) with
  both products resolved to ``.xla`` and to ``.pallas``: first call, iteration
  time by the benchmark's two-point clock, widest gap of y to the host's
  answer.  From PR 26 ``ell_spmv_pallas`` is handed the ``(w, m)`` slab and
  relays it out to row-major tiles inside the call; up to PR 25 the buffers
  were row-major already.
* ``fused``: the naive schedule of the host-staged exchange through
  ``FusedExecutor``: its regions, the tile counts each admits, and for each
  count the first call and the iteration time, or the compiler's refusal.  Up
  to PR 25 ``SpMVOp`` declared its slab tileable; from PR 26 it declares no
  tiling (the row range), so its regions are single-tile.

One process; every number is of the device it prints.  ``--rehearse-cpu``
walks the same path at toy size on the CPU (control flow only).
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _schedule(graph, platform, prefer):
    """The first decision the SDP offers at every step, except that a kernel
    choice takes the alternative whose name ends in ``prefer``."""
    from tenzing_tpu.core.state import State

    st = State(graph)
    while not st.is_terminal():
        ds = st.get_decisions(platform)
        st = st.apply(next((d for d in ds if prefer in str(d)), ds[0]))
    return st.sequence


def _timed_row(executor, seq, want, clock_mod):
    import numpy as np

    run_n = executor.prepare_n(seq)
    t0 = time.perf_counter()
    run_n(1)
    first_call_s = time.perf_counter() - t0
    c = clock_mod.two_point(run_n, clock=time.perf_counter)
    y = np.asarray(executor.run(seq)["y"], dtype=np.float64)
    return {"first_call_s": first_call_s, "iter_ms": c["iter_s"] * 1e3,
            "fixed_ms": c["fixed_s"] * 1e3,
            "iter_ms_rounds": [s * 1e3 for s in c["slopes"]],
            "y_widest_gap": float(np.max(np.abs(y - want)))}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tree", default="change")
    ap.add_argument("--sizes", default="4096,2048")
    ap.add_argument("--seed", type=int, default=2147483907)
    ap.add_argument("--out", default="")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp

    from benchmarks.harness import clock as clock_mod
    from tenzing_tpu.bench.driver import naive_schedule
    from tenzing_tpu.core.graph import Graph
    from tenzing_tpu.core.platform import Platform
    from tenzing_tpu.models.spmv import (
        SpMVCompound,
        make_spmv_buffers,
        spmv_host_buffer_names,
    )
    from tenzing_tpu.runtime.executor import TraceExecutor
    from tenzing_tpu.runtime.fused import FusedExecutor

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse_cpu:
        raise SystemExit(f"device refused: {dev.platform} (no --rehearse-cpu)")
    device = {"platform": dev.platform, "device_kind": dev.device_kind}
    one_lane = Platform.make_n_lanes(1)
    rows = []

    def emit(row):
        row = {"tree": args.tree, "device": device, **row}
        rows.append(row)
        print(json.dumps(row), flush=True)

    for m in (int(s) for s in args.sizes.split(",") if s):
        bufs, want = make_spmv_buffers(m=m, seed=args.seed)
        n_rem = int(bufs["x_remote"].shape[0])
        x_sizes = {"x_local": m, "x_remote": n_rem}
        shapes = {k: list(bufs[k].shape) for k in ("A_loc_vals", "A_rem_vals")}

        def graph_of(**kw):
            g = Graph()
            op = SpMVCompound(x_sizes=x_sizes, **kw)
            g.start_then(op)
            g.then_finish(op)
            return g

        # the kernel menu, all-compute iteration
        ex = TraceExecutor(one_lane, {k: jnp.asarray(v) for k, v in bufs.items()})
        g = graph_of(impl_choice=True, exchange="local")
        for impl in (".xla", ".pallas", ".xla", ".pallas"):
            seq = _schedule(g, one_lane, impl)
            names = [op.name() for op in seq.vector()]
            assert sum(n.endswith(impl) for n in names) == 2, names
            emit({"what": "menu", "m": m, "n_remote": n_rem, "impl": impl,
                  "slab_shapes": shapes, **_timed_row(ex, seq, want, clock_mod)})

        # fused regions of the host-staged exchange's naive schedule
        g = graph_of(exchange="host")
        seq = naive_schedule("spmv", g, m)
        ex = TraceExecutor(one_lane, TraceExecutor.place_host_buffers(
            bufs, spmv_host_buffer_names(n_rem)))
        emit({"what": "stepped", "m": m, **_timed_row(ex, seq, want, clock_mod)})
        plan = FusedExecutor(ex, min_tile_bytes=0).plan(seq)
        regions = [{"ops": list(r.members), "valid_tiles": list(r.valid_tiles)}
                   for r in plan.regions]
        for tiles in sorted({t for r in plan.regions for t in r.valid_tiles}
                            & {1, 2, 8, 32}):
            row = {"what": "fused", "m": m, "tiles": tiles, "regions": regions}
            try:
                fex = FusedExecutor(ex, tiles=tiles, min_tile_bytes=0)
                row.update(_timed_row(fex, seq, want, clock_mod))
            except Exception as e:  # the compiler's refusal is the reading
                row["error"] = f"{type(e).__name__}: {str(e)[:400]}"
            emit(row)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            for row in rows:
                f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
