"""Absolute (roofline) accounting for the halo flagship (VERDICT r3 item 3).

The searched-vs-naive headline is self-relative; this script pins it to the
hardware.  On the real chip it measures, at the flagship config (nq=3, 512^3,
r=3), the achievable bandwidth of each physical engine the schedule uses:

* ``host`` — the pinned-host round trip (spill + fetch + await), both
  serialized one-face-at-a-time (the naive discipline) and all-six-posted
  (the aggregate the overlap schedules can draw);
* ``rdma`` — the on-chip DMA loopback copy (post + await);
* ``compute`` — the pack+unpack slices alone (no transfers): the HBM-bound
  floor no schedule can beat.

From tenzing_tpu.bench.roofline.halo_cost it derives bytes/iteration, then
reports the measured naive and searched-winner times as a fraction of their
*achievable* bound:

  naive bound    = t_compute + xfer_bytes / host_bw_serial      (all serialized)
  searched bound = max(t_compute, host_share / host_bw_agg)     (ideal overlap;
                   the mixed winner moves half the faces on the on-chip DMA,
                   whose time is negligible next to the host path)

Appends/updates the ``halo_pipeline`` entry of
experiments/EXTERNAL_BASELINES.json — the row next to attention's 52%-MFU row.

Run AFTER any driver bench finishes (host CPU is in the measured path:
memory/tpu-bench-hygiene).
"""

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main() -> int:
    import jax
    import numpy as np

    from tenzing_tpu.bench.benchmarker import BenchOpts, EmpiricalBenchmarker
    from tenzing_tpu.bench.roofline import halo_cost
    from tenzing_tpu.core.graph import Graph
    from tenzing_tpu.core.platform import Platform
    from tenzing_tpu.models.halo import DIRECTIONS, HaloArgs, dir_name
    from tenzing_tpu.models.halo_pipeline import (
        HALO_PHASES,
        direction_ops,
        host_buffer_names,
        make_pipeline_buffers,
    )
    from tenzing_tpu.runtime.executor import TraceExecutor
    from tenzing_tpu.solve.greedy import greedy_phase_order

    hargs = HaloArgs(nq=3, lx=512, ly=512, lz=512, radius=3)
    bufs, _ = make_pipeline_buffers(hargs, seed=0, with_expected=False)
    jbufs = TraceExecutor.place_host_buffers(bufs, host_buffer_names())
    face_bytes = {
        dir_name(d): bufs[f"buf_{dir_name(d)}"].nbytes for d in DIRECTIONS
    }
    total_face = float(sum(face_bytes.values()))
    cost = halo_cost(hargs.nq, hargs.lx, hargs.ly, hargs.lz, hargs.radius)

    # HIGH adaptive floor: a single dispatch was probed at
    # ~130-140 ms round trip on the round-5 backend, so per-sample costs are only trustworthy when
    # many samples amortize one dispatch — same reasoning as the driver's
    # final batch (20x floor)
    opts = BenchOpts(n_iters=8, target_secs=0.5)
    out = {"device": str(jax.devices()[0]), "config": vars(hargs).copy()
           if hasattr(hargs, "__dict__") else {
               "nq": hargs.nq, "n": hargs.lx, "radius": hargs.radius}}

    def timed(label, graph_ops_builder, n_lanes=8):
        """Benchmark a schedule built from subsets of the direction chains."""
        plat = Platform.make_n_lanes(n_lanes)
        g = Graph()
        graph_ops_builder(g)
        seq = greedy_phase_order(g, plat, HALO_PHASES)
        ex = TraceExecutor(plat, jbufs)
        t0 = time.time()
        res = EmpiricalBenchmarker(ex).benchmark(seq, opts)
        sys.stderr.write(
            f"{label}: pct50={res.pct50*1e3:.3f}ms (wall {time.time()-t0:.0f}s)\n"
        )
        return res.pct50

    # 1) compute floor: pack-only and unpack-only chains (recv buffers are
    # pre-filled zeros — the unpack's cost is the slice write, independent of
    # values)
    def packs_only(g):
        for d in DIRECTIONS:
            ops = direction_ops(hargs, d, engine="rdma")
            g.start_then(ops[0])
            g.then_finish(ops[0])

    def unpacks_only(g):
        for d in DIRECTIONS:
            ops = direction_ops(hargs, d, engine="rdma")
            g.start_then(ops[-1])
            g.then_finish(ops[-1])

    t_pack = timed("packs x6 (8 lanes)", packs_only)
    t_unpack = timed("unpacks x6 (8 lanes)", unpacks_only)
    t_compute = t_pack + t_unpack  # unpacks serialize on U (SSA); packs overlap

    # 2) host round trip, serialized (naive's transfer regime): one direction
    def host_one(g):
        d = DIRECTIONS[0]
        ops = direction_ops(hargs, d, engine="host")
        g.start_then(ops[0])
        for a, b in zip(ops, ops[1:]):
            g.then(a, b)
        g.then_finish(ops[-1])

    t_host1 = timed("host round trip x1", host_one, n_lanes=2)

    # 3) host round trip, all six posted before any await (aggregate)
    def host_all(g):
        for d in DIRECTIONS:
            ops = direction_ops(hargs, d, engine="host")
            g.start_then(ops[0])
            for a, b in zip(ops, ops[1:]):
                g.then(a, b)
            g.then_finish(ops[-1])

    t_host6 = timed("host round trips x6 overlapped", host_all)

    # 4) on-chip DMA copy (rdma loopback), one direction and all six
    def rdma_one(g):
        d = DIRECTIONS[0]
        ops = direction_ops(hargs, d, engine="rdma")
        g.start_then(ops[0])
        for a, b in zip(ops, ops[1:]):
            g.then(a, b)
        g.then_finish(ops[-1])

    def rdma_all(g):
        for d in DIRECTIONS:
            ops = direction_ops(hargs, d, engine="rdma")
            g.start_then(ops[0])
            for a, b in zip(ops, ops[1:]):
                g.then(a, b)
            g.then_finish(ops[-1])

    t_rdma1 = timed("rdma chain x1", rdma_one, n_lanes=2)
    t_rdma6 = timed("rdma chains x6", rdma_all)

    one_face = float(face_bytes[dir_name(DIRECTIONS[0])])
    # bytes over the host path: spill + fetch = 2 crossings per face
    bw = {
        "host_serial_gbs": 2 * one_face / (t_host1 - (t_pack + t_unpack) / 6) / 1e9
        if t_host1 > (t_pack + t_unpack) / 6 else 2 * one_face / t_host1 / 1e9,
        "host_aggregate_gbs": 2 * total_face / (t_host6 - t_compute) / 1e9
        if t_host6 > t_compute else 2 * total_face / t_host6 / 1e9,
        "rdma_copy_gbs": 2 * one_face / (t_rdma1 - (t_pack + t_unpack) / 6) / 1e9
        if t_rdma1 > (t_pack + t_unpack) / 6 else 2 * one_face / t_rdma1 / 1e9,
    }

    out.update(
        bytes_per_iter={
            "hbm_bytes": cost.hbm_bytes,
            "xfer_bytes_all_host": cost.xfer_bytes,
            "face_bytes_total": total_face,
        },
        measured_ms={
            "packs_x6": t_pack * 1e3,
            "unpacks_x6": t_unpack * 1e3,
            "host_roundtrip_x1": t_host1 * 1e3,
            "host_roundtrip_x6_overlapped": t_host6 * 1e3,
            "rdma_chain_x1": t_rdma1 * 1e3,
            "rdma_chains_x6": t_rdma6 * 1e3,
        },
        achievable_bandwidth=bw,
    )

    # bounds for the two disciplines at the flagship config
    host_serial = 2 * total_face / (bw["host_serial_gbs"] * 1e9)
    naive_bound = t_compute + host_serial
    half_host = total_face  # mixed winner: 3 of 6 faces on the host path
    searched_bound = max(t_compute, half_host / (bw["host_aggregate_gbs"] * 1e9))
    out["bounds_ms"] = {
        "t_compute": t_compute * 1e3,
        "naive_all_host_serial": naive_bound * 1e3,
        "searched_mixed_ideal_overlap": searched_bound * 1e3,
    }

    # menu-aware compute floor: t_compute above is the XLA slice/DUS chain,
    # but the schedule chooses per-face kernels from a 3-way menu
    # (ops/halo_pallas.py), and the r4k+ winners run batched-Pallas z-unpacks
    # far below the XLA DUS chain — so the honest floor per face is the MIN
    # over the measured kernel variants (experiments/kernel_microbench.py,
    # fetch-fenced chain slopes).  Without this the winner "beats the bound",
    # which just means the bound was computed for kernels it doesn't use.
    micro_path = Path(__file__).parent / "KERNEL_MICROBENCH.json"
    if micro_path.exists():
        micro = json.loads(micro_path.read_text())
        t_menu = 0.0
        per_axis = {}
        for a in ("px", "py", "pz"):
            r = micro["faces"][a]
            pmin = min(
                max(r[f"pack_{v}_ms_derived"], 0.02)
                for v in ("xla", "row", "batched")
            )
            umin = min(
                max(r[f"unpack_{v}_ms"], 0.02)
                for v in ("xla", "row", "batched")
            )
            per_axis[a] = {"pack_min_ms": pmin, "unpack_min_ms": umin}
            t_menu += 2 * (pmin + umin)  # both +/- faces per axis
        xfer_rdma = 2 * total_face / (bw["rdma_copy_gbs"] * 1e9) * 1e3
        out["bounds_menu_ms"] = {
            "t_compute_menu": t_menu,
            "per_axis": per_axis,
            "xfer_all_rdma_serial": xfer_rdma,
            "searched_all_rdma_ideal_overlap": max(t_menu, xfer_rdma),
        }

    # fold in the driver's measured verdict when present (BENCH_r04 written by
    # the driver later; fall back to the most recent bench CSV's finals)
    argv = sys.argv[1:]
    if len(argv) >= 2:
        naive_ms, searched_ms = float(argv[0]), float(argv[1])
        out["driver_measured_ms"] = {"naive": naive_ms, "searched": searched_ms}
        out["fraction_of_achievable"] = {
            "naive": naive_bound * 1e3 / naive_ms,
            "searched": searched_bound * 1e3 / searched_ms,
        }
        if "bounds_menu_ms" in out:
            out["fraction_of_achievable"]["searched_vs_menu_bound"] = (
                out["bounds_menu_ms"]["searched_all_rdma_ideal_overlap"]
                / searched_ms
            )

    path = Path(__file__).parent / "EXTERNAL_BASELINES.json"
    db = json.loads(path.read_text())
    db["entries"] = [e for e in db["entries"] if e.get("workload") != "halo_pipeline"]
    db["entries"].append({"workload": "halo_pipeline", **out})
    path.write_text(json.dumps(db, indent=1))
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
