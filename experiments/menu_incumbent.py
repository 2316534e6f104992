"""Can the existing menu compose to the 3.4 ms bound? (VERDICT r4 item 1.)

The round-4 verdict: the searched halo winner (12.56 ms) sits at 27% of the
builder's own menu-aware achievable bound (3.39 ms = per-face kernel minima
from experiments/KERNEL_MICROBENCH.json + all-rdma transfers ideally
overlapped).  Three possible answers — the search can't reach the region, the
bound is wrong, or the all-rdma regime has an unmodeled cost — and this
experiment separates them by *constructing the bound's schedule directly*:
per-face argmin kernels, all-rdma engines, paired await/unpack discipline,
driven through the same SDP machinery the solvers use (solve/local.drive +
phase_policy(prefer=...)), then measured as one decorrelated PAIRED batch
against naive (the driver's screen/final protocol, bench.py).

Variants probed: the microbench-argmin map, the flat-kernel map (pallasf
skips the XLA flatten pass where sz%128==0), lane counts {3, 8}, priorities
{phase, paired}.  Results land in experiments/MENU_INCUMBENT.json; whichever
wins becomes the ``greedy-menu-*`` incumbent family in bench.py.

Run on the real chip AFTER any driver bench (host CPU is in the measured
path).
"""

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

# the per-face kernel argmin measured by experiments/kernel_microbench.py
# (KERNEL_MICROBENCH.json, fetch-fenced chain slopes): x-packs per-row,
# y/z-packs batched, x/y-unpacks batched, z-unpacks XLA DUS
MENU_BEST = {
    "pack_px": ".pallas", "pack_mx": ".pallas",
    "pack_py": ".pallasb", "pack_my": ".pallasb",
    "pack_pz": ".pallasb", "pack_mz": ".pallasb",
    "unpack_px": ".pallasb", "unpack_mx": ".pallasb",
    "unpack_py": ".pallasb", "unpack_my": ".pallasb",
    "unpack_pz": ".xla", "unpack_mz": ".xla",
}
# the flat twins where legal (x/y faces): staging emitted/consumed directly
# in the kernel, no separate XLA flatten/unflatten relayout pass — the pass
# PROFILE_WINNER.json records at ~10 ms/iter across the r4 winner's schedule
MENU_FLAT = dict(MENU_BEST)
MENU_FLAT.update({
    "pack_px": ".pallasf", "pack_mx": ".pallasf",
    "pack_py": ".pallasf", "pack_my": ".pallasf",
    "unpack_px": ".pallasf", "unpack_mx": ".pallasf",
    "unpack_py": ".pallasf", "unpack_my": ".pallasf",
})


def mk_prefer(kernel_map, engine=".rdma"):
    def prefer(op_name, choices):
        if op_name.startswith("xfer_"):
            return next((c for c in choices if c.endswith(engine)), None)
        want = kernel_map.get(op_name)
        if want is not None:
            hit = next((c for c in choices if c.endswith(want)), None)
            if hit is not None:
                return hit
        return next((c for c in choices if c.endswith(".xla")), None)

    return prefer


def main() -> int:
    import jax

    from tenzing_tpu.bench.benchmarker import (
        BenchOpts,
        BenchResult,
        EmpiricalBenchmarker,
    )
    from tenzing_tpu.core.platform import Platform
    from tenzing_tpu.models.halo import HaloArgs
    from tenzing_tpu.models.halo_pipeline import (
        HALO_PHASES,
        build_graph,
        host_buffer_names,
        make_pipeline_buffers,
        naive_order,
        paired_priority,
    )
    from tenzing_tpu.runtime.executor import TraceExecutor
    from tenzing_tpu.solve.local import drive, phase_policy
    from tenzing_tpu.utils.numeric import paired_speedup

    hargs = HaloArgs(nq=3, lx=512, ly=512, lz=512, radius=3)
    bufs, _ = make_pipeline_buffers(hargs, seed=0, with_expected=False)
    jbufs = TraceExecutor.place_host_buffers(bufs, host_buffer_names())
    g = build_graph(hargs, impl_choice=True, xfer_choice=True)
    naive_seq = naive_order(hargs, Platform.make_n_lanes(1))

    variants = []
    for label, kmap, nl, pri in (
        ("menu-best-3l", MENU_BEST, 3, None),
        ("menu-best-3l-paired", MENU_BEST, 3, paired_priority("rdma")),
        ("menu-best-8l", MENU_BEST, 8, None),
        ("menu-flat-3l", MENU_FLAT, 3, None),
        ("menu-flat-3l-paired", MENU_FLAT, 3, paired_priority("rdma")),
        ("menu-flat-8l", MENU_FLAT, 8, None),
    ):
        plat = Platform.make_n_lanes(nl)
        seq, _ = drive(g, plat, phase_policy(
            plat, HALO_PHASES, mk_prefer(kmap), priority=pri))
        variants.append((label, seq))

    ex = TraceExecutor(Platform.make_n_lanes(8), jbufs)
    emp = EmpiricalBenchmarker(ex)

    # screen: one decorrelated paired batch, moderate floor (driver screen)
    screen_opts = BenchOpts(n_iters=8, target_secs=0.1, max_retries=2)
    t0 = time.time()
    times = emp.benchmark_batch_times(
        [naive_seq] + [s for _, s in variants], screen_opts, seed=11)
    rows = {}
    for (label, _), ts in zip(variants, times[1:]):
        res = BenchResult.from_times(ts)
        m, lo, hi = paired_speedup(times[0], ts, seed=12)
        rows[label] = {"pct50_ms": res.pct50 * 1e3,
                       "paired_vs_naive": [m, lo, hi]}
        sys.stderr.write(
            f"{label}: pct50={res.pct50*1e3:.3f}ms paired={m:.4f} "
            f"[{lo:.4f},{hi:.4f}]\n")
    naive_res = BenchResult.from_times(times[0])
    out = {
        "device": str(jax.devices()[0]),
        "protocol": "one decorrelated paired batch, n_iters=8, floor 0.1s",
        "naive_pct50_ms": naive_res.pct50 * 1e3,
        "variants": rows,
        "wall_s": round(time.time() - t0, 1),
    }
    path = Path(__file__).parent / "MENU_INCUMBENT.json"
    path.write_text(json.dumps(out, indent=1))
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
