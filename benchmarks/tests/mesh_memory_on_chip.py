"""What one chip holds of a sharded cell, at the cell's own size or another.

    python benchmarks/tests/mesh_memory_on_chip.py --workload <cell> [--cells N]

Builds the configuration as a run does and reports the first device's bytes
in use and its peak (which never falls) round three steps, each allowed to
fail with the chip's own message: (1) the probe of ``timed_fence_gap`` as
the harness draws it, shard by shard; (2) the timed program of the first
engine-overlap schedule on that probe beside the one-shot program's outputs,
as ``correct`` holds them; (3) the probe as the harness drew it up to PR 26,
every buffer's global shape on one device (kept here as the record of why it
was repaired).  One process; not part of a benchmark run.
"""

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def old_probe_buffers(bufs, seed):
    import jax
    import jax.numpy as jnp

    key = jax.random.key(seed & 0xFFFFFFFF)
    out = {}
    for i, name in enumerate(sorted(bufs)):
        v = bufs[name]
        if jnp.issubdtype(v.dtype, jnp.floating):
            low = 0 if v.size <= 2 ** 20 else -2
            fill = jax.random.randint(jax.random.fold_in(key, i), v.shape,
                                      low, low + 5).astype(v.dtype)
            v = jax.device_put(fill, v.sharding)
        out[name] = v
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--cells", type=int, default=None)
    ap.add_argument("--seed", type=int, default=2147483929)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    import jax

    from benchmarks.harness import cell as cell_mod

    cell = cell_mod.load_cell(args.workload)
    devices = cell_mod.find_devices(cell.chips, args.rehearse_cpu)
    config = cell.config
    if args.rehearse_cpu:
        config = cell_mod.toy_shapes(config)
    if args.cells:
        config = {**config, "shapes": {**config["shapes"],
                                       "cells_per_shard": args.cells}}
    ref = cell_mod.load_module("references", config["reference"])
    builder = cell_mod.load_module("builders", config["builder"])

    def stats(what):
        s = devices[0].memory_stats() or {}
        print(f"{what}: device 0 holds {s.get('bytes_in_use', 0) / 1e9:.3f} "
              f"GB, peak {s.get('peak_bytes_in_use', 0) / 1e9:.3f} GB of "
              f"{s.get('bytes_limit', 0) / 1e9:.3f} GB", flush=True)

    def step(what, fn):
        try:
            fn()
            print(f"{what}: done", flush=True)
        except Exception as e:  # the chip's own message is the finding
            print(f"{what}: FAILED {type(e).__name__}: {str(e)[:400]}",
                  flush=True)
        stats(f"after {what}")

    built = builder.build(config, args.seed, devices, ref)
    ex = built.executor
    ex.init_bufs = cell_mod.committed(ex.init_bufs)
    jax.block_until_ready(ex.init_bufs)
    print(f"cells a shard {config['shapes']['cells_per_shard']}, buffers "
          f"{sum(v.nbytes for v in ex.init_bufs.values()) / 1e9:.3f} GB "
          f"logical over {len(devices)} device(s)")
    stats("after the builder")

    def repaired():
        jax.block_until_ready(cell_mod.probe_buffers(ex.init_bufs, args.seed))

    def timed_on_probe():
        from tenzing_tpu.models.halo import engine_overlap_order

        order = engine_overlap_order(built.graph, built.hints["platform"],
                                     built.hints["engines"][0])
        ex.prepare_n(order)(1)
        stats("after the timed program's first call on the run's buffers")
        probe = cell_mod.probe_buffers(ex.init_bufs, args.seed)
        gap = cell_mod.timed_fence_gap(ex, order, 1, probe)
        print(f"timed_fence_gap {gap!r}")

    def parents():
        jax.block_until_ready(old_probe_buffers(ex.init_bufs, args.seed))

    step("the probe, drawn shard by shard", repaired)
    step("the timed program on the probe beside the one-shot outputs",
         timed_on_probe)
    step("the probe as drawn up to PR 26, global shapes on one device",
         parents)
    return 0


if __name__ == "__main__":
    sys.exit(main())
