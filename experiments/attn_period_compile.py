"""No chip: the attention period's repeat-n program compiled for a described
v5e, at the benchmark cell's widths (Trinity-Mini: 32 query heads over 4
key/value heads of 128, window 2048, three window layers and one full).

    JAX_PLATFORMS=cpu python experiments/attn_period_compile.py 16384 start
    JAX_PLATFORMS=cpu python experiments/attn_period_compile.py 32768 naive

Prints what Mosaic and XLA take or refuse, the compile seconds (this
sandbox's CPU), and ``memory_analysis()``: arguments, temporaries.  Nothing
runs: no time, rate or share comes from here.  ``jax.default_backend`` is
patched for the lowering, because the kernels pick the interpreter by it
(a script's business, never an option of the program: on-chip-measurement
guide, section 2).  Not a test: ``tests/test_tpu_compile.py`` is the one
file of tier 1 that may describe a topology.
"""

import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(n: int, which: str) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from tenzing_tpu.bench.workloads import attn_fused_prefer, naive_schedule
    from tenzing_tpu.core.platform import Platform
    from tenzing_tpu.models.ring_attention import (
        RingAttnArgs,
        blocked_buffer_shapes,
        period_graph,
    )
    from tenzing_tpu.runtime.executor import TraceExecutor
    from tenzing_tpu.solve.local import drive, phase_policy

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", False)
    layers = [(f"L{i}", RingAttnArgs(
        n_devices=n // 2048, seq_local=2048, head_dim=128, dtype="bfloat16",
        heads=32, kv_heads=4, causal=True, window=w, q_block=4096))
        for i, w in enumerate((2048, 2048, 2048, None))]
    graph = period_graph(layers, impl_choice=True, fused_choice=True)
    plat = Platform.make_n_lanes(2)
    shapes = {}
    for tag, a in layers:
        shapes.update(blocked_buffer_shapes(a, tag))
    bufs = {k: jax.ShapeDtypeStruct(s, jnp.dtype(d), sharding=dev)
            for k, (s, d) in shapes.items()}
    held = sum(jnp.dtype(d).itemsize * int(np.prod(s))
               for s, d in shapes.values())
    ex = TraceExecutor(plat, bufs)
    if which == "naive":
        order = naive_schedule("attn", graph, None)
    else:
        order, _ = drive(graph, plat, phase_policy(
            plat, [f"{tag}." for tag, _ in layers], attn_fused_prefer))
    jax.default_backend = lambda: "tpu"
    t0 = time.time()
    compiled = jax.jit(ex._stepped_fn(order.vector())).lower(
        bufs, jax.ShapeDtypeStruct((), jnp.int32, sharding=dev)).compile()
    ma = compiled.memory_analysis()
    text = compiled.as_text()
    print(f"{which} at {n}: {len(order.vector())} ops, buffers "
          f"{held / 1e9:.2f} GB, compiled in {time.time() - t0:.1f} s (CPU), "
          f"temporaries {ma.temp_size_in_bytes / 1e9:.2f} GB, "
          f"{text.count('tpu_custom_call')} kernel calls")
    return 0


if __name__ == "__main__":
    sys.exit(main(int(sys.argv[1]), sys.argv[2]))
