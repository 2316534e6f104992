"""Builder of one packed prefill step through the mixers of one period of a
Mamba-2 hybrid on one chip (``models/mixers_prefill.py``
``mixers_prefill_graph``: Mamba-2 mixers on the chunked selective-state
scan and one packed grouped-query attention, in the order of the residual
stream).

Every input is made on the device by the plain reference from the seed
(prompt lengths from the configuration); what an iteration writes takes the
program's shapes.  Naive is the unfused, unsearched program: one lane, the
layers in order, every scan the chain of four XLA vertices, every query
block a chain of ``attn_fold`` kernels (``builders/attn_period.py``
``unfused_prefer``: a menu's ``.chain``, then ``.pallas``).  The hints give
the climb its start point: every scan and every query block on its fused
kernel (``attn_fused_prefer``), the layers in order.

``cost`` carries, beside the operations and bytes from shapes
(``harness/mixers_costs.py``), what each schedule's one-shot program added
to the program's counters :data:`COUNTED` while it was traced
(:class:`Counted`, as ``builders/attn_period.py``'s ``counted_check``), for
``layer_metrics/ssd_boundary_chunk_share.py`` and
``layer_metrics/mixers_attn_masked_work_share.py``, and the device's
seconds by operation kind in one dispatch of the start point
(:func:`device_ops_of`), for ``layer_metrics/ssd_scan_roofline.py`` and
``layer_metrics/ssd_scan_device_share.py``.  Set-up runs the start point's
one-shot program for both (:func:`start_point_check`): the program goes
through the persistent cache there, and the epilogue's comparison, which
runs with that cache off, finds it compiled where the start point is a
finalist.
"""

from __future__ import annotations

import tempfile
from types import SimpleNamespace

from benchmarks.builders.attn_period import unfused_prefer
from benchmarks.harness.mixers_costs import mixers_prefill_cost

COUNTED = ("ssd.chunks", "ssd.boundary_chunks", "ssd.fused_vertices",
           "attn.pairs_useful", "attn.pairs_computed")


class Counted:
    """What the program's counters :data:`COUNTED` gain from one call of
    :meth:`gain` to the next: a one-shot program counts while it is traced,
    which is the first time it runs."""

    def __init__(self):
        self.last = dict.fromkeys(COUNTED, 0)

    def gain(self) -> dict:
        from tenzing_tpu.obs.metrics import get_metrics

        at = {name: get_metrics().counter(name).value for name in COUNTED}
        got = {name: at[name] - self.last[name] for name in COUNTED}
        self.last = at
        return got

    def check(self, check, cost: dict):
        """``check``, noting beside each call what the counters gained since
        the call before: ``correct`` runs a schedule's one-shot program and
        hands its outputs here, naive first and then the finalists in order.
        ``cost["traced_counts"]`` so holds ``{counter: gain}`` of naive
        (whatever the process traced up to then: not read) and of each
        finalist's own program; a finalist that gains nothing is the start
        point, which set-up traced: ``cost["start_point_counts"]``."""
        seen = cost["traced_counts"] = []

        def checked(out):
            got = self.gain()
            seen.append(got if any(got.values())
                        else cost.get("start_point_counts"))
            return check(out)

        return checked


def device_ops_of(call) -> list:
    """``[[operation kind, seconds]]``, longest first, of the first device
    in one profiled ``call`` (``harness/trace.py``: an operation's own time,
    its children's taken out; names cut to their kinds).  Empty where the
    profile holds no device plane (a CPU)."""
    import jax

    from benchmarks.harness import trace
    from benchmarks.harness.cell import start_trace

    with tempfile.TemporaryDirectory() as out_dir:
        start_trace(out_dir)
        try:
            call()
        finally:
            jax.profiler.stop_trace()
        planes = trace.device_planes(trace.load_xplane(out_dir))
    ops = {}
    for line in planes[0]["lines"] if planes else ():
        if line["name"] == trace.OPS_LINE:
            for name, ns in trace.self_times(line["events"]).items():
                kind = trace.op_kind(name)
                ops[kind] = ops.get(kind, 0) + ns / 1e9
    return sorted(ops.items(), key=lambda kv: -kv[1])


def start_point_check(executor, start, counted: Counted, cost: dict,
                      precompile):
    """Set-up's last step: the reference and the comparison once on naive's
    outputs (``precompile``), which are then let go (the device holds one
    set of outputs beside the program's own buffers, as in the epilogue);
    the start point's one-shot program run once, which compiles it and
    counts it (``cost["start_point_counts"]``), and once more under the
    profiler (``cost["start_point_ops"]``)."""
    import jax

    def check(out):
        precompile(out)
        out.clear()
        counted.gain()
        run = executor.compile(start)
        jax.block_until_ready(run(executor.init_bufs))
        cost["start_point_counts"] = counted.gain()
        cost["start_point_ops"] = device_ops_of(
            lambda: jax.block_until_ready(run(executor.init_bufs)))

    return check


def step_args(z: dict):
    """``(Mamba2Args, RingAttnArgs)`` of the reference's ``sizes``."""
    from tenzing_tpu.models.mamba2 import Mamba2Args
    from tenzing_tpu.models.ring_attention import RingAttnArgs

    tokens = sum(z["lens"])
    if tokens % z["kv_block"]:
        raise ValueError(f"{tokens} tokens in K/V blocks of {z['kv_block']}")
    mamba = Mamba2Args(
        lens=z["lens"], heads=z["heads"], head_dim=z["head_dim"],
        groups=z["groups"], state=z["state"], taps=z["taps"],
        chunk=z["chunk"], eps=z["eps"], dtype=z["dtype"])
    attn = RingAttnArgs(
        n_devices=tokens // z["kv_block"], batch=1, seq_local=z["kv_block"],
        head_dim=z["attn_head_dim"], dtype=z["dtype"], heads=z["attn_heads"],
        kv_heads=z["kv_heads"], causal=True, q_block=z["q_block"],
        segments=mamba.starts)
    return mamba, attn


def build(config: dict, seed: int, devices, reference) -> SimpleNamespace:
    import jax.numpy as jnp

    from tenzing_tpu.bench.workloads import attn_fused_prefer
    from tenzing_tpu.core.platform import Platform
    try:
        from tenzing_tpu.models.mixers_prefill import (
            buffer_shapes,
            mixers_prefill_graph,
            state_fill,
        )
    except ImportError as e:  # a checkout from before the mixers' graph
        from benchmarks.harness.cell import Refused

        raise Refused(f"this checkout cannot run the configuration: {e}")
    from tenzing_tpu.runtime.executor import TraceExecutor
    from tenzing_tpu.solve.local import drive, phase_policy

    z = reference.sizes(config)
    if len(devices) != 1:
        raise ValueError(f"one chip, handed {len(devices)} device(s)")
    mamba, attn = step_args(z)
    bufs = dict(reference.make_data(config, seed))
    for name, (shape, dtype) in buffer_shapes(mamba, attn,
                                              z["pattern"]).items():
        if name not in bufs:  # what the iteration writes
            bufs[name] = jnp.full(shape, state_fill(name), dtype)
    lanes = config["lanes"]
    if lanes["executor"] != lanes["solver"]:
        raise ValueError("executor and solver share one platform here")
    platform = Platform.make_n_lanes(int(lanes["executor"]))
    graph = mixers_prefill_graph(mamba, attn, z["pattern"])
    phases = [f"{tag}." for _, tag in reference.tags(config)]
    one_lane = Platform.make_n_lanes(1)
    naive, _ = drive(graph, one_lane,
                     phase_policy(one_lane, phases, unfused_prefer))
    start, _ = drive(graph, platform,
                     phase_policy(platform, phases, attn_fused_prefer))
    cost = mixers_prefill_cost(
        z["lens"], z["pattern"], z["heads"], z["head_dim"], z["groups"],
        z["state"], z["taps"], z["chunk"], z["attn_heads"], z["kv_heads"],
        z["attn_head_dim"], jnp.dtype(z["dtype"]).itemsize)
    # the one-shot program is the timed loop run once: XLA fuses naive's
    # straight-line program otherwise than the loop's body, and on one probe
    # in nineteen the two left fences an ulp apart (PERF.md section 6, PR 50)
    executor = TraceExecutor(platform, bufs, one_shot_as_loop=True)
    counted = Counted()
    return SimpleNamespace(
        graph=graph, executor=executor, naive=naive,
        hints={"platform": platform, "phases": phases,
               "prefer": attn_fused_prefer},
        check=counted.check(
            lambda out: reference.check(config, seed, out), cost),
        precompile_check=start_point_check(
            executor, start, counted, cost,
            lambda out: reference.precompile(config, seed, out)),
        cost=cost)
