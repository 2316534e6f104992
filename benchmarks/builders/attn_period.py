"""Builder of one period of a model's attention layers on one chip
(``models/ring_attention.py`` ``BlockedAttention``, one per layer, in the
order of the residual stream).

Q, K and V of every layer are made on the device by the plain reference
from the seed; the softmax state and O take the program's shapes
(``blocked_buffer_shapes``).  The graph is the ``attn`` workload's
(``bench/workloads.py``): per query block the engine menu (per-block chain
against one fused kernel) over the per-fold kernel menu.  Naive is the
unfused, unsearched program on the same kernel: every query block a chain
of per-block ``attn_fold`` folds, the softmax state through HBM between
them, one lane, the layers in order (:func:`unfused_prefer`).  The chain of
XLA folds, which is the first decision the SDP offers, pushes a block's
scores through HBM as well: at 16 384 tokens it reads 267 ms an iteration
and the epilogue's clock alone takes 17.6 s for it (PERF.md, PR 33).  The
hints give the climb its start point: every query block on the fused kernel
(``attn_fused_prefer``), the layers in order.

``cost`` carries, beside the operations and bytes from shapes, what each
finalist's one-shot program added to the program's counters
``attn.pairs_useful`` and ``attn.pairs_computed`` while it was traced
(:func:`counted_check`), for ``layer_metrics/attn_masked_work_share.py``.
"""

from __future__ import annotations

from types import SimpleNamespace

from benchmarks.harness.attn_costs import attention_layers_cost


PAIRS = ("attn.pairs_useful", "attn.pairs_computed")


def unfused_prefer(op_name, choices):
    """Naive's menu choices: the per-block chain, each fold the kernel."""
    for want in (".chain", ".pallas"):
        hit = next((c for c in choices if c.endswith(want)), None)
        if hit is not None:
            return hit
    return None


def counted_check(check, cost: dict):
    """``check``, noting beside each call what the program's counters
    :data:`PAIRS` gained since the call before: ``correct`` runs a
    schedule's one-shot program (tracing a finalist's for the first time,
    which is when the counters count) and hands its outputs here, naive
    first and then the finalists in order.  ``cost["traced_pairs"]`` so
    holds ``[useful, computed]`` of naive (whatever the process traced up
    to then: not read) and of each finalist's own program."""
    from tenzing_tpu.obs.metrics import get_metrics

    def now():
        return [get_metrics().counter(name).value for name in PAIRS]

    seen = cost["traced_pairs"] = []
    last = [0, 0]

    def checked(out):
        nonlocal last
        at = now()
        seen.append([a - b for a, b in zip(at, last)])
        last = at
        return check(out)

    return checked


def build(config: dict, seed: int, devices, reference) -> SimpleNamespace:
    import jax.numpy as jnp

    from tenzing_tpu.bench.workloads import attn_fused_prefer
    from tenzing_tpu.core.platform import Platform
    from tenzing_tpu.models.ring_attention import (
        RingAttnArgs,
        blocked_buffer_shapes,
        period_graph,
    )
    from tenzing_tpu.runtime.executor import TraceExecutor
    from tenzing_tpu.solve.local import drive, phase_policy

    s, z = config["shapes"], reference.sizes(config)
    if len(devices) != 1:
        raise ValueError(f"one chip, handed {len(devices)} device(s)")
    kv_block = int(s["kv_block"])
    if z["n"] % kv_block:
        raise ValueError(f"{z['n']} positions in K/V blocks of {kv_block}")
    layers = [
        (tag, RingAttnArgs(
            n_devices=z["n"] // kv_block, batch=1, seq_local=kv_block,
            head_dim=z["d"], dtype=z["dtype"], heads=z["heads"],
            kv_heads=z["kv_heads"], causal=True, window=window,
            q_block=int(s["q_block"])))
        for tag, window in zip(reference.tags(config), z["windows"])]
    bufs = dict(reference.make_data(config, seed))
    for tag, args in layers:
        for name, (shape, dtype) in blocked_buffer_shapes(args, tag).items():
            bufs.setdefault(name, jnp.zeros(shape, dtype))
    lanes = config["lanes"]
    if lanes["executor"] != lanes["solver"]:
        raise ValueError("executor and solver share one platform here")
    platform = Platform.make_n_lanes(int(lanes["executor"]))
    graph = period_graph(layers, impl_choice=True, fused_choice=True)
    phases = [f"{tag}." for tag, _ in layers]
    one_lane = Platform.make_n_lanes(1)
    naive, _ = drive(graph, one_lane,
                     phase_policy(one_lane, phases, unfused_prefer))
    first = reference.outputs(config)[0]
    cost = attention_layers_cost(
        z["n"], z["windows"], z["heads"], z["kv_heads"], z["d"],
        jnp.dtype(z["dtype"]).itemsize)
    return SimpleNamespace(
        graph=graph, executor=TraceExecutor(platform, bufs), naive=naive,
        hints={"platform": platform, "phases": phases,
               "prefer": attn_fused_prefer},
        check=counted_check(
            lambda out: reference.check(config, seed, out), cost),
        precompile_check=lambda out: reference.precompile(config, out[first]),
        cost=cost)
