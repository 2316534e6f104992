"""Builder of one decode step of latent attention over a paged latent cache
on one chip (``models/latent_attention.py`` ``decode_graph``: per layer the
append, the absorb, one engine menu a group of sequences, the
up-projection; the layers in the order of the residual stream).

Every input is made on the device by the plain reference from the seed
(lengths and block table from the configuration); ``qt``, ``o_lat``, ``o``
and the split-K state take the program's shapes (``buffer_shapes``).  Naive
is the unfused, unsearched program on the same kernel body: one lane, every
group a chain of ``mla_fold`` links with the softmax state through HBM
between them (:func:`unfused_prefer`).  The hints give the climb its start
point: every group on the fused ``mla_decode`` kernel (``attn_fused_prefer``:
the menus end in the same suffixes as the prefill's), the layers in order.

``cost`` carries, beside the operations and bytes from lengths and widths,
what each finalist's one-shot program added to the program's counters
``mla.keys_useful`` and ``mla.keys_computed`` while it was traced
(:func:`counted_check`), for ``layer_metrics/mla_padded_key_share.py``.
"""

from __future__ import annotations

from types import SimpleNamespace

from benchmarks.harness.mla_costs import latent_decode_cost

KEYS = ("mla.keys_useful", "mla.keys_computed")


def unfused_prefer(op_name, choices):
    """Naive's menu choices: the split-K chain, each link the kernel."""
    for want in (".chain", ".pallas"):
        hit = next((c for c in choices if c.endswith(want)), None)
        if hit is not None:
            return hit
    return None


def counted_check(check, cost: dict):
    """``check``, noting beside each call what the program's counters
    :data:`KEYS` gained since the call before (``builders/attn_period.py``
    ``counted_check``): ``cost["traced_keys"]`` holds ``[useful,
    computed]`` of naive (whatever the process traced up to then: not
    read) and of each finalist's own one-shot program."""
    from tenzing_tpu.obs.metrics import get_metrics

    def now():
        return [get_metrics().counter(name).value for name in KEYS]

    seen = cost["traced_keys"] = []
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
    from tenzing_tpu.models.latent_attention import (
        LatentDecodeArgs,
        buffer_shapes,
        decode_graph,
    )
    from tenzing_tpu.runtime.executor import TraceExecutor
    from tenzing_tpu.solve.local import drive, phase_policy

    z = reference.sizes(config)
    if len(devices) != 1:
        raise ValueError(f"one chip, handed {len(devices)} device(s)")
    args = LatentDecodeArgs(
        lens=z["lens"], heads=z["heads"], rank=z["rank"], rope=z["rope"],
        nope=z["nope"], v_dim=z["v_dim"], scale=z["scale"], page=z["page"],
        groups=z["groups"], fold_pages=z["fold_pages"], dtype=z["dtype"])
    tags = reference.tags(config)
    bufs = dict(reference.make_data(config, seed))
    for name, (shape, dtype) in buffer_shapes(args, tags).items():
        if name not in bufs:
            bufs[name] = jnp.zeros(shape, dtype)
        elif tuple(bufs[name].shape) != tuple(shape):
            raise ValueError(f"{name}: the reference made {bufs[name].shape},"
                             f" the program wants {shape}")
    lanes = config["lanes"]
    if lanes["executor"] != lanes["solver"]:
        raise ValueError("executor and solver share one platform here")
    platform = Platform.make_n_lanes(int(lanes["executor"]))
    graph = decode_graph(args, tags)
    phases = [f"{tag}." for tag in tags]
    one_lane = Platform.make_n_lanes(1)
    naive, _ = drive(graph, one_lane,
                     phase_policy(one_lane, phases, unfused_prefer))
    cost = latent_decode_cost(z["lens"], z["heads"], z["rank"], z["rope"],
                              z["nope"], z["v_dim"], z["layers"],
                              jnp.dtype(z["dtype"]).itemsize)
    return SimpleNamespace(
        graph=graph, executor=TraceExecutor(platform, bufs), naive=naive,
        hints={"platform": platform, "phases": phases,
               "prefer": attn_fused_prefer},
        check=counted_check(
            lambda out: reference.check(config, seed, out), cost),
        precompile_check=lambda out: reference.precompile(config, out),
        cost=cost)
