"""Builder of one decode step of one period of a linear-attention hybrid on
one chip (``models/delta_attention.py`` ``hybrid_decode_graph``: KDA layers
on a recurrent state, one engine menu a (layer, group) of sequences, and
``models/latent_attention.py``'s latent-attention layer, in the order of the
residual stream).

Every input is made on the device by the plain reference from the seed
(lengths and block table from the configuration); the written state
(``Snew``, ``Cvnew``), every ``o`` and the engines' intermediates take the
program's shapes (both modules' ``buffer_shapes``).  Naive is the unfused,
unsearched program: one lane, every KDA group the chain of the four XLA
vertices (the state through HBM four times) and every latent group a chain
of ``mla_fold`` links (``builders/mla_decode.py`` ``unfused_prefer``: its
suffixes are this graph's too).  The
hints give the climb its start point: every (layer, group) on its fused
kernel (``attn_fused_prefer``: the menus end in the same suffixes), the
layers in order.

``cost`` carries, beside the operations and bytes from shapes
(``harness/kda_costs.py``), what each finalist's one-shot program added to
the program's ``kda.*`` counters while it was traced
(:func:`counted_check`): the run's record keeps them, and
``layer_metrics/kda_state_excess_share.py`` reads the first two.
"""

from __future__ import annotations

from types import SimpleNamespace

from benchmarks.builders.mla_decode import unfused_prefer
from benchmarks.harness.kda_costs import hybrid_decode_cost

KEYS = ("kda.state_bytes", "kda.state_min_bytes", "kda.fused_vertices",
        "kda.chain_vertices", "kda.rows")


def counted_check(check, cost: dict):
    """``check``, noting beside each call what the program's counters
    :data:`KEYS` gained since the call before (``builders/mla_decode.py``
    ``counted_check``): ``cost["traced_kda"]`` holds ``[bytes moved, least
    bytes, fused vertices, chain vertices, sequences stepped]`` of naive
    (whatever the process traced up to then: not read) and of each
    finalist's own one-shot program, so all five are in the run's record."""
    from tenzing_tpu.obs.metrics import get_metrics

    def now():
        return [get_metrics().counter(name).value for name in KEYS]

    seen = cost["traced_kda"] = []
    last = [0] * len(KEYS)

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
    from tenzing_tpu.models import delta_attention, latent_attention
    from tenzing_tpu.runtime.executor import TraceExecutor
    from tenzing_tpu.solve.local import drive, phase_policy

    z = reference.sizes(config)
    if len(devices) != 1:
        raise ValueError(f"one chip, handed {len(devices)} device(s)")
    kda = delta_attention.DeltaDecodeArgs(
        batch=len(z["lens"]), heads=z["kda_heads"], d=z["d"],
        taps=z["taps"], groups=z["kda_groups"], eps=z["eps"],
        dtype=z["dtype"])
    mla = latent_attention.LatentDecodeArgs(
        lens=z["lens"], heads=z["heads"], rank=z["rank"], rope=z["rope"],
        nope=z["nope"], v_dim=z["v_dim"], scale=z["scale"], page=z["page"],
        groups=z["groups"], fold_pages=z["fold_pages"], dtype=z["dtype"])
    pattern = reference.tags(config)
    of = {kind: [t for k, t in pattern if k == kind]
          for kind in ("kda", "mla")}
    bufs = dict(reference.make_data(config, seed))
    wanted = {**delta_attention.buffer_shapes(kda, of["kda"]),
              **latent_attention.buffer_shapes(mla, of["mla"])}
    for name, (shape, dtype) in wanted.items():
        if name not in bufs:
            bufs[name] = jnp.zeros(shape, dtype)
        elif (tuple(bufs[name].shape) != tuple(shape)
              or bufs[name].dtype != jnp.dtype(dtype)):
            raise ValueError(
                f"{name}: the reference made {bufs[name].dtype}"
                f"{bufs[name].shape}, the program wants {dtype}{shape}")
    lanes = config["lanes"]
    if lanes["executor"] != lanes["solver"]:
        raise ValueError("executor and solver share one platform here")
    platform = Platform.make_n_lanes(int(lanes["executor"]))
    graph = delta_attention.hybrid_decode_graph(kda, mla, pattern)
    phases = [f"{tag}." for _, tag in pattern]
    one_lane = Platform.make_n_lanes(1)
    naive, _ = drive(graph, one_lane,
                     phase_policy(one_lane, phases, unfused_prefer))
    cost = hybrid_decode_cost(
        z["lens"], len(of["kda"]), z["kda_heads"], z["d"], z["taps"],
        len(of["mla"]), z["heads"], z["rank"], z["rope"], z["nope"],
        z["v_dim"], jnp.dtype(z["dtype"]).itemsize)
    return SimpleNamespace(
        graph=graph, executor=TraceExecutor(platform, bufs), naive=naive,
        hints={"platform": platform, "phases": phases,
               "prefer": attn_fused_prefer},
        check=counted_check(
            lambda out: reference.check(config, seed, out), cost),
        precompile_check=lambda out: reference.precompile(config, out),
        cost=cost)
