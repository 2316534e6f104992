"""Builder of one decode step of shortcut-connected expert blocks on a mesh
of ranks, one rank a chip (``models/shortcut_moe.py`` ``scmoe_decode_graph``:
latent attention, dense FFNs and an expert block strung through one hidden
state; sequences by rank, experts by rank).

The data are made by the plain reference from the seed, every rank's part
on its own device.  The expert blocks' slot tables are negotiated at set-up
(``scmoe_buffers``), block ``l``'s selection taken from the reference's
float32 forward of the run's data (``router_inputs``: the same picks for
program and reference); a selection beyond capacity raises there.  Executor
and solver share one platform.

Naive is the unsearched program: one lane, the vertices in the residual
stream's written order (the whole expert branch where sublayer 0 computes
it), XLA's all-to-all, every latent group a chain of ``mla_fold`` links.
The hints give the climb its start point: the shortcut discipline as a
phase list (the dense branch between each post and its await), every latent
group on ``mla_decode``, the exchanges on XLA's all-to-all; where the
configuration says ``synth`` the ring of permutes is the other choice of
each exchange's menu.
"""

from __future__ import annotations

from types import SimpleNamespace

from benchmarks.harness.scmoe_costs import scmoe_step_cost

NAIVE = (".fixed", ".chain", ".pallas")
START = (".fixed", ".fused", ".pallas")


def prefer_of(suffixes):
    """A climb policy's menu choices: the first alternative that ends in
    one of ``suffixes``, in their order."""

    def prefer(op_name, choices):
        for want in suffixes:
            hit = next((c for c in choices if c.endswith(want)), None)
            if hit is not None:
                return hit
        return None

    return prefer


def build(config: dict, seed: int, devices, reference) -> SimpleNamespace:
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding

    from tenzing_tpu.core.platform import Platform
    from tenzing_tpu.models import shortcut_moe
    from tenzing_tpu.models.latent_attention import LatentDecodeArgs
    from tenzing_tpu.models.moe import AXIS, MoEArgs
    from tenzing_tpu.obs.metrics import get_metrics
    from tenzing_tpu.runtime.executor import TraceExecutor
    from tenzing_tpu.solve.local import drive, phase_policy

    z = reference.sizes(config)
    if len(devices) != z["ranks"]:
        raise ValueError(f"{z['ranks']} ranks, handed {len(devices)} "
                         "device(s)")
    mla = LatentDecodeArgs(
        lens=z["lens"], heads=z["heads"], rank=z["rank"], rope=z["rope"],
        nope=z["nope"], v_dim=z["v_dim"], scale=z["scale"], page=z["page"],
        groups=z["groups"], fold_pages=z["fold_pages"], dtype=z["dtype"])
    moe = MoEArgs(
        n_ep=z["ranks"], tokens_per_shard=len(z["lens"]), d_model=z["d"],
        d_ff=z["f"], n_chunks=1, dtype=z["dtype"],
        experts_per_shard=z["held"], top_k=z["top_k"], gated=True,
        capacity_factor=z["capacity_factor"], scoring="softmax",
        routed_scale=z["route_scale"], zero_experts=z["zero"],
        gate_in_iteration=True)
    args = shortcut_moe.ScMoEArgs(
        mla=mla, moe=moe, blocks=z["blocks"], q_rank=z["q_rank"],
        ffn=z["ffn"], eps=z["eps"], rope_theta=z["theta"],
        rope_factor=z["factor"], rope_original=z["original"],
        beta_fast=z["beta_fast"], beta_slow=z["beta_slow"])
    mesh = Mesh(np.array(devices), (AXIS,))
    data = reference.make_data(config, seed)
    layout = shortcut_moe.data_layout(args)
    for name, x in data.items():
        shape, dtype, spec = layout[name]
        if (tuple(x.shape) != tuple(shape) or x.dtype != jnp.dtype(dtype)
                or not x.sharding.is_equivalent_to(
                    NamedSharding(mesh, spec), x.ndim)):
            raise ValueError(
                f"{name}: the reference made {x.dtype}{x.shape} under "
                f"{x.sharding}, the program wants {dtype}{shape} under "
                f"{spec} rank by rank on the devices handed to the builder")
    bufs, specs = shortcut_moe.scmoe_buffers(
        args, mesh, data, reference.router_inputs(config, seed),
        synth=z["synth"])
    lanes = config["lanes"]
    if lanes["executor"] != lanes["solver"]:
        raise ValueError("executor and solver share one platform here")
    platform = Platform.make_n_lanes(int(lanes["executor"]), mesh=mesh,
                                     specs=specs)
    graph = shortcut_moe.scmoe_decode_graph(args, synth=z["synth"],
                                            synth_relax=True)
    one_lane = Platform.make_n_lanes(1)
    naive, _ = drive(graph, one_lane, phase_policy(
        one_lane, shortcut_moe.phases(args, shortcut_moe.WRITTEN),
        prefer_of(NAIVE)))
    dropped = get_metrics().counter("moe.dropped_slots")

    def check(out):
        return reference.check(config, seed, out) + [
            {"name": "moe.dropped_slots", "value": dropped.value, "limit": 0}]

    n_router = z["experts"] + z["zero"]
    return SimpleNamespace(
        graph=graph,
        # the one-shot program is the timed loop run once: a straight-line
        # program of this step rounds a value in a million otherwise than
        # the loop's body, which timed_fence_gap reads (PERF.md, PR 46)
        executor=TraceExecutor(platform, bufs, one_shot_as_loop=True),
        naive=naive,
        hints={"platform": platform,
               "phases": shortcut_moe.phases(args, shortcut_moe.SHORTCUT),
               "prefer": prefer_of(START)},
        check=check,
        precompile_check=lambda out: reference.precompile(config, out),
        # one chip's share of the work, against one chip's peaks: a host's
        # real picks (a balanced router: experts of n_router) over its chips
        cost=scmoe_step_cost(
            z["lens"], z["blocks"], z["d"], z["ffn"], z["f"], z["held"],
            n_router, len(z["lens"]) * z["top_k"] * z["experts"] / n_router,
            z["heads"], z["rank"], z["q_rank"], z["rope"], z["nope"],
            z["v_dim"], jnp.dtype(z["dtype"]).itemsize))
