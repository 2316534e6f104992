"""Builder of one decode step of learned sparse attention over two paged
caches on one chip (``models/sparse_attention.py`` ``dsa_graph``: per layer
the two appends and the absorb, one chain ``index -> select -> gather ->
read`` a group of sequences, the up-projection; the layers in the order of
the residual stream).

Every input is made on the device by the plain reference from the seed
(lengths and block table from the configuration); ``qt``, ``o_lat``, ``o``,
the scores, the selections and the gathered tiles take the program's shapes
(``buffer_shapes``), the gathered tiles' key limits the program's values.
Naive is the unsearched program: one lane, a selection a group
(:data:`START`, the first entry of ``SparseReadsChoice``'s menu).  The
hints give the climb the same choice on the platform's lanes as its start
point, the layers in order: the search's own finds are whatever it reads
beyond that (a selection a layer, another order, other lanes).

``cost`` carries, beside the operations and bytes from lengths and widths,
what each finalist's one-shot program added to the program's counters
``dsa.select_candidates`` and ``dsa.select_candidates_padded`` while it was
traced (:func:`counted_check`), for
``layer_metrics/dsa_select_padded_share.py``.
"""

from __future__ import annotations

from types import SimpleNamespace

from benchmarks.harness.dsa_costs import sparse_decode_cost

KEYS = ("dsa.select_candidates", "dsa.select_candidates_padded")
#: how far a selection reaches in naive and at the climb's start point: its
#: group (step 1's reading on the chip, PERF.md section 6, PR 40)
START = ".by_group"


def start_prefer(op_name, choices):
    """Naive's and the start point's menu choices: :data:`START` where a
    menu has it."""
    return next((c for c in choices if c.endswith(START)), None)


def counted_check(check, cost: dict):
    """``check``, noting beside each call what the program's counters
    :data:`KEYS` gained since the call before (``builders/mla_decode.py``
    ``counted_check``): ``cost["traced_candidates"]`` holds ``[visible,
    handed]`` of naive (whatever the process traced up to then: not read)
    and of each finalist's own one-shot program."""
    from tenzing_tpu.obs.metrics import get_metrics

    def now():
        return [get_metrics().counter(name).value for name in KEYS]

    seen = cost["traced_candidates"] = []
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

    from tenzing_tpu.core.platform import Platform
    from tenzing_tpu.models.latent_attention import LatentDecodeArgs
    from tenzing_tpu.models.sparse_attention import (
        SparseDecodeArgs,
        buffer_shapes,
        dsa_graph,
    )
    from tenzing_tpu.runtime.executor import TraceExecutor
    from tenzing_tpu.solve.local import drive, phase_policy

    z = reference.sizes(config)
    if len(devices) != 1:
        raise ValueError(f"one chip, handed {len(devices)} device(s)")
    args = SparseDecodeArgs(
        LatentDecodeArgs(
            lens=z["lens"], heads=z["heads"], rank=z["rank"], rope=z["rope"],
            nope=z["nope"], v_dim=z["v_dim"], scale=z["scale"],
            page=z["page"], groups=z["groups"], dtype=z["dtype"]),
        index_heads=z["index_heads"], index_dim=z["index_dim"],
        topk=z["topk"])
    tags = reference.tags(config)
    bufs = dict(reference.make_data(config, seed))
    bufs["picked"] = jnp.asarray(args.picked, jnp.int32)
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
    graph = dsa_graph(args, tags)
    phases = [f"{tag}." for tag in tags]
    one_lane = Platform.make_n_lanes(1)
    naive, _ = drive(graph, one_lane,
                     phase_policy(one_lane, phases, start_prefer))
    cost = sparse_decode_cost(
        z["lens"], z["heads"], z["rank"], z["rope"], z["nope"], z["v_dim"],
        z["index_heads"], z["index_dim"], z["topk"], z["layers"],
        jnp.dtype(z["dtype"]).itemsize)
    return SimpleNamespace(
        graph=graph, executor=TraceExecutor(platform, bufs), naive=naive,
        hints={"platform": platform, "phases": phases,
               "prefer": start_prefer},
        check=counted_check(
            lambda out: reference.check(config, seed, out), cost),
        precompile_check=lambda out: reference.precompile(config, out),
        cost=cost)
