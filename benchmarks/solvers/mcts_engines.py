"""The tree search of ``solvers/mcts.py``, after one post-all-before-await-any
schedule for each transfer engine of the configuration
(``models/halo.py::engine_overlap_order``, every exchange on that engine):
the deterministic way to have every engine measured in every run, whatever
the playouts happen to draw.  Each goes through the whole stack, at the
mix's own options, and counts as a candidate."""

from __future__ import annotations


def run(ctx, params: dict):
    from benchmarks.harness.cell import load_module
    from tenzing_tpu.bench.benchmarker import BenchOpts
    from tenzing_tpu.models.halo import engine_overlap_order

    h = ctx.hints
    opts = BenchOpts(**params["bench_opts"])
    for engine in h["engines"]:
        ctx.bench.benchmark(
            engine_overlap_order(ctx.graph, h["platform"], engine), opts)
    return load_module("solvers", "mcts").run(ctx, params)
