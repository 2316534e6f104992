"""Adapter of ``solve/dfs.py::explore``: enumerate up to ``max_seqs``
sequences on the configuration's lanes, drop equivalents, measure every
survivor.  The enumeration runs inside the window."""

from __future__ import annotations


def run(ctx, params: dict):
    from tenzing_tpu.bench.benchmarker import BenchOpts
    from tenzing_tpu.solve.dfs import DfsOpts, explore

    opts = DfsOpts(max_seqs=int(params["max_seqs"]),
                   bench_opts=BenchOpts(**params["bench_opts"]),
                   verify=ctx.verifier, prefetch=ctx.prefetcher)
    return explore(ctx.graph, ctx.hints["platform"], ctx.bench, opts)
