"""Adapter of ``solve/local.py::hill_climb``.  The lanes, phases and prefer
function are the configuration's (``ctx.hints``); the mix gives the options;
the climb's seed is the run's ``--seed``."""

from __future__ import annotations


def run(ctx, params: dict):
    from tenzing_tpu.bench.benchmarker import BenchOpts
    from tenzing_tpu.solve.local import LocalOpts, hill_climb

    opts = LocalOpts(budget=int(params["budget"]),
                     bench_opts=BenchOpts(**params["bench_opts"]),
                     seed=ctx.seed, paired=bool(params["paired"]),
                     verify=ctx.verifier, prefetch=ctx.prefetcher)
    h = ctx.hints
    return hill_climb(ctx.graph, h["platform"], ctx.bench, h["phases"],
                      prefer=h.get("prefer"), opts=opts)
