"""Adapter of ``solve/mcts::explore`` (strategy ``FastMin``): one tree search
on the configuration's lanes, stopped by the deadline.  Where the
configuration gives phases, playouts complete with its phase policy and
prefer function (the driver's informed playouts, ``bench/driver.py``
``mcts_rollout_policy``), a random decision with probability
``rollout_eps`` a step; elsewhere they are uniform.  The stack has its own
cache, so the solver's is off; the search's seed is the run's ``--seed``."""

from __future__ import annotations


def run(ctx, params: dict):
    from tenzing_tpu.bench.benchmarker import BenchOpts
    from tenzing_tpu.solve.local import phase_policy
    from tenzing_tpu.solve.mcts import MctsOpts, explore
    from tenzing_tpu.solve.mcts.strategies import FastMin

    h = ctx.hints
    policy = None
    if h.get("phases"):
        policy = phase_policy(h["platform"], h["phases"], h.get("prefer"))
    opts = MctsOpts(n_iters=int(params["n_iters"]),
                    bench_opts=BenchOpts(**params["bench_opts"]),
                    rollout_policy=policy,
                    rollout_eps=float(params["rollout_eps"]),
                    seed=ctx.seed, cache_benchmarks=False,
                    verify=ctx.verifier, prefetch=ctx.prefetcher)
    return explore(ctx.graph, h["platform"], ctx.bench, opts,
                   strategy=FastMin)
