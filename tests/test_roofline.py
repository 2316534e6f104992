"""Cost models + CallableRunner (VERDICT r2 weak #3: absolute yardsticks)."""

import jax.numpy as jnp

from tenzing_tpu.bench.benchmarker import (
    BenchOpts,
    CallableRunner,
    EmpiricalBenchmarker,
)
from tenzing_tpu.bench.roofline import (
    PEAKS,
    UnknownDeviceError,
    V5E_PEAK_BF16_FLOPS,
    attention_cost,
    halo_cost,
    moe_cost,
    peaks_for,
    spmv_cost,
)


def test_attention_cost_counts_both_matmuls():
    c = attention_cost(batch=2, seq=1024, head_dim=128)
    assert c.flops == 4.0 * 2 * 1024 * 1024 * 128
    assert c.hbm_bytes == 4.0 * 2 * 1024 * 128 * 4
    u = c.utilization(1e-3, peaks_for("TPU v5 lite"))
    assert abs(u["mxu_frac"] - c.flops / 1e-3 / V5E_PEAK_BF16_FLOPS) < 1e-12


def test_no_fraction_of_peak_without_the_devices_row():
    """Peaks are keyed by device_kind: a device without a row is an error,
    and a utilization computed without peaks states rates only — never the
    v5e's fractions under another device's name."""
    import pytest

    assert all(p.source for p in PEAKS.values())
    with pytest.raises(UnknownDeviceError, match="cpu"):
        peaks_for("cpu")
    u = attention_cost(batch=2, seq=1024, head_dim=128).utilization(1e-3)
    assert "mxu_frac" not in u and "hbm_frac" not in u and u["tflops"] > 0


def test_moe_cost_staged_adds_transfer_bytes():
    plain = moe_cost(1024, 64, 256, staged=False)
    staged = moe_cost(1024, 64, 256, staged=True)
    assert plain.flops == staged.flops == 4.0 * 1024 * 64 * 256
    assert plain.xfer_bytes == 0.0
    assert staged.xfer_bytes == 4.0 * 1024 * 64 * 4


def test_halo_cost_is_byte_bound():
    c = halo_cost(nq=3, lx=512, ly=512, lz=512, radius=3)
    assert c.flops == 0.0
    faces = 2 * 3 * (512 * 512 * 3) * 3  # 3 axis pairs x face cells x nq
    assert c.hbm_bytes == 4.0 * faces * 4
    assert c.xfer_bytes == 2.0 * faces * 4


def test_spmv_cost():
    c = spmv_cost(m=1000, nnz=10_000)
    assert c.flops == 20_000


def test_callable_runner_measures_named_fns():
    import jax

    f = jax.jit(lambda x: (x * 2).sum())
    x = jnp.ones((64,))
    emp = EmpiricalBenchmarker(CallableRunner({
        "a": lambda: jax.device_get(f(x)),
        "b": lambda: jax.device_get(f(x + 1)),
    }))
    times = emp.benchmark_batch_times(
        ["a", "b"], BenchOpts(n_iters=3, target_secs=1e-4), seed=0
    )
    assert len(times) == 2 and all(len(ts) == 3 for ts in times)
    res = emp.benchmark("a", BenchOpts(n_iters=3, target_secs=1e-4))
    assert res.pct50 > 0


def test_repeat_callable_runner_one_fence_per_measurement():
    import jax
    from jax import lax

    from tenzing_tpu.bench.benchmarker import RepeatCallableRunner

    calls = []

    def make_run_n():
        from tenzing_tpu.runtime.executor import datatie

        x = jnp.ones((64, 64))
        # datatie keeps the body loop-carried so XLA cannot fold the loop
        f = jax.jit(lambda n: lax.fori_loop(
            0, n, lambda i, a: datatie(x, a).sum(), jnp.zeros(())))

        def run_n(n):
            calls.append(n)
            jax.device_get(f(jnp.int32(n)))

        return run_n

    emp = EmpiricalBenchmarker(RepeatCallableRunner({"k": make_run_n()}))
    res = emp.benchmark("k", BenchOpts(n_iters=3, target_secs=1e-4))
    assert res.pct50 > 0
    # the adaptive floor converges by growing n inside ONE dispatch, not by
    # multiplying fenced calls: every recorded call is a single run_n(n)
    assert len(calls) >= 4  # warmup + 3 iters (+ growth probes)
