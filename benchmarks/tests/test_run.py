"""The rest of a run without the look for a chip: a sound toy run comes out
correct, a run with the timed path broken underneath comes out not correct,
and the harness walks a configuration sharded over four (virtual) devices."""

import json
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmarks.harness import cell as cell_mod

HERE = Path(__file__).parent.parent
BENCH = json.loads((HERE.parent / "BENCHMARK.json").read_text())


def run(workload, seconds=4.0, trace=False, **kw):
    import jax

    chips = next((w["chips"] for w in BENCH["workloads"]
                  if w["name"] == workload), 1)
    kw.setdefault("devices", jax.devices()[:chips])
    return cell_mod.run_cell(workload, 2**31 + 5, seconds, trace,
                             time.perf_counter(), rehearse=True, **kw)


def test_sound_halo_run_is_correct():
    r = run("halo512.climb", seconds=5.0)
    assert r["correct"] is True
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert set(r["metrics"]) == {"best_iter_ms", "setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())


def test_halo_run_with_a_cell_altered_where_it_is_produced_is_not_correct(
        monkeypatch):
    from tenzing_tpu.models import halo_pipeline

    sound = halo_pipeline.UnpackRecv.apply

    def broken(self, bufs, ctx):
        out = sound(self, bufs, ctx)
        if self.name() == "unpack_my":
            out["U"] = out["U"].at[1, 5, 12, 5].add(1.0)  # a high-y ghost
        return out

    monkeypatch.setattr(halo_pipeline.UnpackRecv, "apply", broken)
    r = run("halo512.climb", seconds=3.0)
    assert r["correct"] is False


@pytest.mark.parametrize("workload", ["halo512.climb", "spmv16k.dfs",
                                      "halo512-mesh4.mcts"])
def test_run_whose_timed_program_returns_its_state_unchanged_is_not_correct(
        monkeypatch, workload):
    """Broken in the repeat-n program only (it never iterates): the
    one-shot program of every schedule is still right, so only the
    comparison of the timed program itself can see it."""
    from tenzing_tpu.runtime.executor import TraceExecutor

    sound = TraceExecutor._stepped_fn

    def never_iterates(self, ops):
        stepped = sound(self, ops)
        return lambda bufs, n: stepped(bufs, n * 0)

    monkeypatch.setattr(TraceExecutor, "_stepped_fn", never_iterates)
    r = run(workload, seconds=3.0)
    assert r["correct"] is False


def test_run_whose_timed_program_skips_an_operation_is_not_correct(
        monkeypatch):
    from tenzing_tpu.runtime.executor import TraceExecutor

    sound = TraceExecutor._stepped_fn

    def skips_one(self, ops):
        kept = [op for op in ops if op.name() != "unpack_my"]
        return sound(self, kept)

    monkeypatch.setattr(TraceExecutor, "_stepped_fn", skips_one)
    r = run("halo512.climb", seconds=3.0)
    assert r["correct"] is False


def test_spmv_run_with_a_low_precision_add_is_not_correct(monkeypatch):
    import jax.numpy as jnp

    from tenzing_tpu.models import spmv

    def bf16_add(self, bufs, ctx):
        a = bufs[self._a].astype(jnp.bfloat16)
        b = bufs[self._b].astype(jnp.bfloat16)
        return {self._out: (a + b).astype(jnp.float32)}

    monkeypatch.setattr(spmv.VectorAdd, "apply", bf16_add)
    r = run("spmv16k.dfs", seconds=3.0)
    assert r["correct"] is False


def test_traced_spmv_run_reports_per_layer_metrics():
    r = run("spmv16k.dfs", seconds=6.0, trace=True)
    assert r["correct"] is True
    # a CPU has no device plane: the trace's readers find nothing and their
    # metrics are left out; the others are there
    assert {"solver_host_share", "speedup_vs_naive", "stack_s_per_eval",
            "dispatch_fixed_ms", "first_call_s_per_eval",
            "naive_iter_ms"} <= set(r["metrics"])
    assert "iter_hbm_roofline" not in r["metrics"]


def test_harness_takes_a_four_device_configuration_as_data():
    import jax

    import toy_mesh_builder

    devices = jax.devices()[:4]
    assert len(devices) == 4
    cell = SimpleNamespace(
        name="toy-mesh4.dfs", chips=4,
        config={"shapes": {"nq": 2, "cells_per_rank": 8, "radius": 2}},
        mix={"solver": "dfs",
             "params": {"max_seqs": 4,
                        "bench_opts": {"n_iters": 2, "target_secs": 1e-4,
                                       "max_retries": 1}}},
        end_to_end=[{"name": n, "unit": u} for n, u in (
            ("evals_per_s", "evals/s"), ("best_iter_ms", "ms"),
            ("setup_s", "s"))],
        per_layer=[])
    r = run("toy-mesh4.dfs", seconds=20.0, devices=devices, cell=cell,
            builder=toy_mesh_builder)
    assert r["correct"] is True
    assert r["device"]["count"] == 4
    assert r["attempted"] >= 2


def test_sound_mesh_run_is_correct_and_measures_both_engines():
    r = run("halo512-mesh4.mcts", seconds=8.0)
    assert r["correct"] is True
    assert r["device"]["count"] == 4 and r["failed"] == 0
    assert set(r["metrics"]) == {"evals_per_s", "best_iter_ms", "setup_s"}
    rec = json.loads((HERE / "out" / f"halo512-mesh4.mcts.seed{2**31 + 5}" /
                      "record.trace0.json").read_text())["record"]
    # the two engine-overlap schedules come first, then the tree search
    assert rec["window"]["n_completed"] >= 3
    assert not any("error" in c for c in rec["window"]["candidates"][:2])
