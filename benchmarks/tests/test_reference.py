"""The plain references refuse what they must: one wrong ghost cell, a
bfloat16 round trip of the faces, a bfloat16 SpMV."""

import json
from pathlib import Path

import jax.numpy as jnp
import pytest

from benchmarks.harness.cell import load_module, toy_shapes

CONFIGS = Path(__file__).parent.parent / "configs"


def toy(name):
    return toy_shapes(json.loads((CONFIGS / f"{name}.json").read_text()))


HALO = toy("halo512")
SPMV = toy("spmv16k")
halo = load_module("references", "halo_periodic")
spmv = load_module("references", "spmv_band")


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11])
def test_halo_sound_exchange_passes(seed):
    (c,) = halo.check(HALO, seed, halo.sound(HALO, seed))
    assert c["value"] == 0 and c["limit"] == 0


def test_halo_padded_allocation_passes():
    out = halo.sound(HALO, 3, padded=(3, 14, 16, 128))
    assert out["U"].shape == (3, 14, 16, 128)
    assert halo.check(HALO, 3, out)[0]["value"] == 0


@pytest.mark.parametrize("where", [(0, 0, 5, 5), (2, 13, 4, 9), (1, 6, 0, 6),
                                   (1, 6, 13, 6), (0, 7, 7, 1), (0, 7, 7, 12)])
def test_halo_one_wrong_ghost_cell_fails(where):
    out = halo.sound(HALO, 5)
    out["U"] = out["U"].at[where].add(1.0)
    assert halo.check(HALO, 5, out)[0]["value"] == 1


def test_halo_one_wrong_interior_cell_fails():
    out = halo.sound(HALO, 5)
    out["U"] = out["U"].at[1, 6, 6, 6].set(2.0)
    # the cell itself; were it on a face, its ghost copy too
    assert halo.check(HALO, 5, out)[0]["value"] >= 1


def test_halo_unexchanged_grid_fails():
    out = {"U": halo.make_data(HALO, 5)}
    n, r = 8, 3
    assert halo.check(HALO, 5, out)[0]["value"] > 0.9 * 6 * 3 * n * n * r


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_halo_bf16_control_fails(seed):
    (c,) = halo.check(HALO, seed, halo.control(HALO, seed))
    assert c["value"] > c["limit"]
    # nearly every ghost cell moves: a uniform float32 is not a bfloat16
    assert c["value"] > 0.9 * 6 * 3 * 8 * 8 * 3


def test_halo_data_follows_the_seed():
    a, b = halo.make_data(HALO, 1), halo.make_data(HALO, 2)
    assert not bool(jnp.all(a == b))
    assert bool(jnp.all(a == halo.make_data(HALO, 1)))


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 3])
def test_spmv_sound_passes_and_bf16_control_fails(seed):
    (ok,) = spmv.check(SPMV, seed, spmv.sound(SPMV, seed))
    assert ok["value"] <= ok["limit"]
    (bad,) = spmv.check(SPMV, seed, spmv.control(SPMV, seed))
    assert bad["value"] > 30 * bad["limit"]


def test_spmv_one_wrong_row_fails():
    out = spmv.sound(SPMV, 4)
    out["y"] = out["y"].at[17].add(1e-3)
    (c,) = spmv.check(SPMV, 4, out)
    assert c["value"] > c["limit"]
