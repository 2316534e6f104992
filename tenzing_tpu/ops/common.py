"""Shared helpers for the Pallas kernels."""

from __future__ import annotations

import jax


def out_struct(shape, dtype, *like):
    """ShapeDtypeStruct carrying the union of the inputs' varying-across-mesh
    (vma) annotation — required for pallas_call under shard_map."""
    vma = frozenset()
    for a in like:
        vma = vma | jax.typeof(a).vma
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
