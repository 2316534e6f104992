"""Plain reference of the periodic one-rank halo exchange.

Imports nothing of the program.  The grid's interior is made on the device
from the seed; one exchange copies, on each of the three axes, the last
``radius`` interior planes into the low ghost shell and the first ``radius``
interior planes into the high ghost shell (one rank, periodic, so each ghost
shell receives this rank's own opposite interior face).  Faces cover interior
extents only in the two other axes: the source exchanges the six faces, not
edges or corners.  A copy has no rounding, so the comparison is exact.

The grid handed to :func:`check` may be larger than the logical
``(nq, n+2r, n+2r, n+2r)`` (a program may pad it to its tiles): the logical
grid sits at the origin, and cells outside it are not compared.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

#: the output buffer of one iteration that :func:`check` compares
OUTPUT = "U"


def sizes(config: dict):
    s = config["shapes"]
    return int(s["nq"]), int(s["cells_per_rank"]), int(s["radius"])


@partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _grid(seed, nq, n, r, padded):
    """Zero grid of shape ``padded`` whose interior is uniform [0, 1) float32
    drawn from ``seed``: one program, written once, on the device."""
    interior = jax.random.uniform(jax.random.key(seed), (nq, n, n, n),
                                  jnp.float32)
    full = (nq, n + 2 * r, n + 2 * r, n + 2 * r)
    pads = [(0, padded[0] - full[0], 0)] + [
        (r, padded[i] - full[i] + r, 0) for i in (1, 2, 3)]
    return lax.pad(interior, jnp.float32(0), pads)


def make_data(config: dict, seed: int, padded=None):
    """The initial grid (ghost shells zero) on the device.  ``padded`` is
    the allocation's shape where it exceeds the logical grid."""
    nq, n, r = sizes(config)
    full = (nq, n + 2 * r, n + 2 * r, n + 2 * r)
    return _grid(jnp.uint32(seed & 0xFFFFFFFF), nq, n, r,
                 tuple(padded) if padded is not None else full)


def _faces(nq, n, r):
    """(ghost slab, interior slab) index pairs of the six faces, as slices
    into the logical grid."""
    mid = slice(r, r + n)
    out = []
    for ax in (1, 2, 3):
        for ghost, src in ((slice(0, r), slice(n, n + r)),
                           (slice(n + r, n + 2 * r), slice(r, 2 * r))):
            g = [slice(None), mid, mid, mid]
            s = [slice(None), mid, mid, mid]
            g[ax], s[ax] = ghost, src
            out.append((tuple(g), tuple(s)))
    return out


@partial(jax.jit, static_argnums=(2, 3, 4))
def _mismatches(out_grid, seed, nq, n, r):
    interior = jax.random.uniform(jax.random.key(seed), (nq, n, n, n),
                                  jnp.float32)
    mid = slice(r, r + n)
    bad = jnp.sum(out_grid[:, mid, mid, mid] != interior, dtype=jnp.int32)
    for ghost, src in _faces(nq, n, r):
        # src indexes the logical grid; the interior array starts r later
        isrc = tuple(slice(None) if k == 0 else
                     slice(s.start - r, s.stop - r)
                     for k, s in enumerate(src))
        bad = bad + jnp.sum(out_grid[ghost] != interior[isrc],
                            dtype=jnp.int32)
    return bad


def precompile(config: dict, like) -> None:
    """Compile the comparison for an output shaped ``like`` (set-up: the
    persistent cache keeps it, and no run of it is counted as set-up)."""
    nq, n, r = sizes(config)
    _mismatches.lower(jax.ShapeDtypeStruct(like.shape, like.dtype),
                      jnp.uint32(0), nq, n, r).compile()


def check(config: dict, seed: int, outputs: dict) -> list:
    """One number, one fetch: cells of the interior and the six ghost faces
    that differ from the reference's.  Limit 0 (exact: an exchange copies)."""
    nq, n, r = sizes(config)
    bad = int(_mismatches(outputs[OUTPUT], jnp.uint32(seed & 0xFFFFFFFF),
                          nq, n, r))
    return [{"name": "halo_mismatched_cells", "value": bad, "limit": 0}]


@partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _exchange(grid, nq, n, r, via):
    for ghost, src in _faces(nq, n, r):
        face = grid[src]
        if via is not None:
            face = face.astype(via).astype(grid.dtype)
        grid = grid.at[ghost].set(face)
    return grid


def control(config: dict, seed: int, padded=None) -> dict:
    """The reference in the program's place, one precision down: the faces
    travel as bfloat16.  :func:`check` has to refuse it."""
    nq, n, r = sizes(config)
    return {OUTPUT: _exchange(make_data(config, seed, padded), nq, n, r,
                              jnp.bfloat16)}


def sound(config: dict, seed: int, padded=None) -> dict:
    """The reference's own float32 exchange (tests: :func:`check` passes it)."""
    nq, n, r = sizes(config)
    return {OUTPUT: _exchange(make_data(config, seed, padded), nq, n, r, None)}
