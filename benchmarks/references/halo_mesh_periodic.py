"""Plain reference of the periodic halo exchange over a grid of ranks, one
rank a device.

Imports nothing of the program.  The global interior ``(nq, mx*n, my*n,
mz*n)`` is periodic and uniform [0, 1) float32 from the seed; rank ``(i, j,
k)`` of the ``mx x my x mz`` grid holds its block of it inside a local grid
``(nq, n+2r, n+2r, n+2r)`` whose ghost shells start at zero.  One exchange
fills, on each of the three axes, the low ghost shell with the last ``r``
interior planes of the rank before (periodic) and the high ghost shell with
the first ``r`` interior planes of the rank after.  Faces cover interior
extents only in the two other axes: the source exchanges the six faces, not
edges or corners.  A copy has no rounding, so the comparison is exact.

Everything is made and compared per shard: the local grids lie side by side
in one global array ``(nq, mx*(n+2r), my*(n+2r), mz*(n+2r))`` sharded over
the mesh ``("x", "y", "z")``, each device draws its own block (the
generator's values do not depend on the sharding), and no device or host
ever holds more than one rank's grid and faces.
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

#: the output buffer of one iteration that :func:`check` compares
OUTPUT = "U"
AXES = ("x", "y", "z")
SPEC = P(None, *AXES)


def sizes(config: dict):
    s = config["shapes"]
    return (int(s["nq"]), int(s["cells_per_shard"]), int(s["radius"]),
            tuple(int(m) for m in s["mesh"]))


def mesh_of(config: dict) -> Mesh:
    """The reference's own rank grid: the first ``ranks`` devices JAX has."""
    grid = sizes(config)[3]
    return Mesh(np.array(jax.devices()[:int(np.prod(grid))]).reshape(grid),
                AXES)


def _interior(seed, nq, n, grid):
    return jax.random.uniform(jax.random.key(seed),
                              (nq,) + tuple(m * n for m in grid), jnp.float32)


def _shift(x, axis: str, size: int, by: int):
    """``x`` of the rank ``by`` places before this one along ``axis``."""
    if size == 1:
        return x
    return lax.ppermute(x, axis, [(i, (i + by) % size) for i in range(size)])


def _expected_faces(block, r, grid):
    """(ghost slab of the local grid, what one exchange leaves there) for
    the six faces, from this rank's interior ``block`` and its neighbours'."""
    n = block.shape[1]
    mid = slice(r, r + n)
    out = []
    for ax in (1, 2, 3):
        name, size = AXES[ax - 1], grid[ax - 1]
        for ghost, edge, by in ((slice(0, r), slice(n - r, n), 1),
                                (slice(n + r, n + 2 * r), slice(0, r), -1)):
            g = [slice(None), mid, mid, mid]
            e = [slice(None)] * 4
            g[ax], e[ax] = ghost, edge
            out.append((tuple(g), block[tuple(e)], name, size, by))
    return out


@lru_cache(maxsize=None)
def _programs(mesh: Mesh, nq: int, n: int, r: int):
    """``(grid, mismatches, exchange)`` for one mesh and size, each one
    program over the whole mesh with every array sharded rank by rank."""
    grid = tuple(mesh.shape[a] for a in AXES)
    sharded = NamedSharding(mesh, SPEC)
    everywhere = NamedSharding(mesh, P())
    pads = [(0, 0, 0)] + [(r, r, 0)] * 3
    mid = slice(r, r + n)

    def local(fn, n_in, out_spec):
        return jax.shard_map(fn, mesh=mesh, in_specs=(SPEC,) * n_in,
                             out_specs=out_spec)

    @partial(jax.jit, out_shardings=sharded)
    def grid_of(seed):
        return local(lambda b: lax.pad(b, jnp.float32(0), pads), 1, SPEC)(
            _interior(seed, nq, n, grid))

    def count(out_local, block):
        bad = jnp.sum(out_local[:, mid, mid, mid] != block, dtype=jnp.int32)
        for ghost, edge, name, size, by in _expected_faces(block, r, grid):
            bad = bad + jnp.sum(out_local[ghost] != _shift(edge, name, size,
                                                           by),
                                dtype=jnp.int32)
        return lax.psum(bad, AXES)

    @partial(jax.jit, in_shardings=(sharded, everywhere),
             out_shardings=everywhere)
    def mismatches(out_grid, seed):
        return local(count, 2, P())(out_grid, _interior(seed, nq, n, grid))

    def exchanged(u, via):
        for ghost, edge, name, size, by in _expected_faces(
                u[:, mid, mid, mid], r, grid):
            if via is not None:
                edge = edge.astype(via).astype(u.dtype)
            u = u.at[ghost].set(_shift(edge, name, size, by))
        return u

    @partial(jax.jit, static_argnums=1, out_shardings=sharded)
    def exchange(u, via):
        return local(partial(exchanged, via=via), 1, SPEC)(u)

    return grid_of, mismatches, exchange


def _of(config: dict):
    nq, n, r, _ = sizes(config)
    return _programs(mesh_of(config), nq, n, r)


def _seed(seed: int):
    return jnp.uint32(seed & 0xFFFFFFFF)


def make_data(config: dict, seed: int):
    """The initial global grid (ghost shells zero), every rank's local grid
    drawn on its own device."""
    return _of(config)[0](_seed(seed))


def precompile(config: dict, like) -> None:
    """Compile the comparison for an output shaped and placed as ``like``
    (set-up: the persistent cache keeps it, and no run of it is counted as
    set-up)."""
    like = jax.ShapeDtypeStruct(like.shape, like.dtype, sharding=like.sharding)
    _of(config)[1].lower(like, _seed(0)).compile()


def check(config: dict, seed: int, outputs: dict) -> list:
    """Two numbers, one fetch.  Cells of every rank's interior and six ghost
    faces that differ from the reference's: limit 0 (exact: an exchange
    copies).  And ranks whose grid lies on no device of its own: limit 0 (an
    output that never left one chip has all of it on the first)."""
    out = outputs[OUTPUT]
    ranks = int(np.prod(sizes(config)[3]))
    owners = {s.device for s in out.addressable_shards}
    want = NamedSharding(mesh_of(config), SPEC)
    if not out.sharding.is_equivalent_to(want, out.ndim):
        out = jax.device_put(out, want)  # compared where it should have lain
    bad = int(_of(config)[1](out, _seed(seed)))
    return [{"name": "halo_mismatched_cells", "value": bad, "limit": 0},
            {"name": "chips_without_a_shard", "value": ranks - len(owners),
             "limit": 0}]


def control(config: dict, seed: int) -> dict:
    """The reference in the program's place, one precision down: the faces
    travel as bfloat16.  :func:`check` has to refuse it."""
    return {OUTPUT: _of(config)[2](make_data(config, seed), jnp.bfloat16)}


def sound(config: dict, seed: int) -> dict:
    """The reference's own float32 exchange (tests: :func:`check` passes it)."""
    return {OUTPUT: _of(config)[2](make_data(config, seed), None)}
