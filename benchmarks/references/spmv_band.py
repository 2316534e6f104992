"""Plain reference of y = A x for the source's random band matrix.

Imports nothing of the program.  The matrix is made as the source makes it
(``random_band_matrix``: ``nnz`` entries, each at a uniform row and within
``bw`` of the diagonal, columns clipped to the matrix, duplicates kept and
summed by the product), in coordinate form on the host, with the vector x.
The sparsity pattern comes from the configuration's ``pattern_seed`` and the
values and x from the run's seed: the widest row and the number of remote
columns set the program's buffer shapes and its work, and a pattern drawn
anew for every seed would make runs on different seeds compile different
programs and differ by several per cent in iteration time.

The reference product is a float32 segment sum on the device; the comparison is the largest gap between a row of the program's y
and the reference's, as a share of that row's magnitude or of the median
row's, whichever is larger (some rows are all but empty).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

#: the output buffer of one iteration that :func:`check` compares
OUTPUT = "y"

#: set from chip readings, see PERF.md section 2 ("How correct is decided")
Y_GAP_LIMIT = 1e-5


def sizes(config: dict):
    s = config["shapes"]
    m = int(s["m"])
    return m, int(s["nnz_per_row"]) * m, int(s["band_width"])


def make_data(config: dict, seed: int) -> dict:
    """``rows, cols, vals`` (coordinate form) and ``x``, on the host."""
    m, nnz, bw = sizes(config)
    pat = np.random.default_rng(int(config["shapes"]["pattern_seed"]))
    rows = pat.integers(0, m, size=nnz)
    offs = pat.integers(-bw, bw + 1, size=nnz)
    cols = np.clip(rows + offs, 0, m - 1)
    rng = np.random.default_rng(seed)
    vals = rng.random(nnz, dtype=np.float32)
    x = rng.random(m, dtype=np.float32)
    return {"m": m, "rows": rows, "cols": cols, "vals": vals, "x": x}


@partial(jax.jit, static_argnums=(4, 5))
def _product(rows, cols, vals, x, m, dtype):
    prods = vals.astype(dtype) * x.astype(dtype)[cols]
    return jax.ops.segment_sum(prods, rows, num_segments=m)


@jax.jit
def _gap(y, y_ref):
    y = y.astype(jnp.float32)
    mag = jnp.abs(y_ref)
    scale = jnp.maximum(mag, jnp.median(mag))
    return jnp.max(jnp.abs(y - y_ref) / scale)


def _reference_y(data: dict, dtype=jnp.float32):
    return _product(jnp.asarray(data["rows"], jnp.int32),
                    jnp.asarray(data["cols"], jnp.int32),
                    jnp.asarray(data["vals"]), jnp.asarray(data["x"]),
                    data["m"], dtype)


def precompile(config: dict, like) -> None:
    m, nnz, _ = sizes(config)
    i32 = jax.ShapeDtypeStruct((nnz,), jnp.int32)
    f32 = jax.ShapeDtypeStruct((nnz,), jnp.float32)
    vec = jax.ShapeDtypeStruct((m,), jnp.float32)
    _product.lower(i32, i32, f32, vec, m, jnp.float32).compile()
    _gap.lower(jax.ShapeDtypeStruct(like.shape, like.dtype), vec).compile()


def check(config: dict, seed: int, outputs: dict, data: dict = None) -> list:
    """One number, one fetch: the widest relative gap of a row of y."""
    data = data if data is not None else make_data(config, seed)
    gap = float(_gap(outputs[OUTPUT], _reference_y(data)))
    return [{"name": "spmv_y_widest_gap", "value": gap,
             "limit": Y_GAP_LIMIT}]


def control(config: dict, seed: int) -> dict:
    """The reference in the program's place, one precision down: products
    and sums in bfloat16.  :func:`check` has to refuse it."""
    return {OUTPUT: _reference_y(make_data(config, seed), jnp.bfloat16)}


def sound(config: dict, seed: int) -> dict:
    return {OUTPUT: _reference_y(make_data(config, seed))}
