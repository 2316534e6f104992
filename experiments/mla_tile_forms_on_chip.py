"""What one tile of the latent-decode kernel costs on the chip, by form
(ISSUE 35; PERF.md section 6, PR 35; as PR 33 read the prefill's tilings).

    chiprun -- python experiments/mla_tile_forms_on_chip.py [--pages 64] [--rehearse-cpu]

Eight sequences of ``--pages`` whole pages and a half, DeepSeek-V3's widths
(128 heads, 576-wide pages held as columns, V the first 512 rows), one
kernel call over the group, timed by the host's clock round ``reps`` calls
(a call is a millisecond or more; what a call costs beside its tiles is
read from a call over one sealed page a sequence and taken off).  Forms:

* ``program``: ``ops/attention_pallas.py`` ``mla_decode_pallas`` as the
  cell runs it, at pages of 512, 1024 and 2048 keys: K^T and V^T tiles are
  the MXU's stationary operand, the 128 rows of a sequence stream through;
* ``keys_as_rows``: the same sums with the tile as the streaming operand
  (``s^T = K q^T`` by a product that contracts the page's rows, ``acc^T +=
  V^T p^T``), the 128 rows stationary, the softmax along the sublanes.  Not
  in the program: it would be a second kernel body.  Held to ``program``'s
  output before it is timed.

Prints, per form, microseconds a tile and its share of the tile's roofline
(1 179 648 bytes at 819 GB/s: 1.44 us; 285 MFLOP at 197 TFLOP/s: 1.45 us).
Writes ``chiprun_out/mla_tile_forms.json``.
"""

import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

NEG = -1e30


def keys_as_rows_kernel(scale, page, dv, steps, lens, table, q_ref, k_ref,
                        ko_ref, o_ref, acc_s, m_s, l_s):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    b, t = pl.program_id(0), pl.program_id(1)

    @pl.when(t == 0)
    def _():
        acc_s[...] = jnp.zeros_like(acc_s)
        m_s[...] = jnp.full_like(m_s, NEG)
        l_s[...] = jnp.zeros_like(l_s)

    limit = lens[b]
    open_tile = (limit - 1) // page

    def fold(edge, k_ref):
        kt = k_ref[0]  # (d, page)
        s = jax.lax.dot_general(
            kt, q_ref[0], (((0,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (page, rows)
        if edge:
            seen = t * page + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0) < limit
            s = jnp.where(seen, s, NEG)
        m_old = m_s[...]  # (8, rows), the rows alike
        m_new = jnp.maximum(m_old, jnp.max(s, axis=0, keepdims=True))
        alpha = jnp.exp(m_old - m_new)
        p = jnp.exp(s - m_new[:1])
        if edge:
            p = jnp.where(seen, p, 0.0)
        l_s[...] = l_s[...] * alpha + jnp.sum(p, axis=0, keepdims=True)
        acc_s[...] = acc_s[...] * alpha[:1] + jnp.dot(
            k_ref[0, :dv, :], p.astype(kt.dtype),
            preferred_element_type=jnp.float32)  # (dv, rows)
        m_s[...] = m_new

    pl.when(t < open_tile)(lambda: fold(False, k_ref))
    pl.when(t == open_tile)(lambda: fold(True, ko_ref))

    @pl.when(t == steps - 1)
    def _():
        o_ref[0] = (acc_s[...] / l_s[:1]).astype(o_ref.dtype)


def keys_as_rows(q, pool, k_open, lens, table, scale, dv, steps, interpret):
    """``o_lat^T`` ``(batch, dv, rows)`` of every sequence."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, rows, d = q.shape
    page = pool.shape[2]

    def sealed(b, t, lens, table):
        last = (lens[b] - 1) // page - 1
        return (table[b, jnp.clip(jnp.minimum(t, last), 0,
                                  table.shape[1] - 1)], 0, 0)

    return pl.pallas_call(
        functools.partial(keys_as_rows_kernel, scale, page, dv, steps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(batch, steps),
            in_specs=[pl.BlockSpec((1, rows, d), lambda b, t, *_: (b, 0, 0)),
                      pl.BlockSpec((1, d, page), sealed),
                      pl.BlockSpec((1, d, page), lambda b, t, *_: (b, 0, 0))],
            out_specs=pl.BlockSpec((1, dv, rows), lambda b, t, *_: (b, 0, 0)),
            scratch_shapes=[pltpu.VMEM((dv, rows), jnp.float32),
                            pltpu.VMEM((8, rows), jnp.float32),
                            pltpu.VMEM((8, rows), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((batch, dv, rows), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 << 20),
        name="mla_keys_as_rows", interpret=interpret,
    )(lens, table, q, pool, k_open)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--pages", type=int, default=64)
    ap.add_argument("--reps", type=int, default=8)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tenzing_tpu.ops.attention_pallas import mla_decode_pallas

    toy = args.rehearse_cpu
    rows, d, dv, batch = (8, 24, 16, 2) if toy else (128, 576, 512, 8)
    dt = jnp.float32 if toy else jnp.bfloat16
    scale = 0.135234
    report = {}

    def case(page, pages):
        """Operands of ``batch`` sequences of ``pages`` sealed pages and
        half an open one, the table a permutation of the pool."""
        n = batch * pages
        key = jax.random.key(page, impl="rbg")
        ks = jax.random.split(key, 3)
        pool = jax.random.normal(ks[0], (n, d, page), jnp.float32).astype(dt)
        opened = jax.random.normal(ks[1], (batch, d, page),
                                   jnp.float32).astype(dt)
        q = jax.random.normal(ks[2], (batch, rows, d), jnp.float32).astype(dt)
        table = jnp.asarray(np.random.default_rng(page).permutation(
            n).reshape(batch, pages).astype(np.int32))
        table = jnp.pad(table, ((0, 0), (0, 1)))
        lens = jnp.full((batch,), pages * page + page // 2, jnp.int32)
        return q, pool, opened, lens, table

    def timed(f, *operands):
        jax.block_until_ready(f(*operands))
        best = float("inf")
        for _ in range(args.reps):
            t0 = time.perf_counter()
            jax.block_until_ready(f(*operands))
            best = min(best, time.perf_counter() - t0)
        return best

    def program(steps):
        return jax.jit(lambda q, pool, opened, lens, table, o:
                       mla_decode_pallas(q, pool, opened, lens, table, o,
                                         scale, v_dim=dv, lead0=0,
                                         tiles=(steps,) * batch))

    pages1k = 4 if toy else args.pages
    for page in ((8, 16) if toy else (512, 1024, 2048)):
        pages = pages1k * (8 if toy else 1024) // page
        ops = case(page, pages)
        o = jnp.zeros((batch, rows, dv), dt)
        floor = timed(program(2), *case(page, 1), o)
        t = timed(program(pages + 1), *ops, o)
        report[f"program.page{page}"] = {
            "call_ms": t * 1e3, "one_page_call_ms": floor * 1e3,
            "us_a_1024_key_tile": (t - floor) / (batch * (pages - 1)) * 1e6
            * (1024 / page if not toy else 1)}
        del ops
    page = 8 if toy else 1024
    ops = case(page, pages1k)
    want = program(pages1k + 1)(*ops, jnp.zeros((batch, rows, dv), dt))
    rowsf = jax.jit(lambda q, pool, opened, lens, table, steps=pages1k + 1:
                    keys_as_rows(q, pool, opened, lens, table, scale, dv,
                                 steps, toy))
    got = jnp.swapaxes(rowsf(*ops), 1, 2)
    gap = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                - want.astype(jnp.float32))))
    floorf = jax.jit(lambda q, pool, opened, lens, table:
                     keys_as_rows(q, pool, opened, lens, table, scale, dv, 2,
                                  toy))
    floor = timed(floorf, *case(page, 1))
    t = timed(rowsf, *ops)
    report["keys_as_rows.page1024"] = {
        "call_ms": t * 1e3, "one_page_call_ms": floor * 1e3,
        "us_a_1024_key_tile": (t - floor) / (batch * (pages1k - 1)) * 1e6,
        "largest_gap_to_program": gap}
    for name, row in report.items():
        if not toy:
            row["share_of_tile_roofline"] = 1.4525 / row["us_a_1024_key_tile"]
        print(name, json.dumps(row), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "mla_tile_forms.json"),
              "w") as f:
        json.dump(report, f, indent=1)
    return 0 if gap < (1e-4 if toy else 0.05) else 1


if __name__ == "__main__":
    sys.exit(main())
