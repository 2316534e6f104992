"""Step 1 of ISSUE 41, on the chip, before the vertex is rewired: how a
group's selected latent rows reach the attention, part by part.

    chiprun -- python experiments/dsa_rows_step1_on_chip.py [--seed N]

At ``dsv32-dsa-decode.climb``'s size, on its lengths, its block table and a
seed's data (layer 0 of the reference's), with the selection the program
itself makes there (``dsa_index`` then ``select_chunks``, group by group),
device milliseconds a call (the ``XLA Modules`` line of one profiled
session, the median of three calls), for each group of 8 x 2048 rows:

* ``tile_dmas1``, ``tile_dmas8``: (a) the DMAs alone, HBM to VMEM, no
  compute, one and eight started a turn of the loop.  Mosaic takes
  no slice of a tiled HBM operand finer than its tile: the pool arrives as
  ``bf16[pages, 2048, 640]`` tiled ``(8,128)(2,1)``, so the finest DMA is 8
  rows (10 KB) and a one-row DMA does not compile (the error is kept in the
  report: ``row_dma_refused``).  What is read here is one such DMA a
  selected row, into a ``(2048 * 8, 640)`` scratch, one wait for them all;
* ``parent``: (c) ``gather_rows`` into ``G`` and ``mla_decode_pallas`` over
  it, the parent's two vertices; ``parent_gather``: its gather alone;
* ``rows``: (b) ``sealed_rows`` (one XLA gather) and ``mla_decode_rows``;
  ``sealed_rows`` and ``rows_kernel``: each alone;
* ``two_gathers``: both of the parent's gathers and its select, as rows, no
  cut to 576 and no transpose: what the transposes and the tile's columns
  cost, apart from the second gather.

Then (b)'s rows of ``o_lat`` against (c)'s.  ``--rehearse-cpu``: toy
shapes, the host's clock, no DMA kernel.  Writes
``chiprun_out/dsa_rows_step1.json``.
"""

import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def tile_dmas(sel, pool, *, lead0: int, rows: int, page: int, tile: int,
              unroll: int = 1):
    """One DMA a selected position of ``rows`` sequences from ``lead0``:
    the ``tile`` rows of the pool that hold it, into a VMEM scratch, and
    one wait a sequence for them all; ``unroll`` DMAs started a turn of
    the loop (what of a DMA's cost is the scalar core's loop).  ``pool``
    is indexed by position as if the table were the identity: the cost of
    a DMA does not depend on which page it reads."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    k, row = sel.shape[1], pool.shape[2]

    def kernel(sel, pool, o_ref, got, sem):
        b = lead0 + pl.program_id(0)

        def start(turn, carry):
            for j in range(unroll):
                j = turn * unroll + j
                at = sel[b, j]
                first = pl.multiple_of(at % page // tile * tile, tile)
                pltpu.make_async_copy(
                    pool.at[at // page % pool.shape[0], pl.ds(first, tile),
                            :],
                    got.at[pl.ds(pl.multiple_of(j * tile, tile), tile), :],
                    sem).start()
            return carry

        jax.lax.fori_loop(0, k // unroll, start, 0)
        pltpu.make_async_copy(got, got, sem).wait()
        o_ref[0] = got[pl.ds(0, 8), :]

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(rows,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, 8, row), lambda i, sel: (i, 0, 0)),
            scratch_shapes=[pltpu.VMEM((k * tile, row), pool.dtype),
                            pltpu.SemaphoreType.DMA]),
        out_shape=jax.ShapeDtypeStruct((rows, 8, row), pool.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=100 << 20),
        name="tile_dmas")(sel, pool)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="dsv32-dsa-decode.climb")
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--rehearse-cpu", action="store_true")
    opts = ap.parse_args()
    import jax
    import jax.numpy as jnp
    from jax import lax

    from benchmarks.harness import cell as cell_mod
    from benchmarks.tests.dsa_step1_on_chip import module_ms
    from tenzing_tpu.models.latent_attention import LatentDecodeArgs
    from tenzing_tpu.models.sparse_attention import (
        NEG,
        SparseDecodeArgs,
        candidates,
        dsa_plan,
        gather_rows,
        sealed_rows,
        select_chunks,
    )
    from tenzing_tpu.ops.attention_pallas import (
        dsa_index_pallas,
        mla_decode_pallas,
        mla_decode_rows_pallas,
    )

    cell = cell_mod.load_cell(opts.workload)
    config = cell.config
    if opts.rehearse_cpu:
        config = cell_mod.toy_shapes(config)
    cell_mod.find_devices(cell.chips, opts.rehearse_cpu)
    ref = cell_mod.load_module("references", config["reference"])
    z = ref.sizes(config)
    lat = LatentDecodeArgs(
        lens=z["lens"], heads=z["heads"], rank=z["rank"], rope=z["rope"],
        nope=z["nope"], v_dim=z["v_dim"], scale=z["scale"], page=z["page"],
        groups=z["groups"], dtype=z["dtype"])
    args = SparseDecodeArgs(lat, z["index_heads"], z["index_dim"], z["topk"])
    plan = dsa_plan(args)
    b, page, w, k = lat.batch, lat.page, lat.width, args.topk
    data = {name.split(".")[0]: x
            for name, x in ref.make_data(config, opts.seed).items()
            if name.endswith(".L0") or "." not in name}
    lens, table = data["lens"], data["table"]
    pool, opened = data["C"], data["Copen"]
    dt = pool.dtype
    report = {"seed": opts.seed, "rows_a_group": lat.batch // lat.groups * k,
              "what": "device ms a call, one group's rows; my chip run, "
                      "PR 41" if not opts.rehearse_cpu else "CPU rehearsal"}

    # the selection the program makes on this data, group by group
    scores = jnp.full((b, 1, lat.max_pages * page), NEG, jnp.float32)
    sel = jnp.zeros((b, k), jnp.int32)
    for grp, _ in plan:
        rows = slice(grp.lead0, grp.lead0 + grp.rows)
        scores = dsa_index_pallas(data["qI"], data["wI"], data["KI"],
                                  data["KIopen"], lens, table, scores,
                                  lead0=grp.lead0, tiles=grp.tiles)
        have, cols = max(grp.tiles) * page, candidates(args, grp)
        seen = jnp.arange(have)[None, :] < lens[rows][:, None]
        rect = jnp.pad(jnp.where(seen, scores[rows, 0, :have], NEG),
                       ((0, 0), (0, cols - have)), constant_values=NEG)
        sel = lax.dynamic_update_slice_in_dim(
            sel, select_chunks(rect, k), grp.lead0, 0)
    in_open = (sel // page == ((lens - 1) // page)[:, None]).sum(axis=1)
    report["selected_in_the_open_page"] = [int(n) for n in in_open]
    key = jax.random.key(41, impl="rbg")
    qt = jax.random.normal(key, (b, lat.heads, w), jnp.float32).astype(dt)
    picked = jnp.asarray(args.picked, jnp.int32)
    zeros = jnp.zeros((b, 1), jnp.int32)
    o_lat = jnp.zeros((b, lat.heads, lat.rank), dt)
    g_buf = jnp.zeros((b, w, k), dt)

    todo, outputs = [], {}
    for grp, tile in plan:
        rows = slice(grp.lead0, grp.lead0 + grp.rows)

        def parent_gather(pool, opened, table, lens, sel, g_buf, rows=rows,
                          grp=grp):
            got = gather_rows(pool, opened[rows], table[rows], lens[rows],
                              sel[rows], page, w)
            return lax.dynamic_update_slice_in_dim(g_buf, got, grp.lead0, 0)

        def parent(qt, pool, opened, table, lens, sel, picked, zeros, g_buf,
                   o_lat, tile=tile, gather=parent_gather):
            tiles = gather(pool, opened, table, lens, sel, g_buf)
            return mla_decode_pallas(qt, tiles, tiles, picked, zeros, o_lat,
                                     lat.scale, v_dim=lat.rank,
                                     lead0=tile.lead0, tiles=tile.tiles)

        def one_gather(pool, table, sel, rows=rows):
            return sealed_rows(pool, table[rows], sel[rows], page)

        def rows_kernel(qt, got, opened, sel, lens, picked, o_lat, grp=grp):
            return mla_decode_rows_pallas(qt, got, opened, sel, lens, picked,
                                          o_lat, lat.scale, v_dim=lat.rank,
                                          lead0=grp.lead0)

        def by_rows(qt, pool, opened, table, lens, sel, picked, o_lat,
                    gather=one_gather, kernel=rows_kernel):
            return kernel(qt, gather(pool, table, sel), opened, sel, lens,
                          picked, o_lat)

        def two_gathers(pool, opened, table, lens, sel, rows=rows):
            at = sel[rows] % page
            got = jnp.take_along_axis(opened[rows], at[:, :, None], axis=1)
            is_open = sel[rows] // page == ((lens[rows] - 1) // page)[:, None]
            return jnp.where(is_open[:, :, None], got,
                             sealed_rows(pool, table[rows], sel[rows], page))

        g = f"_g{grp.index}"
        cache = (pool, opened, table, lens, sel)
        entries = [
            ("parent", parent, (qt,) + cache + (picked, zeros, g_buf, o_lat)),
            ("parent_gather", parent_gather, cache + (g_buf,)),
            ("rows", by_rows, (qt,) + cache + (picked, o_lat)),
            ("sealed_rows", one_gather, (pool, table, sel)),
            ("two_gathers", two_gathers, cache)]
        for label, f, operands in entries:
            f.__name__ = label + g
            todo.append((label + g, jax.jit(f), operands))
        rows_kernel.__name__ = "rows_kernel" + g
        todo.append(("rows_kernel" + g, jax.jit(rows_kernel),
                     (qt, jax.jit(one_gather)(pool, table, sel), opened, sel,
                      lens, picked, o_lat)))
        for unroll in () if opts.rehearse_cpu else (1, 8):
            dmas = functools.partial(tile_dmas, lead0=grp.lead0,
                                     rows=grp.rows, page=page, tile=8,
                                     unroll=unroll)
            dmas.__name__ = f"tile_dmas{unroll}{g}"
            todo.append((dmas.__name__, jax.jit(dmas), (sel, pool)))

    if not opts.rehearse_cpu:
        try:  # the one-row DMA the issue asked for: what Mosaic says to it
            jax.jit(functools.partial(tile_dmas, lead0=0, rows=1, page=page,
                                      tile=1)).lower(sel, pool).compile()
            report["row_dma_refused"] = None
        except Exception as e:  # noqa: BLE001 - the message is the reading
            report["row_dma_refused"] = str(e).split("\n\n")[0][:400]
        print(f"one-row DMA: {report['row_dma_refused']}", flush=True)

    for label, f, operands in todo:  # compile and run once, then profile
        t0 = time.perf_counter()
        outputs[label] = jax.block_until_ready(f(*operands))
        print(f"{label}: first call {time.perf_counter() - t0:.2f} s",
              flush=True)

    def run():
        for _, f, operands in todo:
            for _ in range(3):
                jax.block_until_ready(f(*operands))

    if opts.rehearse_cpu:
        ms = {}
        for label, f, operands in todo:
            t0 = time.perf_counter()
            jax.block_until_ready(f(*operands))
            ms["jit_" + label] = (time.perf_counter() - t0) * 1e3
    else:
        ms = module_ms(run, "rows")
    per = report["device_ms_a_call"] = {
        label: ms.get("jit_" + label) for label, _, _ in todo}
    report["ns_a_row"] = {label: v * 1e6 / report["rows_a_group"]
                          for label, v in per.items() if v is not None}
    for label, v in per.items():
        print(f"{label}: {v} ms", flush=True)
    report["modules_seen"] = ms

    # (b) against (c): the rows of o_lat each wrote
    gaps = {}
    for grp, _ in plan:
        rows = slice(grp.lead0, grp.lead0 + grp.rows)
        got = outputs[f"rows_g{grp.index}"][rows].astype(jnp.float32)
        want = outputs[f"parent_g{grp.index}"][rows].astype(jnp.float32)
        gaps[f"g{grp.index}"] = {
            "max_abs_gap": float(jnp.max(jnp.abs(got - want))),
            "rms_gap_over_rms": float(jnp.sqrt(jnp.mean((got - want) ** 2))
                                      / jnp.sqrt(jnp.mean(want ** 2))),
            "equal": bool(jnp.array_equal(got, want))}
    report["rows_against_parent"] = gaps
    print(json.dumps(gaps), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "dsa_rows_step1.json"),
              "w") as f:
        json.dump(report, f, indent=1)
    ok = all(v["rms_gap_over_rms"] < 1e-2 for v in gaps.values())
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
