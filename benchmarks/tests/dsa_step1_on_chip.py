"""Step 1 of ISSUE 40, the go/no-go of the sparse-decode cell on the chip.

    python benchmarks/tests/dsa_step1_on_chip.py --workload dsv32-dsa-decode.climb --parts
    python benchmarks/tests/dsa_step1_on_chip.py --workload dsv32-dsa-decode.climb --seeds a,b,c [--control] [--quick]

``--parts``: each part of a layer alone at the cell's size, on data of its
own, the device's milliseconds a call from one profiled session (the
``XLA Modules`` line, the median of three calls):

* the index kernel (``dsa_index``) group by group;
* the selection (``models/sparse_attention.py`` ``select_chunks``) on each
  group's rectangle of scores and on the layer's (the two entries of
  ``SparseReadsChoice``'s menu), that both pick the same sets, and the
  shortest group's against ``lax.top_k``;
* the gather of one group's 4 x 2048 rows from three layouts of the latent
  pool: pages as columns ``(576, page)`` (the dense cell's), rows of 640
  (the program's) and two tokens a row of 1152, each into the ``(576,
  2048)`` tile ``mla_decode`` reads;
* ``mla_decode`` over the gathered tiles, group by group.

Without ``--parts``, for each seed the configuration is built as a run
builds it, and for naive and the climb's start point: first call, the
two-point clock, one profiled dispatch by operation kind, the program's
``dsa.*`` counters, ``timed_fence_gap``, ``check`` (and ``--control``: the
reference's float8 control), peak bytes.  One process; not part of a
benchmark run.  Writes ``chiprun_out/dsa_step1[.parts].json``.
"""

import argparse
import json
import os
import shutil
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

COUNTERS = ("dsa.keys_indexed", "dsa.keys_indexed_computed",
            "dsa.select_candidates", "dsa.select_candidates_padded",
            "dsa.rows_gathered", "dsa.appended_rows", "mla.keys_useful",
            "mla.keys_computed", "executor.value_tied_bytes",
            "executor.index_ties")


def module_ms(run, label):
    """``{module name: median device ms a call}`` of what ``run`` dispatches
    under one profiler session."""
    import jax

    from benchmarks.harness import cell as cell_mod
    from benchmarks.harness import trace as trace_mod

    out = os.path.join(ROOT, "benchmarks", "out", "dsa_step1_" + label)
    shutil.rmtree(out, ignore_errors=True)
    cell_mod.start_trace(out)
    try:
        run()
    finally:
        jax.profiler.stop_trace()
    got = {}
    for name, s in trace_mod.module_seconds(trace_mod.load_xplane(out)):
        got.setdefault(name.split("(")[0], []).append(s * 1e3)
    shutil.rmtree(out, ignore_errors=True)
    return {k: statistics.median(v) for k, v in got.items()}


def parts(config, ref) -> dict:
    import jax
    import jax.numpy as jnp

    from tenzing_tpu.models.sparse_attention import (
        SparseDecodeArgs,
        candidates,
        dsa_plan,
        gather_rows,
        select_chunks,
        whole_batch,
    )
    from tenzing_tpu.models.latent_attention import LatentDecodeArgs
    from tenzing_tpu.ops.attention_pallas import (
        NEG,
        dsa_index_pallas,
        mla_decode_pallas,
    )

    z = ref.sizes(config)
    lat = LatentDecodeArgs(
        lens=z["lens"], heads=z["heads"], rank=z["rank"], rope=z["rope"],
        nope=z["nope"], v_dim=z["v_dim"], scale=z["scale"], page=z["page"],
        groups=z["groups"], dtype=z["dtype"])
    args = SparseDecodeArgs(lat, z["index_heads"], z["index_dim"], z["topk"])
    plan = dsa_plan(args)
    dt = jnp.dtype(z["dtype"])
    b, page, w, k = lat.batch, lat.page, lat.width, args.topk
    key = jax.random.key(40, impl="rbg")

    def normal(i, shape, dtype=dt):
        return jax.random.normal(jax.random.fold_in(key, i), shape,
                                 jnp.float32).astype(dtype)

    lens = jnp.asarray(lat.visible, jnp.int32)
    table = jnp.asarray(ref.block_table(z))
    report = {}
    todo = []  # (label, jitted, operands)

    # -- index ---------------------------------------------------------------
    q_i = normal(1, (b, args.index_heads, args.index_dim))
    w_i = normal(2, (b, args.index_heads), jnp.float32) * (
        args.index_heads * args.index_dim) ** -0.5
    ki = normal(3, (lat.pool_pages, args.index_dim, page))
    ki_open = normal(4, (b, args.index_dim, page))
    scores = jnp.full((b, 1, lat.max_pages * page), NEG, jnp.float32)
    for grp, _ in plan:
        def index(q, wt, pool, opened, lens, table, scores, grp=grp):
            return dsa_index_pallas(q, wt, pool, opened, lens, table, scores,
                                    lead0=grp.lead0, tiles=grp.tiles)
        index.__name__ = f"index_g{grp.index}"
        f = jax.jit(index)
        scores = f(q_i, w_i, ki, ki_open, lens, table, scores)
        todo.append((index.__name__, f,
                     (q_i, w_i, ki, ki_open, lens, table, scores)))
    jax.block_until_ready(scores)

    # -- select --------------------------------------------------------------
    def oracle(rect):
        return jax.lax.top_k(rect, k)[1]

    picked = {}
    for grp in [g for g, _ in plan] + [whole_batch(plan)]:
        whole = grp.rows == b
        rows = slice(grp.lead0, grp.lead0 + grp.rows)
        have = max(grp.tiles) * page
        seen = jnp.arange(have)[None, :] < lens[rows][:, None]
        rect = jnp.where(seen, scores[rows, 0, :have], NEG)
        rect = jnp.pad(rect, ((0, 0), (0, candidates(args, grp) - have)),
                       constant_values=NEG)

        def one(rect):
            return select_chunks(rect, k)
        one.__name__ = "select_layer" if whole else f"select_g{grp.index}"
        f = jax.jit(one)
        picked[one.__name__] = f(rect)
        todo.append((one.__name__, f, (rect,)))
        if grp.index == 0 and not whole:
            picked["oracle"] = jnp.sort(jax.jit(oracle)(rect), 1)
    by_group = jnp.concatenate(
        [picked[f"select_g{g.index}"] for g, _ in plan])
    same = bool(jnp.array_equal(by_group, picked["select_layer"])) and bool(
        jnp.array_equal(picked["select_g0"], picked["oracle"]))
    report["selections_agree"] = same
    print(f"selections agree: {same}", flush=True)

    # -- gather (the longest group's rows) -------------------------------------
    grp = plan[-1][0]
    rows = slice(grp.lead0, grp.lead0 + grp.rows)
    sel = picked[f"select_g{grp.index}"]
    pool_rows = normal(5, (lat.pool_pages, page, args.row))
    open_rows = normal(6, (grp.rows, page, args.row))

    def gather_rows640(pool, opened, table, lens, sel):
        return gather_rows(pool, opened, table, lens, sel, page, w)

    def gather_columns(pool, opened, table, lens, sel):
        # the dense cell's layout: a page holds its keys as columns
        slot, at = sel // page, sel % page
        page_id = jnp.take_along_axis(table, jnp.clip(
            slot, 0, table.shape[1] - 1), axis=1)
        sealed = jnp.swapaxes(pool[page_id, :, at], 1, 2)
        in_open = jnp.take_along_axis(opened, at[:, None, :], axis=2)
        is_open = slot == ((lens - 1) // page)[:, None]
        return jnp.where(is_open[:, None, :], in_open, sealed)

    def gather_pairs(pool, opened, table, lens, sel):
        # two tokens a row of 1152: no padding, twice the bytes fetched
        slot, at = sel // page, sel % page
        page_id = jnp.take_along_axis(table, jnp.clip(
            slot, 0, table.shape[1] - 1), axis=1)
        sealed = pool.reshape(-1, 2 * w)[(page_id * page + at) // 2]
        in_open = jnp.take_along_axis(opened, (at // 2)[:, :, None], axis=1)
        is_open = slot == ((lens - 1) // page)[:, None]
        pair = jnp.where(is_open[:, :, None], in_open, sealed).reshape(
            grp.rows, k, 2, w)
        got = jnp.where((at % 2 == 1)[:, :, None], pair[:, :, 1], pair[:, :, 0])
        return jnp.swapaxes(got, 1, 2)

    small = (table[rows], lens[rows], sel)
    gathers = [
        (gather_rows640, (pool_rows, open_rows) + small),
        (gather_pairs, (normal(7, (lat.pool_pages, page // 2, 2 * w)),
                        normal(8, (grp.rows, page // 2, 2 * w))) + small),
        (gather_columns, (normal(9, (lat.pool_pages, w, page)),
                          normal(10, (grp.rows, w, page))) + small)]
    for f, operands in gathers:
        todo.append((f.__name__, jax.jit(f), operands))

    # -- read: mla_decode over the gathered tiles --------------------------------
    tiles = normal(11, (b, w, k))
    qt = normal(12, (b, lat.heads, w))
    o_lat = jnp.zeros((b, lat.heads, lat.rank), dt)
    limits = jnp.asarray(args.picked, jnp.int32)
    zeros = jnp.zeros((b, 1), jnp.int32)
    for _, tile in plan:
        def read(qt, tiles, limits, zeros, o_lat, tile=tile):
            return mla_decode_pallas(qt, tiles, tiles, limits, zeros, o_lat,
                                     lat.scale, v_dim=lat.rank,
                                     lead0=tile.lead0, tiles=tile.tiles)
        read.__name__ = f"read_g{tile.index}"
        todo.append((read.__name__, jax.jit(read),
                     (qt, tiles, limits, zeros, o_lat)))

    for label, f, operands in todo:  # compile and run once, then profile
        t0 = time.perf_counter()
        jax.block_until_ready(f(*operands))
        print(f"{label}: first call {time.perf_counter() - t0:.2f} s",
              flush=True)

    def run():
        for _, f, operands in todo:
            for _ in range(3):
                jax.block_until_ready(f(*operands))

    ms = module_ms(run, "parts")
    report["device_ms_a_call"] = {
        label: ms.get("jit_" + label) for label, _, _ in todo}
    for label, v in report["device_ms_a_call"].items():
        print(f"{label}: {v} ms", flush=True)
    report["modules_seen"] = ms
    print(f"modules: {json.dumps(ms)}", flush=True)
    return report


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--parts", action="store_true")
    ap.add_argument("--seeds", default="2147483659,2147483693,2147483713")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--skip-naive", action="store_true")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    import jax

    from benchmarks.harness import cell as cell_mod
    from benchmarks.harness import clock as clock_mod
    from benchmarks.harness import trace as trace_mod
    from tenzing_tpu.bench.compile_cache import enable_compile_cache
    from tenzing_tpu.obs.metrics import get_metrics
    from tenzing_tpu.solve.local import drive, phase_policy

    cell = cell_mod.load_cell(args.workload)
    config = cell.config
    if args.rehearse_cpu:
        config = cell_mod.toy_shapes(config)
    devices = cell_mod.find_devices(cell.chips, args.rehearse_cpu)
    enable_compile_cache(0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cell_mod.persistent_cache(False)  # first calls as the window pays them
    ref = cell_mod.load_module("references", config["reference"])
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    if args.parts:
        report = parts(config, ref)
        with open(os.path.join(ROOT, "chiprun_out", "dsa_step1.parts.json"),
                  "w") as f:
            json.dump(report, f, indent=1)
        return 0 if report["selections_agree"] else 1
    builder = cell_mod.load_module("builders", config["builder"])
    report = {"seeds": {}}
    reg = get_metrics()

    def peak():
        return cell_mod.memory_peak(devices[:1]) / 1e9

    def wall(f, *a):
        t0 = time.perf_counter()
        f(*a)
        return time.perf_counter() - t0

    def counters():
        return {n: reg.counter(n).value for n in COUNTERS}

    def profiled(run_n, n):
        """Device ms an iteration by operation kind, from one profiled
        dispatch at ``n`` repeats and one at 1 (differenced)."""
        out = os.path.join(ROOT, "benchmarks", "out", "dsa_step1_profile")
        per = {}
        for reps in (1, n):
            shutil.rmtree(out, ignore_errors=True)
            cell_mod.start_trace(out)
            try:
                run_n(reps)
            finally:
                jax.profiler.stop_trace()
            plane = trace_mod.device_planes(trace_mod.load_xplane(out))[0]
            ops = {}
            events = trace_mod._line(plane, trace_mod.OPS_LINE)["events"]
            for name, ns in trace_mod.self_times(events).items():
                kind = trace_mod.op_kind(name)
                ops[kind] = ops.get(kind, 0) + ns
            per[reps] = ops
        shutil.rmtree(out, ignore_errors=True)
        ms = {k: (per[n].get(k, 0) - per[1].get(k, 0)) / (n - 1) / 1e6
              for k in per[n]}
        return dict(sorted(ms.items(), key=lambda kv: -kv[1])[:20])

    for at, seed in enumerate(int(s) for s in args.seeds.split(",") if s):
        t0 = time.perf_counter()
        built = builder.build(config, seed, devices, ref)
        ex = built.executor
        ex.init_bufs = cell_mod.committed(ex.init_bufs)
        jax.block_until_ready(ex.init_bufs)
        h = built.hints
        start, _ = drive(built.graph, h["platform"], phase_policy(
            h["platform"], h["phases"], h["prefer"]))
        print(f"seed {seed}: built in {time.perf_counter() - t0:.1f} s, "
              f"peak {peak():.2f} GB, naive {len(built.naive.vector())} ops, "
              f"start point {len(start.vector())} ops, cost "
              f"{json.dumps(built.cost)}", flush=True)
        rows = report["seeds"][str(seed)] = {}

        def one_schedule(order, profile):
            t0 = time.perf_counter()
            before = counters()
            run_n = ex.prepare_n(order)
            row = {"first_call_s": wall(run_n, 1)}
            row["traced_body"] = {k: v - before[k]
                                  for k, v in counters().items()}
            if args.quick:
                t1, t5 = wall(run_n, 1), wall(run_n, 5)
                row.update(iter_ms=(t5 - t1) / 4 * 1e3, n=2)
            else:
                c = clock_mod.two_point(run_n)
                row.update(iter_ms=c["iter_s"] * 1e3,
                           fixed_ms=c["fixed_s"] * 1e3, n=c["n"])
            if profile and not args.rehearse_cpu:
                row["device_ms_an_iteration"] = profiled(run_n, 9)
            row["peak_after_timing_gb"] = peak()
            t1 = time.perf_counter()
            out = ex.run(order)
            jax.block_until_ready(out)
            row["one_shot_first_call_s"] = time.perf_counter() - t1
            t1 = time.perf_counter()
            row["compared"] = {x["name"]: [x["value"], x["limit"]]
                               for x in built.check(out)}
            row["check_s"] = time.perf_counter() - t1
            if hasattr(ref, "readings"):
                row["readings"] = ref.readings(config, seed, out)
            del out
            row["timed_fence_gap"] = cell_mod.timed_fence_gap(
                ex, order, row["n"],
                cell_mod.probe_buffers(ex.init_bufs, seed))
            row["peak_gb"] = peak()
            row["seconds"] = time.perf_counter() - t0
            return row

        todo = [("start", start)] + (
            [] if args.skip_naive else [("naive", built.naive)])
        for label, order in todo:
            try:
                rows[label] = one_schedule(order, profile=at == 0)
            except Exception as e:  # out of memory at a size too large: read on
                rows[label] = {"error": f"{type(e).__name__}: {str(e)[:400]}"}
            print(f"seed {seed} {label}: {json.dumps(rows[label])}",
                  flush=True)
        if args.control:
            out = ref.control(config, seed)
            rows["control"] = {x["name"]: [x["value"], x["limit"]]
                               for x in ref.check(config, seed, out)}
            rows["control_readings"] = ref.readings(config, seed, out)
            print(f"seed {seed} control: {json.dumps(rows['control'])} "
                  f"{json.dumps(rows['control_readings'])}", flush=True)
            del out
        del built, ex
    stats = devices[0].memory_stats() or {}
    report["bytes_limit"] = stats.get("bytes_limit")
    report["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    with open(os.path.join(ROOT, "chiprun_out", "dsa_step1.json"), "w") as f:
        json.dump(report, f, indent=1)
    gaps = [r.get("timed_fence_gap", float("nan"))
            for rows in report["seeds"].values()
            for k, r in rows.items() if k in ("start", "naive")]
    limit, top = report["bytes_limit"] or 0, report["peak_bytes_in_use"] or 0
    print(json.dumps({"largest_fence_gap": max(gaps),
                      "peak_gb": top / 1e9, "limit_gb": limit / 1e9,
                      "free_gb": (limit - top) / 1e9}))
    return 0 if max(gaps) == 0.0 else 1


if __name__ == "__main__":
    sys.exit(main())
