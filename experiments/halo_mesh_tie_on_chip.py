"""ISSUE 29's go/no-go on the chip, and ISSUE 32's step 0: what the mesh
exchange's iteration costs, operation by operation, and which operations
write the ghost shells.

    chiprun --chips 4 -- python experiments/halo_mesh_tie_on_chip.py [--cells N] [--ranks 1]

Parent against change in one call (ISSUE 32): unpack the parent into a
directory ``.gitignore`` lists and measure its program with this script,
then this checkout's, then compare the two reports (no chip in that step):

    git archive <parent> | tar -x -C .bench_checkout/parent
    chiprun --chips 4 -- sh -c 'python experiments/halo_mesh_tie_on_chip.py \
        --root .bench_checkout/parent --label parent && \
      python experiments/halo_mesh_tie_on_chip.py --label change && \
      python experiments/halo_mesh_tie_on_chip.py --compare \
        chiprun_out/halo_mesh_tie.parent.json chiprun_out/halo_mesh_tie.change.json'

Builds ``halo512-mesh4.mcts``'s stack as a run builds it
(``benchmarks/builders/halo_mesh.py``) and, for naive and both
engine-overlap schedules (``engine_overlap_order``, ``xla`` and ``rdma``):

* the iteration time by the benchmark's two-point clock (``prepare_n`` at n
  and 4n, the slope);
* ``timed_fence_gap`` and the one-shot program against the plain reference
  (``halo_mismatched_cells``, ``chips_without_a_shard``), as the harness
  takes them;
* the compiled repeat-n program's temporaries a chip (``memory_analysis``)
  and every operation inside its ``while`` body whose result is a shard's
  whole grid (``obs/attrib/hlo.py``);
* the program's counters ``executor.index_ties``,
  ``executor.value_tied_bytes``, ``halo.window_unpacks`` and
  ``halo.window_packs`` for one traced body;
* a profiled dispatch: the first device's milliseconds an iteration by
  operation, and of them the loop's writes into the grid by the face each
  writes (``writes_ms``: x, y, z; a ``dynamic-update-slice`` or the window
  kernel of ``ops/halo_pallas.py``).

``--ranks 1`` puts one rank on one chip (a 1x1x1 grid: every exchange wraps
onto its own shard), the same slices, updates and tokens a chip at a quarter
of the chip time.  ``--compile-only N`` compiles the first engine-overlap
schedule's repeat-n program at N cells a shard for the attached chips from
shapes alone and reports its temporaries (what decides whether the source's
512^3 fits).  One process; not part of a benchmark run.  Writes
``chiprun_out/halo_mesh_tie[.<label>].json``.

ISSUE 44's step 1, one chip (``--pack``): how a thin face leaves a shard's
``(3, 454, 454, 454)`` grid, each form alone in a ``fori_loop`` whose next
start waits for the face before it (:func:`pack_section`):

    chiprun -- python experiments/halo_mesh_tie_on_chip.py --pack

Its readings are kept in ``experiments/halo_mesh_pack_step0.json``.

ISSUE 47's step 1, one chip (``--unpack``): how a z face enters that grid,
each form alone in a ``fori_loop`` that carries the grid
(:func:`unpack_section`):

    chiprun -- python experiments/halo_mesh_tie_on_chip.py --unpack

Its readings are kept in ``experiments/halo_mesh_unpack_step1.json``.
"""

import argparse
import json
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# z writes together, ms an iteration (ISSUE 32, step 0)
GO_Z_WRITES_MS = 4.0


def grid_ops(compiled, local_shape) -> list:
    from tenzing_tpu.obs.attrib.hlo import loop_ops_of_shape

    shape = "f32[" + ",".join(str(int(x)) for x in local_shape) + "]"
    return [[o.name, o.opcode, list(o.fused)]
            for o in loop_ops_of_shape(compiled.as_text(), shape)]


def grid_writes(compiled, local_shape) -> dict:
    """{operation: thin axis of the face it writes} for the writes into a
    shard's grid inside the loop: a ``dynamic-update-slice``'s update or a
    kernel's last operand, looked up by name for its shape.  A z face that
    enters its kernel turned (PR 47: ``(nq, sx, sz, sy)``) has a y face's
    shape and reads as y here."""
    import re

    from tenzing_tpu.obs.attrib.hlo import loop_ops_of_shape

    text = compiled.as_text()
    types = dict(re.findall(
        r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\w+\[[\d,]*\])", text, re.M))
    shape = "f32[" + ",".join(str(int(x)) for x in local_shape) + "]"
    out = {}
    for o in loop_ops_of_shape(text, shape):
        if o.opcode not in ("dynamic-update-slice", "custom-call"):
            continue
        operands = re.search(
            re.escape(o.name) + r" = .*? " + o.opcode + r"\(([^)]*)\)", text)
        names = re.findall(r"%([\w.\-]+)", operands.group(1))
        update = names[1] if o.opcode == "dynamic-update-slice" else names[-1]
        dims = [int(x) for x in
                types.get(update, "[]").split("[")[1].rstrip("]").split(",")
                if x]
        if len(dims) == 4:
            out[o.name] = "xyz"[min(range(3), key=lambda i: dims[1 + i])]
    return out


def device_ms_by_op(run_n, n: int, top: int = 24) -> list:
    """One profiled dispatch of ``n`` iterations: the first device's
    milliseconds an iteration by operation, nested operations taken out of
    their parents (a ``while`` keeps its own time only)."""
    import jax

    from tenzing_tpu.obs.attrib import xplane

    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            run_n(n)
        finally:
            jax.profiler.stop_trace()
        trace = xplane.load_xplane(d)
    planes = xplane.device_planes(trace)
    if not planes:
        return []
    line = next(ln for ln in planes[0]["lines"]
                if ln["name"] == xplane.OPS_LINE)
    ops = {}
    for a, b, name in xplane.innermost(line["events"]):
        head = name.split(" = ", 1)[0].strip().lstrip("%")
        ops[head] = ops.get(head, 0) + (b - a)
    ranked = sorted(ops.items(), key=lambda kv: -kv[1])
    return [[k, v / 1e6 / n] for k, v in ranked[:top]]


def slope_and_ops(call, n0: int) -> dict:
    """``call(n)`` runs a form's loop of ``n`` repeats to its end: ms a
    repeat by the slope between ``n0`` and ``5 * n0`` (the best of three
    each), and one profiled dispatch's ms a repeat by operation."""
    def timed(n):
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            call(n)
            best = min(best, time.perf_counter() - t0)
        return best

    slope = (timed(5 * n0) - timed(n0)) / (4 * n0) * 1e3
    return {"ms": slope, "device_ms_by_op": device_ms_by_op(
        call, 5 * n0, top=6)}


def _window_padded(u, starts, sizes, tok_zero):
    """ISSUE 44's first form of the window pack, kept here for its reading
    alone: the face leaves the kernel as ``(nq, sx, sy, sz)``, which the
    default layout pads 3 -> 128 lanes when z is the thin axis."""
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from tenzing_tpu.ops.halo_pallas import _shell_block

    nq, sx, sy, sz = sizes
    _, x0, y0, z0 = starts
    _, _, Y, Z = u.shape
    WH, by, yl = _shell_block(y0, sy, Y, 8)
    WW, bz, zl = _shell_block(z0, sz, Z, 128)

    def kernel(tok_ref, u_ref, f_ref):
        f_ref[...] = u_ref[:, yl:yl + sy, zl:zl + sz]

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(sx,),
            in_specs=[pl.BlockSpec(
                (nq, None, WH, WW),
                lambda i, t: (0, x0 + i + t[0], by, bz))],
            out_specs=pl.BlockSpec((nq, None, sy, sz),
                                   lambda i, t: (0, i, 0, 0))),
        out_shape=jax.ShapeDtypeStruct((nq, sx, sy, sz), u.dtype),
        name="halo_window_pack_padded",
    )(tok_zero.reshape(1), u)


def pack_section(cells: int, seed: int, rehearse: bool) -> dict:
    """ISSUE 44, step 1: a thin face's pack alone, ms a face.

    Forms, each as the next thing the program does with the face would see
    it (a value tie, then XLA's choice of layout for a loop carry):
    ``slice`` = ``Pack``'s ``dynamic_slice`` at the token's zero, ``padded``
    = :func:`_window_padded`, ``window`` = ``ops/halo_pallas.py``
    ``pack_face_window``; ``+tie`` adds the executor's value tie onto the
    face, as ``PermuteStart`` and ``RdmaShiftStart`` take their token.  By
    the slope of a ``fori_loop`` between ``n`` and ``5n`` repeats, the
    token's zero of a repeat drawn from the face before it, and by one
    profiled dispatch (the first device's ms a repeat by operation).  Each
    form's face is compared with ``lax.dynamic_slice`` to the bit."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tenzing_tpu.models.halo import (
        DIRECTIONS,
        HaloArgs,
        _face_axis,
        _face_slices,
        dir_name,
    )
    from tenzing_tpu.ops.halo_pallas import _interpret, pack_face_window
    from tenzing_tpu.runtime.executor import datatie

    hargs = HaloArgs(nq=3, lx=cells, ly=cells, lz=cells, radius=3)
    u = jax.random.uniform(jax.random.PRNGKey(seed % (2**31)),
                           hargs.local_shape(), jnp.float32)
    n0 = 2 if rehearse else 8
    out = {"cells_per_shard": cells, "n": [n0, 5 * n0], "faces": {}}

    def forms(d):
        starts, sizes = _face_slices(hargs, d, "pack")
        axis = _face_axis(d)

        def slice_(u, z):
            at = tuple(s + z if i == axis else s
                       for i, s in enumerate(starts))
            return jax.lax.dynamic_slice(u, at, sizes)

        def padded(u, z):
            return _window_padded(u, tuple(starts), tuple(sizes), z)

        def window(u, z):
            return pack_face_window(u, tuple(starts), tuple(sizes), z,
                                    interpret=_interpret())

        def tied(f):
            return lambda u, z: datatie(f(u, z), z.astype(jnp.float32))

        made = {"slice+tie": tied(slice_), "window": window,
                "window+tie": tied(window)}
        if not rehearse:  # the interpreter is slow, and this form is A/B only
            made.update({"padded": padded, "padded+tie": tied(padded)})
        return made, jax.lax.dynamic_slice(u, starts, sizes)

    for d in [d for d in DIRECTIONS if d[0] == 0]:
        made, want = forms(d)
        row = out["faces"][dir_name(d)] = {}
        for label, f in made.items():
            def loop(u, n, f=f):
                def body(_, face):
                    x = face[0, 0, 0, 0]
                    z = jnp.where(x != x, 1, 0).astype(jnp.int32)
                    return f(u, z)

                return jax.lax.fori_loop(0, n, body, jnp.zeros_like(want))

            run = jax.jit(loop)
            face = jax.block_until_ready(run(u, jnp.int32(1)))
            same = bool(jnp.array_equal(face, want))

            row[label] = {"bit_equal": same, **slope_and_ops(
                lambda n: jax.block_until_ready(run(u, jnp.int32(n))), n0)}
            print(f"pack {dir_name(d)} {label}: {json.dumps(row[label])}",
                  flush=True)
    return out


def unpack_section(cells: int, seed: int, rehearse: bool) -> dict:
    """ISSUE 47, step 1: a z face's unpack alone, ms a face.

    The grid is the ``fori_loop``'s carry (the kernel is aliased, so it
    stays in place) and the token's zero of a repeat is drawn from the grid
    the repeat before left.  Forms:

    * ``padded``: ``unpack_face_window`` on the shell's own ``(nq, sx, sy,
      sz)``, the face resident in that (default, 3 -> 128 lanes) layout:
      the parent's kernel alone;
    * ``turned``: the committed kernel on a resident ``(nq, sx, sz, sy)``;
    * ``exchange+padded`` and ``exchange+turned``: the face of every repeat
      is a collective-permute's result (one device sending to itself, the
      zero added onto what it sends, as ``PermuteStart`` ties its token), so
      that XLA lays it out as it does in the cell's program and relayouts it
      inside the loop for whichever operand the kernel pins: the parent's
      whole path from the exchange into the shell, and the change's.

    By the slope of the loop between ``n`` and ``5n`` repeats and by one
    profiled dispatch; each form's grid is compared with
    ``lax.dynamic_update_slice``'s to the bit."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from tenzing_tpu.models.halo import (
        DIRECTIONS,
        HaloArgs,
        _face_slices,
        dir_name,
    )
    from tenzing_tpu.ops.halo_pallas import _interpret, unpack_face_window

    hargs = HaloArgs(nq=3, lx=cells, ly=cells, lz=cells, radius=3)
    mesh = Mesh(np.array(jax.devices()[:1]), ("x",))
    k_u, k_f = jax.random.split(jax.random.PRNGKey(seed % (2**31)))
    u = jax.random.uniform(k_u, hargs.local_shape(), jnp.float32)
    n0 = 2 if rehearse else 8
    out = {"cells_per_shard": cells, "n": [n0, 5 * n0], "faces": {}}

    for d in [d for d in DIRECTIONS if d[2] != 0]:
        starts, sizes = _face_slices(hargs, d, "unpack")
        starts = tuple(starts)
        shell = jax.random.uniform(k_f, sizes, jnp.float32) + 2.0
        want = jax.lax.dynamic_update_slice(u, shell, starts)

        def window(u, face, z, turned):
            return unpack_face_window(u, face, starts, z, turned=turned,
                                      interpret=_interpret())

        def exchanged(f, z):
            return jax.shard_map(
                lambda a: jax.lax.ppermute(a, "x", [(0, 0)]), mesh=mesh,
                in_specs=P(), out_specs=P(), check_vma=False,
            )(f + z.astype(f.dtype))

        forms = {
            "padded": (shell, lambda u, f, z: window(u, f, z, False)),
            "turned": (jnp.swapaxes(shell, 2, 3),
                       lambda u, f, z: window(u, f, z, True)),
            "exchange+padded": (
                shell, lambda u, f, z: window(u, exchanged(f, z), z, False)),
            "exchange+turned": (
                shell, lambda u, f, z: window(
                    u, jnp.swapaxes(exchanged(f, z), 2, 3), z, True)),
        }
        row = out["faces"][dir_name(d)] = {}
        for label, (face, f) in forms.items():
            def loop(u, face, n, f=f):
                def body(_, u):
                    x = u[0, 0, 0, 0]
                    return f(u, face, jnp.where(x != x, 1, 0).astype(
                        jnp.int32))

                return jax.lax.fori_loop(0, n, body, u)

            run = jax.jit(loop)
            face = jax.block_until_ready(face)
            got = jax.block_until_ready(run(u, face, jnp.int32(1)))
            same = bool(jnp.array_equal(got, want))
            del got

            row[label] = {"bit_equal": same, **slope_and_ops(
                lambda n: jax.block_until_ready(run(u, face, jnp.int32(n))),
                n0)}
            print(f"unpack {dir_name(d)} {label}: {json.dumps(row[label])}",
                  flush=True)
        del want
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="halo512-mesh4.mcts")
    ap.add_argument("--cells", type=int, default=None)
    ap.add_argument("--ranks", type=int, default=None)
    ap.add_argument("--seed", type=int, default=2147484101)
    ap.add_argument("--compile-only", type=int, action="append", default=[])
    ap.add_argument("--schedules", default="naive,xla,rdma")
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--root", default=HERE,
                    help="the checkout whose program is measured")
    ap.add_argument("--label", default=None)
    ap.add_argument("--compare", nargs=2, metavar=("PARENT", "CHANGE"))
    ap.add_argument("--pack", action="store_true",
                    help="ISSUE 44's step 1 alone: the pack forms, one chip")
    ap.add_argument("--unpack", action="store_true",
                    help="ISSUE 47's step 1 alone: a z face's unpack forms, "
                    "one chip")
    args = ap.parse_args()
    if args.compare:
        return compare(*args.compare)
    sys.path.insert(0, os.path.abspath(args.root))
    if args.pack or args.unpack:
        section = pack_section if args.pack else unpack_section
        report = section(args.cells or (16 if args.rehearse_cpu else 448),
                         args.seed, args.rehearse_cpu)
        os.makedirs(os.path.join(HERE, "chiprun_out"), exist_ok=True)
        name = "halo_mesh_pack.json" if args.pack else "halo_mesh_unpack.json"
        with open(os.path.join(HERE, "chiprun_out", name), "w") as f:
            json.dump(report, f, indent=1)
        bad = [f"{d}/{k}" for d, row in report["faces"].items()
               for k, r in row.items() if not r["bit_equal"]]
        print(json.dumps({"not_bit_equal": bad}))
        return 1 if bad else 0
    import jax
    import jax.numpy as jnp

    from benchmarks.harness import cell as cell_mod
    from benchmarks.harness import clock as clock_mod
    from tenzing_tpu.models.halo import engine_overlap_order
    from tenzing_tpu.obs.metrics import get_metrics

    cell = cell_mod.load_cell(args.workload)
    config = cell.config
    if args.rehearse_cpu:
        config = cell_mod.toy_shapes(config)
    shapes = dict(config["shapes"])
    chips = cell.chips
    if args.ranks == 1:
        shapes.update(ranks=1, mesh=[1, 1, 1])
        chips = 1
    if args.cells:
        shapes["cells_per_shard"] = args.cells
    config = {**config, "shapes": shapes}
    devices = cell_mod.find_devices(chips, args.rehearse_cpu)
    ref = cell_mod.load_module("references", config["reference"])
    builder = cell_mod.load_module("builders", config["builder"])
    t0 = time.perf_counter()
    built = builder.build(config, args.seed, devices, ref)
    ex = built.executor
    ex.init_bufs = cell_mod.committed(ex.init_bufs)
    jax.block_until_ready(ex.init_bufs)
    n_cells = int(shapes["cells_per_shard"])
    local = (int(shapes["nq"]),) + (n_cells + 2 * int(shapes["radius"]),) * 3
    report = {"device": devices[0].device_kind, "chips": len(devices),
              "cells_per_shard": n_cells, "seed": args.seed, "schedules": {}}
    print(f"{len(devices)} x {devices[0].device_kind}, {n_cells}^3 a shard, "
          f"built in {time.perf_counter() - t0:.1f} s", flush=True)
    plat = built.hints["platform"]
    orders = {"naive": built.naive}
    for engine in built.hints["engines"]:
        orders[engine] = engine_overlap_order(built.graph, plat, engine)
    reg = get_metrics()

    def counters():
        return tuple(reg.counter(name).value for name in (
            "executor.index_ties", "executor.value_tied_bytes",
            "halo.window_unpacks", "halo.window_packs",
            "halo.window_unpacks_turned"))

    for label in [s for s in args.schedules.split(",") if s]:
        order = orders[label]
        t0 = time.perf_counter()
        before = counters()
        stepped = jax.jit(ex._stepped_fn(order.vector()))
        compiled = stepped.lower(ex.init_bufs, jnp.int32(1)).compile()
        ties = [b - a for a, b in zip(before, counters())]
        mem = compiled.memory_analysis()
        row = report["schedules"][label] = {
            "index_ties": ties[0], "value_tied_bytes": ties[1],
            "window_unpacks": ties[2], "window_packs": ties[3],
            "window_unpacks_turned": ties[4],
            "temp_gb": mem.temp_size_in_bytes / 1e9,
            "argument_gb": mem.argument_size_in_bytes / 1e9,
            "grid_ops_in_loop": grid_ops(compiled, local)}
        writes = grid_writes(compiled, local)
        del compiled, stepped
        run_n = ex.prepare_n(order)
        c = clock_mod.two_point(run_n)
        out = ex.run(order)
        compared = built.check(out)
        del out
        gap = cell_mod.timed_fence_gap(
            ex, order, c["n"], cell_mod.probe_buffers(ex.init_bufs, args.seed))
        row.update(iter_ms=c["iter_s"] * 1e3, fixed_ms=c["fixed_s"] * 1e3,
                   n=c["n"], slopes_ms=[s * 1e3 for s in c["slopes"]],
                   timed_fence_gap=gap,
                   compared={x["name"]: [x["value"], x["limit"]]
                             for x in compared},
                   peak_gb=cell_mod.memory_peak(devices[:1]) / 1e9,
                   seconds=time.perf_counter() - t0)
        row["device_ms_per_iter"] = device_ms_by_op(run_n, c["n"], top=40)
        ms = dict(row["device_ms_per_iter"])
        row["writes_ms"] = {axis: sorted(
            (ms.get(name, 0.0) for name, a in writes.items() if a == axis),
            reverse=True) for axis in "xyz"}
        print(f"{label}: {json.dumps(row)}", flush=True)
    del built, ex
    for cells in args.compile_only:
        report.setdefault("compile_only", {})[str(cells)] = compile_only(
            config, cells, devices)
    os.makedirs(os.path.join(HERE, "chiprun_out"), exist_ok=True)
    name = ".".join(x for x in ("halo_mesh_tie", args.label, "json") if x)
    with open(os.path.join(HERE, "chiprun_out", name), "w") as f:
        json.dump(report, f, indent=1)
    bad = [k for k, r in report["schedules"].items()
           if r["timed_fence_gap"] != 0.0
           or any(v > lim for v, lim in r["compared"].values())]
    print(json.dumps({"not_correct": bad}))
    return 1 if bad else 0


def compare(parent_json: str, change_json: str) -> int:
    """ISSUE 32's go/no-go from two reports of this script: the two z
    writes together within ``GO_Z_WRITES_MS``, no whole-grid ``copy`` or
    ``add`` in the loop that the parent's program of the same schedule did
    not have, temporaries not above the parent's.  Exit 1 on a no-go."""
    with open(parent_json) as f:
        parent = json.load(f)["schedules"]
    with open(change_json) as f:
        change = json.load(f)["schedules"]

    def passes(row):
        ops = row["grid_ops_in_loop"]
        return (sum(op == "copy" for _, op, _ in ops),
                sum(op == "add" or "add" in fused for _, op, fused in ops))

    no_go = []
    for label, c in change.items():
        p = parent[label]
        writes = {axis: [sum(p["writes_ms"][axis]), sum(c["writes_ms"][axis])]
                  for axis in "xyz"}
        line = {"schedule": label,
                "iter_ms": [p["iter_ms"], c["iter_ms"]],
                "writes_ms": writes,
                "grid_copies_adds": [passes(p), passes(c)],
                "temp_gb": [p["temp_gb"], c["temp_gb"]],
                "timed_fence_gap": [p["timed_fence_gap"],
                                    c["timed_fence_gap"]],
                "compared": c["compared"]}
        print(json.dumps(line), flush=True)
        # y and z together: a turned z face reads as y (grid_writes)
        if (writes["y"][1] + writes["z"][1] > GO_Z_WRITES_MS
                or any(a > b for a, b in zip(passes(c), passes(p)))
                or c["temp_gb"] > p["temp_gb"]):
            no_go.append(label)
    print(json.dumps({"no_go": no_go}))
    return 1 if no_go else 0


def compile_only(config: dict, cells: int, devices) -> dict:
    """The first engine-overlap schedule's repeat-n program at ``cells`` a
    shard, compiled for ``devices`` from shapes: nothing is allocated."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from tenzing_tpu.core.graph import Graph
    from tenzing_tpu.core.platform import Platform
    from tenzing_tpu.models.halo import (
        DIRECTIONS,
        HaloArgs,
        _face_slices,
        add_to_graph,
        dir_name,
        engine_overlap_order,
    )
    from tenzing_tpu.runtime.executor import TraceExecutor

    s = config["shapes"]
    grid = tuple(int(m) for m in s["mesh"])
    mesh = Mesh(np.array(devices).reshape(grid), ("x", "y", "z"))
    spec = P(None, "x", "y", "z")
    sharded = NamedSharding(mesh, spec)
    hargs = HaloArgs(nq=int(s["nq"]), lx=cells, ly=cells, lz=cells,
                     radius=int(s["radius"]), dtype=s["dtype"])

    def tiled(local):
        return jax.ShapeDtypeStruct(
            (local[0],) + tuple(m * e for m, e in zip(grid, local[1:])),
            jnp.dtype(hargs.dtype), sharding=sharded)

    bufs = {"U": tiled(hargs.local_shape())}
    for d in DIRECTIONS:
        _, sz = _face_slices(hargs, d, "pack")
        bufs[f"buf_{dir_name(d)}"] = tiled(sz)
        bufs[f"recv_{dir_name(d)}"] = tiled(sz)
    plat = Platform.make_n_lanes(int(config["lanes"]["executor"]), mesh=mesh,
                                 specs={k: spec for k in bufs})
    order = engine_overlap_order(
        add_to_graph(Graph(), hargs, xfer_choice=True), plat, s["engines"][0])
    ex = TraceExecutor(plat, bufs)
    n = jax.ShapeDtypeStruct((), jnp.int32,
                             sharding=NamedSharding(mesh, P()))
    out = {}
    try:
        compiled = jax.jit(ex._stepped_fn(order.vector())).lower(
            bufs, n).compile()
        mem = compiled.memory_analysis()
        out = {"temp_gb": mem.temp_size_in_bytes / 1e9,
               "argument_gb": mem.argument_size_in_bytes / 1e9,
               "grid_ops_in_loop": grid_ops(compiled, hargs.local_shape())}
    except Exception as e:  # the chip's own message is the finding
        out = {"failed": f"{type(e).__name__}: {str(e)[:400]}"}
    print(f"compile only, {cells}^3 a shard: {json.dumps(out)}", flush=True)
    return out


if __name__ == "__main__":
    sys.exit(main())
