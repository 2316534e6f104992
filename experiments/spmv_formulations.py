"""Which form of the ELL product to land: compile seconds and iteration time.

    chiprun -- python experiments/spmv_formulations.py --out chiprun_out/spmv_formulations.json

For each size m (the benchmark's ``spmv16k`` configuration with ``shapes.m``
set to it, ``band_width`` m/4, data from ``--seed``) the workload is built
once, through the benchmark's own builder, and then for each form of
``y = A x`` over the ELL slab the naive schedule's timed (repeat-n) program
is called for the first time with the persistent compile cache off, as a
window's candidates are.  Read per form: the parts of that first call by the
program's own spans (``executor.lower`` / ``executor.xla_compile`` /
``executor.first_run``), the iteration time by the benchmark's two-point
clock, and (where a second compile is affordable) the widest gap of a row of
y to the plain reference, through the one-shot program.

The forms (PERF.md, PR 26; ``sweep1`` and ``sweep1_rows`` are
``models/spmv.SpMVOp`` as the library has it, the others are patched in here
and live nowhere else; all but the last sweep every matrix row):

* ``rowmajor``  slab ``(m, w)``, ``sum(vals * x[cols], axis=1)``: the form up
  to PR 25;
* ``gather_t``  slab ``(w, m)``, one gather, ``sum(vals_t * x[cols_t], 0)``;
* ``sweep1``    slab ``(w, m)``, a ``fori_loop`` over the w slab rows,
  ``acc + vals_t[j] * x[cols_t[j]]``;
* ``sweep4``, ``sweep8``  the same loop, 4 and 8 slab rows a step (unrolled);
* ``sweep1_pib``  ``sweep1`` with the gather promised in bounds (what the
  clamp and the negative-index select cost; not a candidate to land: an
  index out of bounds would then be undefined);
* ``sweep1_rows``  ``sweep1`` over the row range that holds entries only
  (``A_*_rows`` as ``make_spmv_buffers`` builds them; ``sweep1`` is given
  ``arange(m)`` in their place): what a run of the benchmark executes.

One process; every number is of the device it prints.  ``--rehearse-cpu``
walks the same path at toy size on the CPU (control flow only: no number of
such a run is a device number).
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

SLABS = ("A_loc_vals", "A_loc_cols", "A_rem_vals", "A_rem_cols")
ROWS = ("A_loc_rows", "A_rem_rows")
PARTS = ("executor.lower", "executor.xla_compile", "executor.first_run")
#: forms whose first call at the large size is too long to pay twice: their
#: one-shot program (the check of y) is compiled at the small size only
CHECK_SMALL_ONLY = ("rowmajor",)


def _forms():
    import jax.numpy as jnp
    from jax import lax

    def rowmajor(vals, cols, x):
        return jnp.sum(vals * x[cols], axis=1)

    def gather_t(vals_t, cols_t, x):
        return jnp.sum(vals_t * x[cols_t], axis=0)

    def sweep(unroll, mode=None):
        def f(vals_t, cols_t, x):
            w, m = vals_t.shape

            def column(j, acc):
                return acc + vals_t[j] * x.at[cols_t[j]].get(mode=mode)

            return lax.fori_loop(0, w, column, jnp.zeros((m,), vals_t.dtype),
                                 unroll=unroll)
        return f

    # name -> (product or None for the library's own, slab transposed?)
    return {
        "rowmajor": (rowmajor, False),
        "gather_t": (gather_t, True),
        "sweep1": (None, True),
        "sweep4": (sweep(4), True),
        "sweep8": (sweep(8), True),
        "sweep1_pib": (sweep(1, "promise_in_bounds"), True),
        "sweep1_rows": (None, True),
    }


def _patched_apply(product):
    def apply(self, bufs, ctx):
        return {self._y: product(bufs[self._vals], bufs[self._cols],
                                 bufs[self._x])}
    return apply


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="spmv16k.dfs")
    ap.add_argument("--sizes", default="16384,150000")
    ap.add_argument("--forms", default="")
    ap.add_argument("--seed", type=int, default=2147483801)
    ap.add_argument("--out", default="")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness import cell as cell_mod
    from benchmarks.harness import clock as clock_mod
    from tenzing_tpu.core.platform import Platform
    from tenzing_tpu.models import spmv as spmv_mod
    from tenzing_tpu.obs import tracer as tracer_mod
    from tenzing_tpu.runtime.executor import TraceExecutor

    cell = cell_mod.load_cell(args.workload)
    devices = cell_mod.find_devices(cell.chips, args.rehearse_cpu)
    ref = cell_mod.load_module("references", cell.config["reference"])
    builder = cell_mod.load_module("builders", cell.config["builder"])
    cell_mod.persistent_cache(False)
    tracer = tracer_mod.configure(enabled=True)
    forms = _forms()
    names = [f for f in args.forms.split(",") if f] or list(forms)
    library_apply = spmv_mod.SpMVOp.apply
    sizes = [int(s) for s in args.sizes.split(",") if s]
    device = {"platform": devices[0].platform,
              "device_kind": devices[0].device_kind, "count": len(devices)}
    rows = []
    for m in sizes:
        shapes = {**cell.config["shapes"], "m": m, "band_width": m // 4}
        config = {**cell.config, "shapes": shapes}
        t0 = time.perf_counter()
        built = builder.build(config, args.seed, devices, ref)
        build_s = time.perf_counter() - t0
        base = built.executor.init_bufs
        slabs_t = {k: np.asarray(base[k]) for k in SLABS}  # (w, m), host
        platform = Platform.make_n_lanes(int(config["lanes"]["executor"]))
        for name in names:
            product, transposed = forms[name]
            bufs = dict(base)
            if not transposed:
                for k, v in slabs_t.items():
                    bufs[k] = jnp.asarray(np.ascontiguousarray(v.T))
            if name != "sweep1_rows":
                for k in ROWS:
                    bufs[k] = jnp.arange(m, dtype=jnp.int32)
            spmv_mod.SpMVOp.apply = (library_apply if product is None
                                     else _patched_apply(product))
            try:
                ex = TraceExecutor(platform, bufs)
                tracer.clear()
                run_n = ex.prepare_n(built.naive)
                t1 = time.perf_counter()
                run_n(1)
                first_call_s = time.perf_counter() - t1
                parts = {p: sum(s.t1 - s.t0 for s in tracer.spans()
                                if s.name == p) for p in PARTS}
                c = clock_mod.two_point(run_n, clock=time.perf_counter)
                row = {"m": m, "form": name, "device": device,
                       "slab_shapes": {k: list(bufs[k].shape) for k in SLABS},
                       "rows_swept": {k: int(bufs[k].shape[0]) for k in ROWS},
                       "build_s": build_s, "first_call_s": first_call_s,
                       "lower_s": parts[PARTS[0]],
                       "xla_compile_s": parts[PARTS[1]],
                       "first_run_s": parts[PARTS[2]],
                       "iter_ms": c["iter_s"] * 1e3,
                       "fixed_ms": c["fixed_s"] * 1e3,
                       "iter_ms_rounds": [s * 1e3 for s in c["slopes"]]}
                if m == min(sizes) or name not in CHECK_SMALL_ONLY:
                    (gap,) = built.check(ex.run(built.naive))
                    row[gap["name"]] = gap["value"]
                del ex, run_n
            finally:
                spmv_mod.SpMVOp.apply = library_apply
            rows.append(row)
            print(json.dumps(row), flush=True)
        del built, base
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"seed": args.seed, "workload": args.workload,
                       "jax": jax.__version__, "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
