"""ISSUE 37's step 0 and its A/B: what the slices of Q, K and V cost one
iteration of the attention cell's start-point program on the chip, and what
is left when the fused kernel takes the layer's buffers as they lie.

Parent against change in one call: unpack the parent into a directory
``.gitignore`` lists and measure its program with this script, then this
checkout's with its forms, then compare the two reports (no chip in that
step):

    git archive <parent> | tar -x -C .bench_checkout/parent
    chiprun -- sh -c 'python experiments/attn_operands_on_chip.py \
        --root .bench_checkout/parent --label parent && \
      python experiments/attn_operands_on_chip.py --label change --forms && \
      python experiments/attn_operands_on_chip.py --compare \
        chiprun_out/attn_operands.parent.json \
        chiprun_out/attn_operands.change.json'

On ``attn_finish_on_chip.py``'s pattern (``trinity-attn32k.climb``'s stack
as a run builds it; the benchmark's two-point clock; ``timed_fence_gap`` and
the one-shot program against the plain reference as the harness takes them;
the program's counters for one traced body; one profiled dispatch a
schedule, the device's milliseconds an iteration by operation and by kind),
and besides, per schedule, ``first_call_s``: the seconds of the first call
of its repeat-n program (trace, lower, compile, first run), taken before
anything else has compiled it.

``--forms`` (this checkout's program only): the start point again as
**step 1** of ISSUE 37 alone, ``start.step1``: Q by index map, K and V
sliced to the vertex's key range before the call and the ordering token
added onto K by value (the executor's default), where the committed form
(``start``: steps 1 + 2) hands K and V whole and takes the token onto the
positions the kernel prefetches.  The vertex and the entry point are wrapped
here, in this script; the program has no switch for it.  Step 2 stays only
if ``start`` is faster than ``start.step1``.

One process; not part of a benchmark run.  Writes
``chiprun_out/attn_operands[.<label>].json``.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the Q row slices, ms an iteration at the parent (ISSUE 37, step 0)
GO_Q_SLICES_MS = 1.0
COUNTERS = ("executor.index_ties", "executor.value_tied_bytes", "attn.tiles",
            "attn.pairs_computed", "attn.fused_finishes",
            "attn.operands_in_place")
# the kinds ISSUE 37 names, as ``attn_finish_on_chip.kind_of`` spells them
NAMED = ("attn_fused", "fusion (bf16[32,4096,128]", "slice-done",
         "broadcast_add_fusion", "slice_add_fusion", "fusion (bf16[4,")


def step1_form(attention_pallas, ring_attention):
    """Make the fused vertex ISSUE 37's step 1 alone; returns the undo.  The
    entry point slices K and V to the key range it is given and drops the
    token; the vertex takes its token by value again (the executor adds it
    onto its smallest read, K) and hands the kernel a plain zero."""
    import jax.lax as lax
    import jax.numpy as jnp

    plain = attention_pallas.attn_fused_pallas
    vertex = ring_attention.FusedBlockAttn
    apply = vertex.apply

    def sliced(q, k, v, *a, k_row0=0, keys=None, tok=None, **kw):
        if keys is not None:
            k, v = (lax.dynamic_slice_in_dim(t, k_row0, keys, 1)
                    for t in (k, v))
        return plain(q, k, v, *a, **kw)

    def by_value(self, bufs, ctx):
        ctx.tok_index_zero = jnp.zeros((), jnp.int32)
        return apply(self, bufs, ctx)

    attention_pallas.attn_fused_pallas = sliced
    vertex.apply, vertex.INDEX_TIE = by_value, False

    def undo():
        attention_pallas.attn_fused_pallas = plain
        vertex.apply, vertex.INDEX_TIE = apply, True

    return undo


def named_ms(by_kind: dict) -> dict:
    """ms an iteration of the kinds ISSUE 37 names (by prefix), and of all."""
    out = {name: sum(v for k, v in by_kind.items() if k.startswith(name))
           for name in NAMED}
    out["all"] = sum(by_kind.values())
    return out


def compare(parent_json: str, change_json: str) -> int:
    with open(parent_json) as f:
        parent = json.load(f)["schedules"]
    with open(change_json) as f:
        change = json.load(f)["schedules"]
    rows = [("parent", parent.get("start")),
            ("step 1", change.get("start.step1")),
            ("steps 1 + 2", change.get("start")),
            ("parent, naive", parent.get("naive")),
            ("change, naive", change.get("naive"))]
    out = {"go_q_slices_ms": GO_Q_SLICES_MS, "rows": {}}
    for label, row in rows:
        if row is None:
            continue
        out["rows"][label] = {
            "iter_ms": row["iter_ms"], "slopes_ms": row["slopes_ms"],
            "fixed_ms": row["fixed_ms"], "first_call_s": row["first_call_s"],
            "device_ms": row["named_ms"],
            "counters": row["counters"], "temp_gb": row["temp_gb"],
            "timed_fence_gap": row["timed_fence_gap"],
            "compared": row["compared"]}
    have = out["rows"]
    if "parent" in have:
        out["go"] = have["parent"]["device_ms"][NAMED[1]] >= GO_Q_SLICES_MS
    if "step 1" in have and "steps 1 + 2" in have:
        out["step2_gains_ms"] = (have["step 1"]["iter_ms"]
                                 - have["steps 1 + 2"]["iter_ms"])
        out["keep_step2"] = out["step2_gains_ms"] > 0
    print(json.dumps(out, indent=1))
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="trinity-attn32k.climb")
    ap.add_argument("--seed", type=int, default=2147484211)
    ap.add_argument("--schedules", default="start,naive")
    ap.add_argument("--forms", action="store_true")
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--root", default=HERE,
                    help="the checkout whose program is measured")
    ap.add_argument("--label", default=None)
    ap.add_argument("--compare", nargs=2, metavar=("PARENT", "CHANGE"))
    args = ap.parse_args()
    if args.compare:
        return compare(*args.compare)
    sys.path.insert(0, os.path.abspath(args.root))
    import jax
    import jax.numpy as jnp

    # sibling scripts: the compiled text's instructions and their kinds;
    # one profiled dispatch reduced to ms by operation
    from attn_finish_on_chip import instructions, kind_of, sources
    from benchmarks.harness import cell as cell_mod
    from benchmarks.harness import clock as clock_mod
    from halo_mesh_tie_on_chip import device_ms_by_op
    from tenzing_tpu.models import ring_attention
    from tenzing_tpu.obs.metrics import get_metrics
    from tenzing_tpu.ops import attention_pallas
    from tenzing_tpu.solve.local import drive, phase_policy

    cell = cell_mod.load_cell(args.workload)
    config = cell.config
    if args.rehearse_cpu:
        config = cell_mod.toy_shapes(config)
    devices = cell_mod.find_devices(cell.chips, args.rehearse_cpu)
    ref = cell_mod.load_module("references", config["reference"])
    builder = cell_mod.load_module("builders", config["builder"])
    t0 = time.perf_counter()
    built = builder.build(config, args.seed, devices, ref)
    ex = built.executor
    ex.init_bufs = cell_mod.committed(ex.init_bufs)
    jax.block_until_ready(ex.init_bufs)
    h = built.hints
    start, _ = drive(built.graph, h["platform"], phase_policy(
        h["platform"], h["phases"], h["prefer"]))
    orders = {"start": start, "naive": built.naive}
    report = {"device": devices[0].device_kind, "seed": args.seed,
              "prompt_tokens": config["shapes"]["prompt_tokens"],
              "root": os.path.relpath(os.path.abspath(args.root), HERE),
              "schedules": {}}
    print(f"{devices[0].device_kind}, built in "
          f"{time.perf_counter() - t0:.1f} s", flush=True)
    reg = get_metrics()

    def counters():
        return [reg.counter(name).value for name in COUNTERS]

    def one_schedule(label, order):
        t0 = time.perf_counter()
        run_n = ex.prepare_n(order)
        run_n(1)  # trace, lower, compile (16 Mosaic kernels), first run
        first_call = time.perf_counter() - t0
        before = counters()
        stepped = jax.jit(ex._stepped_fn(order.vector()))
        compiled = stepped.lower(ex.init_bufs, jnp.int32(1)).compile()
        counted = dict(zip(COUNTERS, (b - a for a, b in
                                      zip(before, counters()))))
        instr = instructions(compiled.as_text())
        row = {"first_call_s": first_call, "counters": counted,
               "temp_gb": compiled.memory_analysis().temp_size_in_bytes / 1e9}
        del compiled, stepped
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
                   peak_gb=cell_mod.memory_peak(devices[:1]) / 1e9)
        ranked = device_ms_by_op(run_n, c["n"], top=1 << 20)
        row["device_ms_per_iter"] = [
            [k, v, *instr.get(k, ("?", "?", ""))[:2], sources(k, instr)]
            for k, v in ranked[:40]]
        kinds = {}
        for k, v in ranked:
            kind = kind_of(k, instr)
            kinds[kind] = kinds.get(kind, 0.0) + v
        row["by_kind"] = dict(sorted(kinds.items(), key=lambda kv: -kv[1]))
        row["named_ms"] = named_ms(row["by_kind"])
        row["seconds"] = time.perf_counter() - t0
        report["schedules"][label] = row
        print(f"{label}: {json.dumps(row)}", flush=True)

    for label in [s for s in args.schedules.split(",") if s]:
        one_schedule(label, orders[label])
    if args.forms:
        undo = step1_form(attention_pallas, ring_attention)
        ex._cache.clear()
        try:
            one_schedule("start.step1", start)
        finally:
            undo()
            ex._cache.clear()
    os.makedirs(os.path.join(HERE, "chiprun_out"), exist_ok=True)
    name = ".".join(x for x in ("attn_operands", args.label, "json") if x)
    with open(os.path.join(HERE, "chiprun_out", name), "w") as f:
        json.dump(report, f, indent=1)
    bad = [k for k, r in report["schedules"].items()
           if r["timed_fence_gap"] != 0.0
           or any(v > lim for v, lim in r["compared"].values())]
    print(json.dumps({"not_correct": bad}))
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
