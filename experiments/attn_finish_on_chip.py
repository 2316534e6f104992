"""ISSUE 34's step 0 and its A/B: what one iteration of the attention
cell's start-point program costs on the chip, operation by operation, and
what each ``copy`` of it moves.

    chiprun -- python experiments/attn_finish_on_chip.py --label parent

Parent against change in one call: unpack the parent into a directory
``.gitignore`` lists and measure its program with this script, then this
checkout's, then compare the two reports (no chip in that step):

    git archive <parent> | tar -x -C .bench_checkout/parent
    chiprun -- sh -c 'python experiments/attn_finish_on_chip.py \
        --root .bench_checkout/parent --label parent && \
      python experiments/attn_finish_on_chip.py --label change --forms && \
      python experiments/attn_finish_on_chip.py --compare \
        chiprun_out/attn_finish.parent.json chiprun_out/attn_finish.change.json'

Builds ``trinity-attn32k.climb``'s stack as a run builds it
(``benchmarks/builders/attn_period.py``) and, for the climb's start point
(every query block on the fused kernel, driven as ``hill_climb`` drives it)
and the builder's naive (``--schedules start,naive``):

* the iteration time by the benchmark's two-point clock;
* ``timed_fence_gap`` and the one-shot program against the plain reference,
  as the harness takes them;
* the program's counters for one traced body (``executor.value_tied_bytes``,
  ``attn.tiles``, ``attn.fused_finishes`` where the program has it) and the
  op names of the schedule that end in ``attn_finalize``;
* a profiled dispatch: the device's milliseconds an iteration by operation,
  each beside what the compiled program says it is (result type, opcode,
  operands with their types: a ``copy`` of ``bf16[32,4096,128]`` from a
  ``dynamic-slice`` of Q is a row slice, one of ``f32[32,4096,128]`` into
  the loop's carry is the state), and summed by kind (``by_kind``: the
  kernel, the finalisers, the copies by the type they move, the fence).

``--forms`` (this checkout's program only, where ``attn_fused_pallas`` takes
``finish``): the start point again with the rows of O landing the other way,
a fresh ``(32, rows, 128)`` output put into O by
``dynamic_update_slice_in_dim`` where the committed form writes the aliased
O in place (ISSUE 34, "where the rows land").  The entry point is wrapped
here, in this script; the program has no switch for it.

One process; not part of a benchmark run.  Writes
``chiprun_out/attn_finish[.<label>].json``.
"""

import argparse
import json
import os
import re
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the four finalisers together, ms an iteration (ISSUE 34, step 0)
GO_FINALISERS_MS = 1.5
INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\(.*?\)|\S+)\s+([\w\-]+)\((.*)$")


def instructions(text: str) -> dict:
    """``{name: (result type, opcode, operands)}`` of a compiled program's
    text, operands as written (each with its type) up to the closing
    parenthesis."""
    out = {}
    for line in text.splitlines():
        m = INSTR.match(line)
        if m:
            name, typ, opcode, rest = m.groups()
            out[name] = (typ.split("{")[0], opcode,
                         rest.split("), ")[0].rstrip(")")[:400])
    return out


def sources(name: str, instr: dict) -> list:
    """What an operation reads, one level up: ``opcode:type`` of each of its
    operands (a ``copy`` of a ``dynamic-slice`` of Q is a row slice, one of
    a ``get-tuple-element`` the loop's carry)."""
    out = []
    for operand in re.findall(r"%?([A-Za-z_][\w.\-]*)",
                              instr.get(name, ("", "", ""))[2]):
        if operand in instr:
            typ, opcode, _ = instr[operand]
            out.append(f"{operand}={opcode}:{typ}")
    return out[:6]


def kind_of(name: str, instr: dict) -> str:
    """The row of ``by_kind`` an operation's time goes to."""
    typ, opcode, _ = instr.get(name, ("?", "?", ""))
    base = re.sub(r"[.\d]+$", "", name)
    if base.startswith(("attn_fused", "attn_fold")):
        return base
    if opcode == "copy" or base.startswith("copy"):
        return f"copy {typ}"
    # acc / l, and the concatenate of a layer's four (a pad and a maximum)
    if base.startswith(("divide", "pad_maximum")):
        return f"finaliser {base} {typ}"
    return f"{base} {typ}"


def dus_form(module):
    """``attn_fused_pallas`` of ``module`` with the rows of O landing by
    ``dynamic_update_slice_in_dim``: the finishing kernel writes a fresh
    ``(h, rows, d)`` and XLA puts it into O."""
    import jax.lax as lax

    plain = module.attn_fused_pallas

    def wrapped(*a, o=None, o_row0=0, **kw):
        if o is None:
            return plain(*a, **kw)
        rows = plain(*a, **kw)
        return lax.dynamic_update_slice_in_dim(o, rows, o_row0, 1)

    return plain, wrapped


def compare(parent_json: str, change_json: str) -> int:
    with open(parent_json) as f:
        parent = json.load(f)
    with open(change_json) as f:
        change = json.load(f)
    out = {"go_finalisers_ms": GO_FINALISERS_MS}
    for label in sorted(set(parent["schedules"]) & set(change["schedules"])):
        p, c = parent["schedules"][label], change["schedules"][label]
        kinds = sorted(set(p["by_kind"]) | set(c["by_kind"]),
                       key=lambda k: -max(p["by_kind"].get(k, 0),
                                          c["by_kind"].get(k, 0)))
        out[label] = {
            "iter_ms": [p["iter_ms"], c["iter_ms"]],
            "device_ms": [sum(p["by_kind"].values()),
                          sum(c["by_kind"].values())],
            "finalisers_ms": [p["finalisers_ms"], c["finalisers_ms"]],
            "by_kind": {k: [p["by_kind"].get(k, 0.0), c["by_kind"].get(k, 0.0)]
                        for k in kinds[:16]}}
    for label, row in change["schedules"].items():
        if label not in out:
            out[label] = {"iter_ms": row["iter_ms"],
                          "device_ms": sum(row["by_kind"].values())}
    start = parent["schedules"].get("start")
    out["go"] = bool(start and start["finalisers_ms"] >= GO_FINALISERS_MS)
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

    from benchmarks.harness import cell as cell_mod
    from benchmarks.harness import clock as clock_mod
    # a sibling script: one profiled dispatch reduced to ms by operation
    from halo_mesh_tie_on_chip import device_ms_by_op
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
    names = ("executor.value_tied_bytes", "attn.tiles", "attn.tiles_edge",
             "attn.pairs_computed", "attn.fused_finishes")

    def counters():
        return [reg.counter(name).value for name in names]

    def one_schedule(label, order):
        t0 = time.perf_counter()
        before = counters()
        stepped = jax.jit(ex._stepped_fn(order.vector()))
        compiled = stepped.lower(ex.init_bufs, jnp.int32(1)).compile()
        counted = dict(zip(names, (b - a for a, b in
                                   zip(before, counters()))))
        instr = instructions(compiled.as_text())
        mem = compiled.memory_analysis()
        row = {"counters": counted,
               "finalisers_in_schedule": sum(
                   op.name().endswith("attn_finalize")
                   for op in order.vector()),
               "temp_gb": mem.temp_size_in_bytes / 1e9}
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
                   peak_gb=cell_mod.memory_peak(devices[:1]) / 1e9)
        ranked = device_ms_by_op(run_n, c["n"], top=1 << 20)
        ms = dict(ranked)
        row["device_ms_per_iter"] = [
            [k, v, *instr.get(k, ("?", "?", ""))[:2], sources(k, instr)]
            for k, v in ranked[:48]]
        kinds = {}
        for k, v in ms.items():
            kind = kind_of(k, instr)
            kinds[kind] = kinds.get(kind, 0.0) + v
        row["by_kind"] = dict(sorted(kinds.items(), key=lambda kv: -kv[1]))
        row["finalisers_ms"] = sum(
            v for k, v in kinds.items() if k.startswith("finaliser"))
        row["seconds"] = time.perf_counter() - t0
        report["schedules"][label] = row
        print(f"{label}: {json.dumps(row)}", flush=True)

    for label in [s for s in args.schedules.split(",") if s]:
        one_schedule(label, orders[label])
    if args.forms:
        plain, wrapped = dus_form(attention_pallas)
        attention_pallas.attn_fused_pallas = wrapped
        ex._cache.clear()
        try:
            one_schedule("start.dus", start)
        finally:
            attention_pallas.attn_fused_pallas = plain
            ex._cache.clear()
    os.makedirs(os.path.join(HERE, "chiprun_out"), exist_ok=True)
    name = ".".join(x for x in ("attn_finish", args.label, "json") if x)
    with open(os.path.join(HERE, "chiprun_out", name), "w") as f:
        json.dump(report, f, indent=1)
    bad = [k for k, r in report["schedules"].items()
           if r["timed_fence_gap"] != 0.0
           or any(v > lim for v, lim in r["compared"].values())]
    print(json.dumps({"not_correct": bad}))
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
