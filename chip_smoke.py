#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py             one TPU chip: search -> measure -> serve
    python chip_smoke.py --chips 4   one host with four: the mesh path only

One chip (what the driver runs), at the flagship size — halo 512^3, nQ=3,
radius 3, the ``python bench.py`` default cell — with small search budgets
and the soundness verifier ON:

1. *search*: ``python bench.py --workload halo --halo-n 512 ...``.  Pass =
   rc 0, no ``error``, ``device.platform == "tpu"``, ``fault.verified``,
   not degraded, nothing quarantined, the winner-vs-naive integrity gate
   agreed, ``value > 0``, and Pallas-unpack + rdma-transfer schedules among
   the measured rows of the dumped database.
2. *serve*: ``python -m tenzing_tpu.serve query`` for the same request (a
   miss that enqueues and touches no device) -> ``python -m
   tenzing_tpu.serve.daemon --once`` (its child drains on the chip, same
   budgets as ``--override``) -> the same query again is an exact hit whose
   schedule re-verifies.  The drain repeats phase 1's schedules, so its
   compile seconds against phase 1's show whether the compile cache hit.

Four chips (``--chips 4``, run by the builder, never by the driver): only
the mesh path — ``__graft_entry__.halo_mesh_on_chips`` in ONE process that
owns all four chips (2x2x1 mesh, 256^3 cells per shard, both transfer
engines, every output compared with the host-built expected grid, shards on
four distinct devices).

This parent is plain stdlib and never imports jax: a chip belongs to one
process at a time, so every phase is a child process through the normal
entry point, one at a time, all sharing one compile cache
(tenzing_tpu/bench/compile_cache.py: ``JAX_COMPILATION_CACHE_DIR`` if set,
else ``<repo>/.jax_cache``).

Output: one JSON line per phase, then — only if every phase passed — the
last line ``{"ok": true, "device": {"platform": ..., "kind": ..., "count":
...}}`` with the device as the phase verdicts report it, exit 0.  Any failed
phase: non-zero exit, no success line.  Without an accelerator ``bench.py``
refuses the run and this script fails with it.  Artifacts (logs, the dumped
database, metrics, the serve store/queue) land under
``chiprun_out/chip_smoke/``.
"""

import argparse
import glob
import json
import os
import shutil
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(ROOT, "chiprun_out", "chip_smoke")
# inside the 1200 s the contract allows for the whole script, compiles included
PHASE_TIMEOUT_SECS = 900
# small budgets: minutes, not the eleven of a default search
BUDGET = {"mcts_iters": 4, "climb_budget": 4, "iters": 6, "search_iters": 3,
          "seed_topk": 1}


class PhaseFailed(Exception):
    pass


def emit(doc):
    sys.stdout.write(json.dumps(doc) + "\n")
    sys.stdout.flush()


def child(name, cmd, env=None, timeout=PHASE_TIMEOUT_SECS):
    """Run one child to its end (killed, with its process group, at the
    timeout); stdout is returned, stderr goes to ``<OUT>/<name>.err``."""
    err_path = os.path.join(OUT, f"{name}.err")
    t0 = time.time()
    with open(err_path, "wb") as err:
        proc = subprocess.Popen(cmd, cwd=ROOT, env=env, stdout=subprocess.PIPE,
                                stderr=err, start_new_session=True)
        try:
            out, _ = proc.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            os.killpg(proc.pid, 9)
            proc.wait()
            raise PhaseFailed(f"{name}: no end after {timeout}s (killed; "
                              f"see {err_path})")
    return proc.returncode, out.decode(errors="replace"), time.time() - t0


def last_json(name, text):
    lines = [l for l in text.strip().splitlines() if l.strip()]
    try:
        return json.loads(lines[-1])
    except (IndexError, ValueError):
        raise PhaseFailed(f"{name}: last stdout line is not JSON: "
                          f"{lines[-1][:200] if lines else '<nothing>'}")


def require(cond, msg):
    if not cond:
        raise PhaseFailed(msg)


def check_verdict(name, v, platform):
    """The pass criteria every driver verdict of this script is held to."""
    require("error" not in v, f"{name}: verdict carries error: {v.get('error')}")
    dev = v.get("device") or {}
    require(dev.get("platform") == platform,
            f"{name}: ran on {dev}, not on {platform!r}")
    fault = v.get("fault") or {}
    require(fault.get("verified") is True,
            f"{name}: integrity gate did not agree: {fault}")
    require(fault.get("degraded") is False, f"{name}: degraded: {fault}")
    require(fault.get("quarantined") == 0,
            f"{name}: {fault.get('quarantined')} schedule(s) quarantined")
    require(v.get("value", 0) > 0 and v.get("naive_us", 0) > 0,
            f"{name}: no measured value: {v.get('value')}")
    return dev


def trimmed(v):
    keep = ("metric", "value", "unit", "vs_baseline", "naive_us",
            "winner_label", "device", "fault")
    return {k: v[k] for k in keep if k in v}


def measured_kernels(csv_path):
    """(rows, rows with a Pallas unpack, rows with an rdma transfer) of the
    dumped database — what was actually measured, not what was on the menu."""
    rows = pallas = rdma = 0
    with open(csv_path) as f:
        for line in f:
            if not line.strip():
                continue
            rows += 1
            names = []
            for cell in line.rstrip("\n").split("|"):
                if cell.startswith("{"):
                    names.append(json.loads(cell).get("name", ""))
            if any(n.startswith("unpack_") and ".pallas" in n for n in names):
                pallas += 1
            if any(n.startswith("xfer_") and n.endswith(".rdma")
                   for n in names):
                rdma += 1
    return rows, pallas, rdma


def budget_flags():
    return [f for k, v in BUDGET.items()
            for f in ("--" + k.replace("_", "-"), str(v))]


def phase_search(size, env, platform, rehearsal):
    csv = os.path.join(OUT, "search.csv")
    cmd = [sys.executable, "bench.py", *size,
           *budget_flags(), "--dump-csv", csv,
           "--metrics-json", os.path.join(OUT, "search_metrics.json")]
    rc, out, wall = child("search", cmd, env)
    v = last_json("search", out)
    require(rc == 0, f"search: bench.py exited {rc}: "
                     f"{v.get('error', '(see search.err)')}")
    dev = check_verdict("search", v, platform)
    rows, pallas, rdma = measured_kernels(csv)
    if not rehearsal:  # the rehearsal's smoke graph has no such menu
        require(pallas > 0 and rdma > 0,
                f"search: of {rows} measured schedules {pallas} used a "
                f"Pallas unpack and {rdma} an rdma transfer")
    perf = v.get("perf", {})
    emit({"phase": "search", "ok": True, "wall_secs": round(wall, 1),
          "compile_secs": perf.get("compile_secs"),
          "compiled_programs": perf.get("compiled_programs"),
          "compile_cache_dir": perf.get("compile_cache_dir"),
          "measured": {"rows": rows, "pallas_unpack": pallas, "rdma": rdma},
          "verdict": trimmed(v)})
    return dev, perf.get("compile_secs")


def phase_serve(size, env, platform, search_compile_secs):
    serve_dir = os.path.join(OUT, "serve")
    os.makedirs(serve_dir)
    store = os.path.join(serve_dir, "store.json")
    queue = os.path.join(serve_dir, "queue")
    query = [sys.executable, "-m", "tenzing_tpu.serve", "query",
             "--store", store, "--queue", queue, *size]
    # resolution must never initialise a backend (a parent that touched jax
    # holds the chip): the query children get a platform that does not exist
    no_backend = dict(env, JAX_PLATFORMS="chip_smoke_no_backend")

    rc, out, wall_q1 = child("query_cold", query, no_backend, timeout=120)
    q1 = last_json("query_cold", out)
    require(rc == 0 and q1.get("tier") == "cold" and q1.get("work_item"),
            f"serve: cold query rc={rc} answered {q1.get('tier')!r}")

    daemon = [sys.executable, "-m", "tenzing_tpu.serve.daemon",
              "--queue", queue, "--store", store, "--once",
              "--item-timeout", str(PHASE_TIMEOUT_SECS - 60)]
    for k, val in BUDGET.items():
        daemon += ["--override", f"{k}={val}"]
    rc, out, wall_d = child("daemon", daemon, env)
    summary = last_json("daemon", out)
    counters = summary.get("counters", {})
    # the daemon exits 0 unless the device was lost: read its summary
    require(rc == 0 and summary.get("drained") == 1
            and counters.get("completed") == 1
            and summary.get("queue_depth") == 0
            and not counters.get("poisoned"),
            f"serve: item not drained (rc={rc}): {summary}")
    verdicts = glob.glob(os.path.join(queue, "ckpt-*", "verdict.json"))
    require(len(verdicts) == 1, f"serve: drain verdicts found: {verdicts}")
    with open(verdicts[0]) as f:
        dv = json.load(f)
    dev = check_verdict("drain", dv, platform)
    drain_compile = dv.get("perf", {}).get("compile_secs")

    rc, out, wall_q2 = child("query_hit", query, no_backend, timeout=120)
    q2 = last_json("query_hit", out)
    require(rc == 0 and q2.get("tier") == "exact",
            f"serve: second query rc={rc} answered {q2.get('tier')!r}, "
            "not an exact hit")
    require(q2.get("fingerprint", {}).get("exact")
            == q1.get("fingerprint", {}).get("exact"),
            "serve: the hit is for another fingerprint than the miss")
    prov = q2.get("provenance", {})
    require(prov.get("verified") is True,
            f"serve: the served schedule did not re-verify: {prov}")
    emit({"phase": "serve", "ok": True,
          "wall_secs": round(wall_q1 + wall_d + wall_q2, 1),
          "drain_wall_secs": round(wall_d, 1),
          "compile_secs": drain_compile,
          "search_compile_secs": search_compile_secs,
          "compile_cache_hit": (drain_compile is not None
                                and search_compile_secs is not None
                                and drain_compile < search_compile_secs),
          "compile_cache_dir": dv.get("perf", {}).get("compile_cache_dir"),
          "daemon": {"drained": summary.get("drained"),
                     "counters": counters},
          "hit": {"tier": q2.get("tier"), "provenance": prov},
          "verdict": trimmed(dv)})
    return dev


def phase_mesh(env, platform, rehearsal):
    n = 8 if rehearsal else 256
    code = ("import json, __graft_entry__ as ge; "
            f"print(json.dumps(ge.halo_mesh_on_chips(4, {n}, {platform!r})))")
    # a hung collective holds four chips: a shorter leash than a one-chip phase
    rc, out, wall = child("mesh", [sys.executable, "-c", code], env,
                          timeout=600)
    require(rc == 0, f"mesh: child exited {rc} (see mesh.err): "
                     f"{tail_of('mesh')}")
    doc = last_json("mesh", out)
    dev = doc.get("device") or {}
    require(dev.get("platform") == platform and dev.get("count") == 4,
            f"mesh: ran on {dev}")
    emit({"phase": "mesh", "ok": True, "child_wall_secs": round(wall, 1),
          **doc})
    return dev


def tail_of(name, n=600):
    try:
        with open(os.path.join(OUT, f"{name}.err"), errors="replace") as f:
            return f.read()[-n:]
    except OSError:
        return ""


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the mesh path on four chips, and no other phase")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help=argparse.SUPPRESS)  # private: smoke size on the CPU;
    args = ap.parse_args()                   # never prints the success line
    if not os.path.exists(os.path.join(ROOT, "bench.py")):
        sys.stderr.write("chip_smoke: no bench.py next to this script — "
                         "nothing to drive\n")
        return 2
    shutil.rmtree(OUT, ignore_errors=True)
    os.makedirs(OUT)
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [ROOT] + [p for p in [os.environ.get("PYTHONPATH")] if p]))
    size = ["--workload", "halo", "--halo-n", "512"]
    platform = "tpu"
    if args.rehearse_cpu:
        # attn: the one smoke workload whose search reliably beats naive on
        # the CPU, so the drain has a record to admit and the hit a record
        # to find (halo's toy grid is noise there)
        size, platform = ["--workload", "attn", "--smoke"], "cpu"
        env["JAX_PLATFORMS"] = "cpu"
        if args.chips == 4:
            env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    t0 = time.time()
    try:
        if args.chips == 4:
            device = phase_mesh(env, platform, args.rehearse_cpu)
        else:
            device, compile_secs = phase_search(size, env, platform,
                                                args.rehearse_cpu)
            served_on = phase_serve(size, env, platform, compile_secs)
            require(served_on == device,
                    f"phases ran on different devices: {device} / {served_on}")
    except PhaseFailed as e:
        # stderr only: a failed run prints no result line
        sys.stderr.write(f"chip_smoke FAILED after "
                         f"{time.time() - t0:.1f}s: {e}\n")
        return 1
    if args.rehearse_cpu:
        emit({"rehearsal": "passed", "device": device,
              "wall_secs": round(time.time() - t0, 1)})
        return 0
    emit({"ok": True, "device": {"platform": device["platform"],
                                 "kind": device["kind"],
                                 "count": device["count"]}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
