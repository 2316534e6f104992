"""Drain-daemon acceptance (ISSUE 9): leased claims, crash-resume,
poison quarantine — the serve→search→serve loop closed end-to-end.

The protocol tests drive :class:`DrainDaemon` in-process with stub
runners (claim exclusivity, reclaim, retry/poison policy, status JSON)
— no device, no search.  The chaos acceptance runs the real thing: a
cold attn-smoke work item drained by the real subprocess runner under
seeded transient+hang injection, the daemon SIGKILLed mid-item, and a
restarted daemon reclaiming the expired lease and completing the item
via checkpoint resume (journaled measurements replayed, store warmed,
re-query answers exact-tier) — the item's effect lands exactly once.
"""

import collections
import json
import os
import signal
import subprocess
import sys
import threading
import time

import pytest

from tenzing_tpu.bench.driver import DriverConfigError, DriverRequest
from tenzing_tpu.fault.checkpoint import atomic_write_json, read_checked_json
from tenzing_tpu.fault.errors import (
    DeterministicScheduleError,
    DeviceLostError,
    TransientError,
)
from tenzing_tpu.serve.daemon import (
    DaemonOpts,
    DrainDaemon,
    apply_overrides,
    parse_override,
)
from tenzing_tpu.serve.fingerprint import fingerprint_of
from tenzing_tpu.serve.store import ScheduleStore, WorkQueue

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _enqueue(qdir, m=512, **req_kw):
    q = WorkQueue(qdir)
    req = DriverRequest(workload="spmv", m=m, **req_kw)
    fp = fingerprint_of(req)
    q.enqueue(fp, req.to_json(), reason="cold")
    return q, fp


def _opts(tmp_path, **kw):
    base = dict(queue_dir=str(tmp_path / "q"),
                store_path=str(tmp_path / "store.json"),
                once=True, handle_signals=False, heartbeat_secs=0.1,
                backoff_base_secs=0.01, owner="t")
    base.update(kw)
    return DaemonOpts(**base)


def _ok_verdict(*_a, **_k):
    return {"metric": "m", "value": 1.0, "unit": "us", "vs_baseline": 1.2}


def test_drain_completes_deletes_item_and_lease_after_merge(tmp_path):
    q, fp = _enqueue(str(tmp_path / "q"))
    d = DrainDaemon(_opts(tmp_path),
                    runner=lambda p, pl, t: _ok_verdict(), log=lambda m: None)
    s = d.run()
    assert s["drained"] == 1 and s["counters"]["completed"] == 1
    assert len(q) == 0
    assert not os.path.exists(q.lease_path_for(fp.exact_digest))
    assert not os.path.exists(q.fail_path_for(fp.exact_digest))
    # the store was flushed by the merge step (empty drain CSV → 0
    # records admitted, but the store file exists and loads)
    assert os.path.exists(str(tmp_path / "store.json"))
    h = d.history[-1]
    assert h["outcome"] == "completed" and h["resumed"] is False
    # status JSON: the liveness document a probe reads
    st = json.load(open(d.status_path))
    assert st["owner"] == "t" and st["state"] == "stopped"
    assert st["counters"]["completed"] == 1
    assert st["history"][-1]["exact"] == fp.exact_digest


def test_claim_is_exclusive_and_lease_heartbeat_renews(tmp_path):
    q, fp = _enqueue(str(tmp_path / "q"))
    a = DrainDaemon(_opts(tmp_path, owner="a"), runner=_ok_verdict,
                    log=lambda m: None)
    b = DrainDaemon(_opts(tmp_path, owner="b"), runner=_ok_verdict,
                    log=lambda m: None)
    exact = fp.exact_digest
    lease = a._claim(exact)
    assert lease is not None
    assert b._claim(exact) is None  # fresh lease: rival must lose
    before = os.path.getmtime(lease)
    time.sleep(0.05)
    assert a._renew(lease) is True
    assert os.path.getmtime(lease) >= before
    doc = json.load(open(lease))
    assert doc["owner"] == "a" and doc["exact"] == exact
    a._release(lease)
    assert not os.path.exists(lease)


def test_expired_lease_is_reclaimed_live_lease_is_not(tmp_path):
    q, fp = _enqueue(str(tmp_path / "q"))
    exact = fp.exact_digest
    lease = q.lease_path_for(exact)
    with open(lease, "w") as f:
        json.dump({"owner": "dead-worker"}, f)
    past = time.time() - 999
    os.utime(lease, (past, past))
    d = DrainDaemon(_opts(tmp_path, lease_ttl_secs=60),
                    runner=lambda p, pl, t: _ok_verdict(), log=lambda m: None)
    s = d.run()
    assert s["counters"]["reclaimed"] == 1 and s["counters"]["completed"] == 1
    # fresh lease: not reclaimable, item not claimable
    q2, fp2 = _enqueue(str(tmp_path / "q2"), m=500)
    l2 = q2.lease_path_for(fp2.exact_digest)
    with open(l2, "w") as f:
        json.dump({"owner": "alive"}, f)
    d2 = DrainDaemon(_opts(tmp_path, queue_dir=str(tmp_path / "q2"),
                           lease_ttl_secs=300),
                     runner=lambda p, pl, t: _ok_verdict(),
                     log=lambda m: None)
    s2 = d2.run()
    assert s2["counters"]["claimed"] == 0 and len(q2) == 1


def test_renew_detects_lost_lease_by_nonce(tmp_path):
    q, fp = _enqueue(str(tmp_path / "q"))
    d = DrainDaemon(_opts(tmp_path), runner=_ok_verdict, log=lambda m: None)
    lease = d._claim(fp.exact_digest)
    # a rival reclaims during our stall: same path, rival's claim nonce
    # (inode numbers recycle on unlink, so the payload nonce is the
    # lease identity)
    os.unlink(lease)
    with open(lease, "w") as f:
        json.dump({"owner": "rival", "nonce": "rival-1-2"}, f)
    assert d._renew(lease) is False
    assert d._lease_lost.is_set()
    # and release must NOT delete a lease that is no longer ours
    d._release(lease)
    assert os.path.exists(lease)


def test_transient_failure_retries_then_leaves_item(tmp_path):
    q, fp = _enqueue(str(tmp_path / "q"))
    calls = []

    def flaky(item_path, payload, timeout):
        calls.append(1)
        raise TransientError("connection reset")

    d = DrainDaemon(_opts(tmp_path, retries=2), runner=flaky,
                    log=lambda m: None)
    s = d.run()
    assert len(calls) == 3  # 1 + 2 bounded retries (fault/backoff.py)
    assert s["counters"]["retried"] == 2
    assert s["counters"]["failed_transient"] == 1
    assert s["counters"]["poisoned"] == 0
    assert len(q) == 1  # the item survives for a later pass / worker
    # the failure history records the transient (economics, not poison)
    fails = json.load(open(q.fail_path_for(fp.exact_digest)))
    assert fails["attempts"][-1]["error_class"] == "transient"


def test_poison_after_n_deterministic_failures_survives_restarts(tmp_path):
    q, fp = _enqueue(str(tmp_path / "q"))
    exact = fp.exact_digest

    def broken(item_path, payload, timeout):
        raise DeterministicScheduleError("bad request, forever")

    # two separate daemon processes-worth of attempts: the count is
    # persistent (fail-<exact>.json), not in-memory
    d1 = DrainDaemon(_opts(tmp_path, max_failures=2), runner=broken,
                     log=lambda m: None)
    assert d1.run()["counters"]["poisoned"] == 0
    assert os.path.exists(q.fail_path_for(exact))
    d2 = DrainDaemon(_opts(tmp_path, max_failures=2), runner=broken,
                     log=lambda m: None)
    s2 = d2.run()
    assert s2["counters"]["poisoned"] == 1
    poison = read_checked_json(q.poison_path_for(exact))
    assert poison["kind"] == "poisoned_request"
    assert len(poison["attempts"]) == 2
    assert all(a["error_class"] == "deterministic"
               for a in poison["attempts"])
    assert poison["exact"] == exact
    assert poison["request"]["workload"] == "spmv"
    # item + sidecar are gone; the queue never offers the item again
    assert len(q) == 0
    assert not os.path.exists(q.fail_path_for(exact))
    d3 = DrainDaemon(_opts(tmp_path, max_failures=2), runner=broken,
                     log=lambda m: None)
    assert d3.run()["counters"]["claimed"] == 0
    # and the rot is visible: queue stats carry the poison set
    st = q.stats()
    assert st["poisoned"] == [f"poison-{exact}.json"]


def test_device_lost_stops_the_daemon(tmp_path):
    qdir = str(tmp_path / "q")
    q, _ = _enqueue(qdir, m=500)
    _enqueue(qdir, m=512)

    def dead(item_path, payload, timeout):
        raise DeviceLostError("chip rebooted")

    d = DrainDaemon(_opts(tmp_path, once=False, idle_exit_secs=30),
                    runner=dead, log=lambda m: None)
    s = d.run()  # must stop after the FIRST device-lost, not spin
    assert d.history[-1]["outcome"] == "device_lost"
    assert s["counters"]["claimed"] == 1
    assert len(q) == 2  # nothing consumed


def test_two_concurrent_daemons_zero_double_runs(tmp_path):
    """The acceptance bullet: two daemons, one multi-item queue, every
    item drained exactly once."""
    qdir = str(tmp_path / "q")
    for m in (500, 512, 520, 540):
        _enqueue(qdir, m=m)
    runs = collections.Counter()
    lock = threading.Lock()

    def runner(item_path, payload, timeout):
        with lock:
            runs[item_path] += 1
        time.sleep(0.15)  # hold the lease long enough for real overlap
        return _ok_verdict()

    ds = [DrainDaemon(_opts(tmp_path, owner=o), runner=runner,
                      log=lambda m: None) for o in ("a", "b")]
    ts = [threading.Thread(target=d.run) for d in ds]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert len(runs) == 4 and all(v == 1 for v in runs.values()), runs
    assert sum(d.counters["completed"] for d in ds) == 4
    assert len(WorkQueue(qdir)) == 0


def test_graceful_stop_releases_lease_and_stamps_interrupted(tmp_path):
    q, fp = _enqueue(str(tmp_path / "q"))
    d = DrainDaemon(_opts(tmp_path, once=False), log=lambda m: None)

    def slow(item_path, payload, timeout):
        d.stop()  # a stop request lands mid-drain...
        return _ok_verdict()  # ...the in-flight item still finishes

    d._runner = slow
    s = d.run()
    assert s["counters"]["completed"] == 1
    assert not os.path.exists(q.lease_path_for(fp.exact_digest))
    st = json.load(open(d.status_path))
    assert st["state"] in ("stopped", "interrupted")


def test_override_identity_guard_and_parsing():
    req = DriverRequest(workload="spmv", m=512).to_json()
    # budget overrides pass and apply
    eff = apply_overrides(req, {"mcts_iters": 4, "climb_budget": 2})
    assert eff.mcts_iters == 4 and eff.m == 512
    # identity overrides refuse: the merged record would land under a
    # different fingerprint than the queued request's
    with pytest.raises(DriverConfigError):
        apply_overrides(req, {"m": 4096})
    with pytest.raises(DriverConfigError):
        apply_overrides(req, {"no_such_field": 1})
    assert parse_override("mcts_iters=8") == ("mcts_iters", 8)
    assert parse_override("inject_faults=transient:0.3:7") == \
        ("inject_faults", "transient:0.3:7")
    with pytest.raises(ValueError):
        parse_override("not-a-pair")


def test_report_queue_section_mines_daemon_state(tmp_path):
    """The report CLI's queue section (ISSUE 9 satellite): lease ages,
    daemon status + heartbeat staleness, poison quarantine, per-item
    drain economics — all from the queue directory alone."""
    from tenzing_tpu.obs.report import queue_section

    qdir = str(tmp_path / "q")
    q, fp = _enqueue(qdir)

    def broken(item_path, payload, timeout):
        raise DeterministicScheduleError("always broken")

    d = DrainDaemon(_opts(tmp_path, max_failures=1), runner=broken,
                    log=lambda m: None)
    d.run()
    # leave a live lease + a torn item behind for the section to show
    q2, fp2 = _enqueue(qdir, m=500)
    with open(q.lease_path_for(fp2.exact_digest), "w") as f:
        json.dump({"owner": "someone", "nonce": "x"}, f)
    with open(os.path.join(qdir, "work-torn.json"), "w") as f:
        f.write("{")
    text = "\n".join(queue_section(qdir))
    assert "poisoned" in text and fp.exact_digest[:12] in text
    assert "someone" in text  # the lease owner with its heartbeat age
    assert "work-torn.json" in text
    assert "daemon `t`" in text  # the status document
    assert "| item | outcome |" in text  # per-item drain economics


def test_torn_item_is_counted_and_visible(tmp_path):
    from tenzing_tpu.obs.metrics import get_metrics

    qdir = str(tmp_path / "q")
    q, fp = _enqueue(qdir)
    with open(os.path.join(qdir, "work-torn.json"), "w") as f:
        f.write("{")
    before = get_metrics().counter("serve.queue.torn").value
    items = q.items()
    assert len(items) == 1  # the drainer still never crashes on it
    assert [os.path.basename(p) for p in q.torn_paths] == ["work-torn.json"]
    assert get_metrics().counter("serve.queue.torn").value == before + 1
    # re-scanning the SAME damage does not inflate the counter...
    q.items()
    assert get_metrics().counter("serve.queue.torn").value == before + 1
    # ...but a rewrite (new damage) counts again
    time.sleep(0.01)
    with open(os.path.join(qdir, "work-torn.json"), "w") as f:
        f.write("{{")
    os.utime(os.path.join(qdir, "work-torn.json"))
    q.items()
    assert get_metrics().counter("serve.queue.torn").value >= before + 1
    # the torn set rides queue stats (serve stats / report CLI)
    assert "work-torn.json" in q.stats()["torn"]


# -- the chaos acceptance (real driver, real subprocesses) -------------------

def _wait_journal(jpath, n, timeout_s=300.0):
    t0 = time.time()
    while time.time() - t0 < timeout_s:
        if os.path.exists(jpath):
            with open(jpath) as f:
                got = sum(1 for line in f if line.strip())
            if got >= n:
                return got
        time.sleep(0.1)
    raise AssertionError(f"journal never reached {n} lines")


def test_chaos_sigkill_mid_item_reclaim_resume_exactly_once(tmp_path):
    """SIGKILL the daemon (and its drain child) mid-item under seeded
    transient+hang injection; a restarted daemon reclaims the expired
    lease and completes via checkpoint resume — journaled measurements
    replayed (the driver's ``resume:`` line + ``fault.resumed``), store
    warmed, re-query exact — the item's effect lands exactly once.

    Telemetry-plane acceptance rides along (ISSUE 12): the work item is
    enqueued under a trace context, and the SUCCESSOR daemon — which
    never saw the originating process — resumes the drain under the
    SAME trace_id (re-read from the envelope), stamping it into its own
    bundle and its drain child's."""
    from tenzing_tpu.obs.context import new_trace

    qdir = str(tmp_path / "q")
    store = str(tmp_path / "store.json")
    q = WorkQueue(qdir)
    req = DriverRequest(workload="attn", smoke=True, mcts_iters=6,
                        climb_budget=6, search_iters=2, iters=6,
                        inject_faults="transient:0.3:7,hang:0.05:11",
                        inject_hang_secs=1.0, measure_timeout=300.0)
    fp = fingerprint_of(req)
    ctx = new_trace()
    q.enqueue(fp, req.to_json(), reason="cold", trace=ctx)
    exact = fp.exact_digest
    ckpt = q.checkpoint_dir_for(exact)

    daemon = subprocess.Popen(
        [sys.executable, "-m", "tenzing_tpu.serve.daemon",
         "--queue", qdir, "--store", store,
         "--poll", "0.2", "--heartbeat", "0.3", "--lease-ttl", "2"],
        cwd=REPO, start_new_session=True,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    try:
        prior = _wait_journal(os.path.join(ckpt, "measurements.jsonl"), 2)
    finally:
        # SIGKILL the whole group: daemon AND its drain child die with
        # no chance to release the lease or flush anything
        os.killpg(daemon.pid, signal.SIGKILL)
        daemon.wait()
    assert os.path.exists(q.lease_path_for(exact)), \
        "a SIGKILLed worker must leave its lease behind (mtime now stale)"
    assert len(q) == 1, "the item must survive the kill"
    time.sleep(2.2)  # age the lease past the TTL

    daemon_bundle = str(tmp_path / "daemon.jsonl")
    r = subprocess.run(
        [sys.executable, "-m", "tenzing_tpu.serve.daemon",
         "--queue", qdir, "--store", store, "--once", "--lease-ttl", "2",
         "--trace-out", daemon_bundle],
        cwd=REPO, capture_output=True, text=True, timeout=500)
    assert r.returncode == 0, r.stderr[-2000:]
    summary = json.loads(r.stdout.splitlines()[-1])
    assert summary["counters"]["reclaimed"] == 1, summary
    assert summary["counters"]["completed"] == 1, summary

    # checkpoint resume actually replayed the dead worker's measurements
    log = open(os.path.join(ckpt, "drain.log")).read()
    resumes = [line for line in log.splitlines()
               if line.startswith("resume: ")]
    assert resumes, "the restarted drain must resume from the journal"
    restored = int(resumes[-1].split()[1])
    assert restored >= prior >= 2
    verdict = json.load(open(os.path.join(ckpt, "verdict.json")))
    assert verdict["fault"]["resumed"] is True
    assert verdict["fault"]["injected"]  # the chaos spec really fired

    # exactly once: item + lease consumed, store warmed, re-query exact
    assert len(q) == 0
    assert not os.path.exists(q.lease_path_for(exact))
    st = ScheduleStore(store)
    assert st.best(exact) is not None
    from tenzing_tpu.serve.resolver import Resolver

    res = Resolver(st).resolve(req)
    assert res.tier == "exact"
    assert res.provenance["compiles"] == 0

    # the successor — a fresh process that never met the enqueuer —
    # drained under the envelope's trace_id: its own bundle (daemon.drain
    # + the store merge) and its drain child's both carry it, and the
    # stitcher ties the two processes into one trace
    from tenzing_tpu.obs.export import read_jsonl, stitch

    drain_spans = [rec for rec in read_jsonl(daemon_bundle)
                   if rec.get("name") == "daemon.drain"]
    assert drain_spans, "successor daemon recorded no drain span"
    assert drain_spans[0]["attrs"]["trace_id"] == ctx.trace_id
    merge_spans = [rec for rec in read_jsonl(daemon_bundle)
                   if rec.get("name") == "serve.store.flush"]
    assert merge_spans
    assert merge_spans[0]["attrs"]["trace_id"] == ctx.trace_id
    child_bundle = os.path.join(ckpt, "trace", "trace.jsonl")
    assert os.path.exists(child_bundle), \
        "the traced daemon's child must archive its own bundle"
    child_traced = [rec for rec in read_jsonl(child_bundle)
                    if (rec.get("attrs") or {}).get("trace_id")
                    == ctx.trace_id]
    assert child_traced, "child spans must carry the item's trace_id"
    merged = stitch([daemon_bundle, child_bundle])
    t = merged["traces"][ctx.trace_id]
    assert t["n_processes"] == 2
    assert "daemon.drain" in t["names"]
    assert "serve.store.flush" in t["names"]


def test_malformed_item_poisons_through_the_real_child(tmp_path):
    """A deterministic-failure item (unknown workload → DriverConfigError
    before any backend touch) lands in the poison quarantine through the
    real subprocess runner — the error class crosses the process
    boundary via the verdict report, not stderr scraping."""
    qdir = str(tmp_path / "q")
    store = str(tmp_path / "store.json")
    q = WorkQueue(qdir)
    good = DriverRequest(workload="spmv", m=512)
    fp = fingerprint_of(good)
    bad = good.to_json()
    bad["workload"] = "bogus"
    os.makedirs(qdir, exist_ok=True)
    atomic_write_json(q.path_for(fp.exact_digest), {
        "kind": "search_request", "reason": "cold",
        "fingerprint": fp.to_json(), "request": bad,
        "checkpoint": q.checkpoint_dir_for(fp.exact_digest),
    })
    r = subprocess.run(
        [sys.executable, "-m", "tenzing_tpu.serve.daemon",
         "--queue", qdir, "--store", store, "--once", "--max-failures", "1"],
        cwd=REPO, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    summary = json.loads(r.stdout.splitlines()[-1])
    assert summary["counters"]["poisoned"] == 1, summary
    poison = read_checked_json(q.poison_path_for(fp.exact_digest))
    assert poison["attempts"][-1]["error_class"] == "deterministic"
    assert "bogus" in poison["attempts"][-1]["message"]
    assert len(q) == 0


# -- fleet telemetry plane (ISSUE 12): trace-context propagation -------------

def test_drain_runs_under_item_trace_context(tmp_path):
    """The trace context stamped into the work-item envelope at enqueue
    is ambient for the whole drain: the daemon.drain span AND the store
    merge's serve.warm / serve.store.flush spans carry its trace_id."""
    from tenzing_tpu.obs.context import new_trace
    from tenzing_tpu.obs.tracer import Tracer, set_tracer

    qdir = str(tmp_path / "q")
    q = WorkQueue(qdir)
    req = DriverRequest(workload="spmv", m=512)
    fp = fingerprint_of(req)
    ctx = new_trace()
    q.enqueue(fp, req.to_json(), reason="cold", trace=ctx)
    item = read_checked_json(q.path_for(fp.exact_digest))
    assert item["trace"] == ctx.to_json()

    tr = Tracer(enabled=True)
    prev = set_tracer(tr)
    try:
        d = DrainDaemon(_opts(tmp_path),
                        runner=lambda p, pl, t: _ok_verdict(),
                        log=lambda m: None)
        s = d.run()
        assert s["counters"]["completed"] == 1
    finally:
        set_tracer(prev)
    spans = {s.name: s for s in tr.spans()}
    assert spans["daemon.drain"].attrs["trace_id"] == ctx.trace_id
    assert spans["serve.warm"].attrs["trace_id"] == ctx.trace_id
    assert spans["serve.store.flush"].attrs["trace_id"] == ctx.trace_id
    # an item enqueued WITHOUT a trace drains unstamped (no leakage of
    # the previous item's context through the process default)
    q.enqueue(fp, req.to_json(), reason="cold")
    tr2 = Tracer(enabled=True)
    prev = set_tracer(tr2)
    try:
        DrainDaemon(_opts(tmp_path),
                    runner=lambda p, pl, t: _ok_verdict(),
                    log=lambda m: None).run()
    finally:
        set_tracer(prev)
    drain2 = [s for s in tr2.spans() if s.name == "daemon.drain"]
    assert drain2 and "trace_id" not in drain2[0].attrs


def test_exec_item_adopts_envelope_then_env_and_restores(tmp_path,
                                                         monkeypatch):
    """exec_item prefers the envelope's trace (the SIGKILL-survivable
    copy), falls back to the env var, and restores the process default
    on the way out (the in-process drain loop must not leak item N's
    context into item N+1)."""
    from tenzing_tpu.obs import context as obs_context
    from tenzing_tpu.obs.context import TRACE_ENV, new_trace
    from tenzing_tpu.serve import daemon as daemon_mod

    seen = {}

    def fake_run(req):
        seen["ctx"] = obs_context.current()

        class R:
            verdict = {"metric": "m", "value": 1.0}

        return R()

    import tenzing_tpu.bench.driver as driver_mod

    monkeypatch.setattr(driver_mod, "run", fake_run)
    q = WorkQueue(str(tmp_path / "q"))
    req = DriverRequest(workload="spmv", m=512)
    fp = fingerprint_of(req)
    env_ctx = new_trace()
    monkeypatch.setenv(TRACE_ENV, env_ctx.to_env_value())
    # envelope wins over env
    envelope_ctx = new_trace()
    path = q.enqueue(fp, req.to_json(), reason="cold", trace=envelope_ctx)
    daemon_mod.exec_item(read_checked_json(path), path)
    assert seen["ctx"].trace_id == envelope_ctx.trace_id
    assert obs_context.current() is None  # restored
    # env is the fallback when the envelope has no trace
    path = q.enqueue(fp, req.to_json(), reason="cold")
    daemon_mod.exec_item(read_checked_json(path), path)
    assert seen["ctx"].trace_id == env_ctx.trace_id
    assert obs_context.current() is None
