"""One in-process CPU smoke run of ``driver.run`` for each branch of the
driver that no other tier-1 test enters (ISSUE 31 step 1): the four
post-search provenance reports, the planted tile menu, the serialized
fleet, and the two workloads with hand incumbents.  ``.github/workflows/
ci.yml`` runs the same requests through ``bench.py``; the invariants it
checks after each run are the model for the ones below.

Each case pins what the serve plane parses: the verdict's keys and their
order at both levels, the ``metric`` string, ``fault.verified`` and that no
block carries ``error``.  The pins were taken from the driver as it stood
before ``_run`` was split into phases (commit b45c30a), and the split is
held to them unedited.
"""

import json

import pytest

from tenzing_tpu.bench.driver import DriverRequest, run

TOP = ["metric", "value", "unit", "vs_baseline", "device", "perf",
       "naive_us", "search_floor_s", "screen_floor_s", "final_floor_s",
       "mcts_screen_floor_s", "winner_label", "recorded_seeds"]
PERF = ["compiled_programs", "compile_secs", "compile_cache_dir", "prefetch"]
PREFETCH = ["workers", "issued", "hits", "wasted", "failed", "surfaced",
            "dropped"]
FAULT = ["degraded", "quarantined", "resumed", "verified"]
ATTRIB = ["schedule", "source", "n_ops", "n_timed", "sum_of_parts_us",
          "critical_path_us", "measured_us", "dispatch_overhead_us",
          "overlap_efficiency", "critical_path", "per_lane_busy_us",
          "utilization", "timeline"]
FUSED = ["regions", "region_sizes", "fused_ops", "n_ops_total", "tiles",
         "measured_us", "compile_secs", "compiled_programs", "verified",
         "dispatch_overhead_us", "sum_of_parts_us"]
CHUNKED = ["menus", "searched_counts", "n_candidates_chunked", "chosen",
           "hidden_comm_us"]
SYNTH = ["menus", "searched_sketches", "n_candidates_synth", "chosen",
         "est_comm_us", "measured_hidden_us", "verified", "note"]
DISTRIBUTED = ["workers", "measure_batch", "jobs", "failed_jobs", "wall_s",
               "candidates", "distinct_candidates", "best_cost_us",
               "candidates_per_s", "rounds", "singles", "hints",
               "batch_occupancy", "reclaimed_subtrees", "worker_exits",
               "worker_restarts", "job_wall_s", "scaling_factor",
               "incumbent_costs_s"]

# id -> (request overrides, metric, perf keys after PERF, top keys after TOP)
CASES = {
    "profile+fuse": (
        dict(workload="attn", mcts_iters=8, profile_winner=True,
             profile_repeats=3, fuse_winner=True),
        "attn_blockwise_pct50_searched_n64", ["fused"], ["attrib", "fault"]),
    "chunk": (
        dict(workload="attn", mcts_iters=12, chunk=True),
        "attn_blockwise_pct50_searched_n64", ["chunked"], ["fault"]),
    "synth": (
        dict(workload="spmv", mcts_iters=12, synth_collectives=True),
        "spmv_iter_pct50_searched_m512", ["synth"], ["fault"]),
    "tiles": (
        dict(workload="attn", mcts_iters=8, fuse_search_tiles=True),
        "attn_blockwise_pct50_searched_n64", ["fuse_search_tiles"],
        ["fault"]),
    "fleet11": (
        dict(workload="attn", mcts_iters=8, search_workers=1,
             measure_batch=1),
        "attn_blockwise_pct50_searched_n64", ["distributed"], ["fault"]),
    "halo": (dict(workload="halo"), "halo_iter_pct50_searched_n4", [],
             ["fault"]),
    "moe": (dict(workload="moe"), "moe_pipe_pct50_searched_t32", [],
            ["fault"]),
    "spmv": (dict(workload="spmv"), "spmv_iter_pct50_searched_m512", [],
             ["fault"]),
    "attn": (dict(workload="attn", mcts_iters=8),
             "attn_blockwise_pct50_searched_n64", [], ["fault"]),
    "mla_decode": (dict(workload="mla_decode", mcts_iters=6),
                   "mla_decode_pct50_searched_k212", [], ["fault"]),
    "dsa_decode": (dict(workload="dsa_decode", mcts_iters=6),
                   "dsa_decode_pct50_searched_k212", [], ["fault"]),
    "kda_decode": (dict(workload="kda_decode", mcts_iters=6),
                   "kda_decode_pct50_searched_k212", [], ["fault"]),
}
# two files, so that --dist loadfile gives the runs to two workers: the
# switches of one workload here, the plain workloads in
# test_driver_smoke_workloads.py
BRANCHES = ["profile+fuse", "chunk", "tiles", "fleet11"]


def _in_order(err, markers):
    """``markers`` start lines of ``err`` in this order (the phases' own
    messages: a phase that moved would say its piece out of turn)."""
    at = 0
    lines = err.splitlines()
    for m in markers:
        hit = next((i for i in range(at, len(lines))
                    if lines[i].startswith(m)), None)
        assert hit is not None, (m, [l[:40] for l in lines[at:]])
        at = hit + 1


# case -> the messages between the fixed ones, by where they come
EXTRA = {
    "profile+fuse": (["profile-winner: ", "fuse-winner: "], "tail"),
    "chunk": (["chunked: "], "tail"),
    "synth": (["synth: "], "tail"),
    "tiles": (["fuse-search-tiles: menu "], "head"),
    "fleet11": (["fleet: 1w K=1: "], "climb"),
    "halo": (["greedy-overlap incumbent: pct50="], "incumbents"),
    "moe": (["greedy-overlap incumbent: pct50="], "incumbents"),
    "spmv": ([], "incumbents"),
    "attn": ([], "incumbents"),
    "mla_decode": ([], "incumbents"),
    "dsa_decode": ([], "incumbents"),
    "kda_decode": ([], "incumbents"),
}


def _blocks(v):
    """Every dict of the verdict that a provenance report fills."""
    yield "attrib", v.get("attrib")
    yield "fault", v.get("fault")
    for k in ("fused", "fuse_search_tiles", "chunked", "synth",
              "distributed"):
        yield f"perf.{k}", v["perf"].get(k)


@pytest.mark.needs_pinned_host
@pytest.mark.parametrize("case", BRANCHES)
def test_smoke_branch_verdict_is_pinned(case, capfd):
    check_smoke_verdict(case, capfd)


def check_smoke_verdict(case, capfd):
    over, metric, perf_extra, top_extra = CASES[case]
    v = run(DriverRequest(smoke=True, **over)).verdict
    err = capfd.readouterr().err
    # the line bench.py prints round-trips with its order kept
    assert list(json.loads(json.dumps(v))) == list(v)

    assert v["metric"] == metric
    assert v["unit"] == "us" and v["value"] > 0 and v["vs_baseline"] >= 1.0
    assert v["device"]["platform"] == "cpu"
    assert list(v) == TOP + top_extra
    assert list(v["perf"]) == PERF + perf_extra
    assert list(v["perf"]["prefetch"]) == PREFETCH
    assert list(v["fault"]) == FAULT
    assert v["fault"]["verified"] is True
    assert v["fault"]["degraded"] is False
    for where, block in _blocks(v):
        if block is not None:
            assert "error" not in block, (where, block)
    # the baseline is hinted and then measured on every run (ci.yml)
    assert v["perf"]["compiled_programs"] > 0
    assert v["perf"]["prefetch"]["hits"] > 0
    extra, where = EXTRA[case]
    at = lambda w: extra if where == w else []
    _in_order(err, ["backend: "] + at("head") + ["naive: pct50="]
              + at("incumbents")
              + ["mcts wall ", "phase counters:", "bench cache: ",
                 "prefetch: "] + at("climb")
              + ["screen (paired vs naive, wall ", "integrity gate: "]
              + at("tail"))

    if case == "profile+fuse":
        a, f = v["attrib"], v["perf"]["fused"]
        # a searched winner also carries "explain" and "measured_times"
        assert list(a)[:len(ATTRIB)] == ATTRIB
        assert list(a)[len(ATTRIB):] in (["measured_times"],
                                         ["explain", "measured_times"])
        assert a["dispatch_overhead_us"] >= 0
        assert a["n_timed"] > 0 and len(a["timeline"]) == a["n_ops"]
        assert a["critical_path"] and 0 < a["overlap_efficiency"] <= 1
        assert list(f) == FUSED
        assert f["regions"] >= 1 and f["verified"] is True
        assert f["tiles"]["chosen"] in f["tiles"]["menu"]
        assert list(f["tiles"]) == ["chosen", "menu", "per_region",
                                    "by_tiles_us"]
        for k in ("dispatch_overhead_us", "sum_of_parts_us"):
            assert list(f[k]) == ["before", "after"]
        # the fused "before" is the profile's own analysis, not a second one
        assert f["dispatch_overhead_us"]["before"] == round(
            a["dispatch_overhead_us"], 3)
    elif case == "chunk":
        c = v["perf"]["chunked"]
        assert list(c)[:len(CHUNKED)] == CHUNKED
        assert set(c) - set(CHUNKED) <= {"note"}
        assert c["menus"] and all(any(n > 1 for n in m["counts"])
                                  for m in c["menus"].values())
        for base, n in c["chosen"].items():
            assert n in c["menus"][base]["counts"]
        assert list(c["hidden_comm_us"]) == ["estimated", "measured"]
    elif case == "synth":
        s = v["perf"]["synth"]
        assert list(s) == SYNTH
        assert any(len(m["menu"]) >= 2 for m in s["menus"].values())
        for base, label in s["chosen"].items():
            assert label in s["menus"][base]["menu"]
        assert s["note"] and s["verified"] is True
    elif case == "tiles":
        t = v["perf"]["fuse_search_tiles"]
        assert list(t) == ["menu", "planted", "chosen"]
        assert t["planted"] is True and t["chosen"] in t["menu"]
    elif case == "fleet11":
        d = v["perf"]["distributed"]
        assert list(d) == DISTRIBUTED
        assert (d["workers"], d["measure_batch"]) == (1, 1)
        assert d["jobs"] == 2 and d["failed_jobs"] == 0
        assert d["rounds"] == 0 and d["batch_occupancy"] is None
