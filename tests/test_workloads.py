"""``bench/workloads.py``: one row a workload (ISSUE 31).

What the rows answer is pinned from ``bench/driver.py`` as it stood when it
held the six functions that switched on a workload's name (commit b45c30a);
the shape and lane goldens stay in tests/test_driver.py.
"""

import subprocess
import sys

import pytest

from tenzing_tpu.bench import driver, workloads
from tenzing_tpu.bench.driver import DriverRequest
from tenzing_tpu.bench.workloads import WORKLOADS, DriverConfigError

NAMES = ["halo", "spmv", "attn", "mla_decode", "dsa_decode", "kda_decode",
         "moe"]


def test_the_table_is_the_set_of_workloads():
    assert list(WORKLOADS) == NAMES
    assert list(workloads.BUILDERS) == NAMES
    assert all(workloads.BUILDERS[n] is WORKLOADS[n].build for n in NAMES)


# -- (a) the metric series' names: a change re-files every recorded number ----

@pytest.mark.parametrize("over,metric", [
    (dict(workload="halo", smoke=True), "halo_iter_pct50_searched_n4"),
    (dict(workload="halo"), "halo_iter_pct50_searched_n512"),
    (dict(workload="halo", halo_n=64), "halo_iter_pct50_searched_n64"),
    (dict(workload="spmv", smoke=True), "spmv_iter_pct50_searched_m512"),
    (dict(workload="spmv"), "spmv_iter_pct50_searched_m150000"),
    (dict(workload="spmv", m=640, spmv_bw=32),
     "spmv_iter_pct50_searched_m640_bw32"),
    (dict(workload="attn", smoke=True), "attn_blockwise_pct50_searched_n64"),
    (dict(workload="attn"), "attn_blockwise_pct50_searched_n8192"),
    (dict(workload="mla_decode", smoke=True),
     "mla_decode_pct50_searched_k212"),
    (dict(workload="mla_decode"), "mla_decode_pct50_searched_k32908"),
    (dict(workload="dsa_decode", smoke=True),
     "dsa_decode_pct50_searched_k212"),
    (dict(workload="dsa_decode"), "dsa_decode_pct50_searched_k32908"),
    (dict(workload="kda_decode", smoke=True),
     "kda_decode_pct50_searched_k212"),
    (dict(workload="kda_decode"), "kda_decode_pct50_searched_k32908"),
    (dict(workload="moe", smoke=True), "moe_pipe_pct50_searched_t32"),
    (dict(workload="moe"), "moe_pipe_pct50_searched_t8192"),
    (dict(workload="moe", moe_tokens=4096), "moe_pipe_pct50_searched_t4096"),
], ids=lambda v: "-".join(f"{k}={x}" for k, x in v.items())
    if isinstance(v, dict) else None)
def test_metric_goldens(over, metric):
    req = DriverRequest(**over)
    assert workloads.metric_for(req.workload, req) == metric


@pytest.mark.parametrize("lookup", [
    lambda r: workloads.metric_for(r.workload, r),
    lambda r: workloads.workload_cost(r.workload, None),
    workloads.workload_shape,
    workloads.search_lanes,
    workloads.graph_for,
    lambda r: driver.run(r),
], ids=["metric_for", "workload_cost", "workload_shape", "search_lanes",
        "graph_for", "run"])
def test_an_unknown_name_is_a_config_error(lookup):
    with pytest.raises(DriverConfigError, match="unknown workload 'hallo'"):
        lookup(DriverRequest(workload="hallo"))


@pytest.mark.parametrize("name,lanes", [("halo", 8), ("spmv", 2),
                                        ("attn", 2), ("mla_decode", 2),
                                        ("dsa_decode", 2), ("kda_decode", 2),
                                        ("moe", 2)])
def test_lane_rule_per_row(name, lanes):
    assert workloads.search_lanes(DriverRequest(workload=name)) == lanes
    assert workloads.search_lanes(
        DriverRequest(workload=name, smoke=True)) == 2
    assert workloads.search_lanes(
        DriverRequest(workload=name, smoke=True, lanes=5)) == 5


# -- (b) the backend-free builder and the row's naive schedule ---------------

@pytest.mark.needs_pinned_host
@pytest.mark.parametrize("name", NAMES)
def test_graph_builds_and_naive_is_sound(name):
    from tenzing_tpu.verify import ScheduleVerifier

    req = DriverRequest(workload=name, smoke=True)
    g, nbytes = workloads.graph_for(req)
    assert len(list(g.vertices())) > 0
    assert nbytes and all(v >= 0 for v in nbytes.values())
    built = WORKLOADS[name].build(req)
    wargs = built[3]
    assert built[2] == workloads.metric_for(name, req)
    # the device-placing builder and the backend-free one build one graph
    assert sorted(v.name() for v in built[0].vertices()) == \
        sorted(v.name() for v in g.vertices())
    naive = workloads.naive_schedule(name, g, wargs)
    verdict = ScheduleVerifier(g)(naive)
    assert verdict.ok, verdict.witness()


def test_a_name_without_a_row_takes_the_generic_naive():
    """The benchmark's mesh builders call ``naive_schedule`` with names of
    their own (``halo_mesh``, ``moe_mesh``, ``toy``): the first decision the
    SDP offers, as before the table."""
    g, _ = workloads.graph_for(DriverRequest(workload="spmv", smoke=True))
    want = [op.desc() for op in
            workloads.naive_schedule("spmv", g, None).vector()]
    for name in ("halo_mesh", "moe_mesh", "toy"):
        got = workloads.naive_schedule(name, g, None)
        assert [op.desc() for op in got.vector()] == want


# -- what the driver's incumbent and climb phases ask of a row ---------------

@pytest.mark.needs_pinned_host
@pytest.mark.parametrize("name,labels", [
    ("halo", ["greedy-overlap"]), ("spmv", []), ("attn", []),
    ("mla_decode", []), ("dsa_decode", []), ("kda_decode", []),
    ("moe", ["greedy-overlap"])])
def test_smoke_incumbents_per_row(name, labels):
    from tenzing_tpu.core.platform import Platform
    from tenzing_tpu.verify import ScheduleVerifier

    req = DriverRequest(workload=name, smoke=True)
    built = WORKLOADS[name].build(req)
    plat = Platform.make_n_lanes(2)
    inc = WORKLOADS[name].incumbents(req, built[0], built[3], plat)
    assert [label for label, _ in inc.seqs] == labels
    assert inc.seed_paths == [] and inc.rollout_policy is None
    # only attn's are driven on the menu of a chip that may refuse a kernel
    assert inc.tolerant is (name == "attn")
    for _, seq in inc.seqs:
        assert ScheduleVerifier(built[0])(seq).ok
    assert WORKLOADS[name].climb_config(req, plat, []) == []


def test_full_size_climbs_per_row():
    """The climbs the driver runs without a recorded database: halo's budget
    split 4:3 over the aliased recipe at 3 and 6 lanes, moe's one."""
    from tenzing_tpu.core.platform import Platform
    from tenzing_tpu.models.halo_pipeline import HALO_PHASES
    from tenzing_tpu.models.moe_pipeline import PHASES

    plat = Platform.make_n_lanes(8)
    halo = WORKLOADS["halo"].climb_config(
        DriverRequest(workload="halo"), plat, [])
    assert [(len(p.lanes), ph, pf, pri, b, n, c)
            for p, ph, pf, pri, b, n, c in halo] == [
        (3, HALO_PHASES, workloads.halo_alias_prefer, None, 25,
         "halo_alias", None),
        (6, HALO_PHASES, workloads.halo_alias_prefer, None, 19,
         "halo_alias", None)]
    moe = WORKLOADS["moe"].climb_config(
        DriverRequest(workload="moe"), plat, [])
    assert moe == [(plat, PHASES, workloads.moe_bf16_prefer, None, 44,
                    "moe_bf16", None)]
    for name in ("spmv", "attn"):
        assert WORKLOADS[name].climb_config(
            DriverRequest(workload=name), plat, []) == []
    assert WORKLOADS["halo"].phases() is HALO_PHASES
    assert WORKLOADS["moe"].phases() is PHASES
    assert WORKLOADS["spmv"].phases() == ("",)


# what ``halo_alias_prefer`` picks, op by op: every transfer on the chip's DMA
# engine, the aliased unpack kernel of each face axis, a z face through the
# window pair (ISSUE 48), XLA's slice for the x and y packs
ALIAS_RECIPE = {
    "pack_px": ".xla", "pack_mx": ".xla", "pack_py": ".xla",
    "pack_my": ".xla", "pack_pz": ".window", "pack_mz": ".window",
    "unpack_px": ".pallas", "unpack_mx": ".pallas",
    "unpack_py": ".pallasf", "unpack_my": ".pallasf",
    "unpack_pz": ".window", "unpack_mz": ".window",
    **{f"xfer_{s}{a}": ".rdma" for s in "pm" for a in "xyz"}}


def _halo_menus(hargs):
    from tenzing_tpu.models.halo_pipeline import build_graph

    g = build_graph(hargs, impl_choice=True, xfer_choice=True)
    return {op.name(): [c.name() for c in op.choices()]
            for op in g.vertices() if hasattr(op, "choices")}


@pytest.mark.parametrize("op_name", sorted(ALIAS_RECIPE))
def test_halo_alias_prefer_per_op(op_name):
    """The climb's start point on the flagship's menus, one case an op."""
    from tenzing_tpu.models.halo import HaloArgs

    menus = _halo_menus(HaloArgs(nq=3, lx=512, ly=512, lz=512, radius=3))
    assert sorted(menus) == sorted(ALIAS_RECIPE)
    assert workloads.halo_alias_prefer(op_name, menus[op_name]) == (
        op_name + ALIAS_RECIPE[op_name])


def test_halo_alias_prefer_without_the_window_pair():
    """A grid whose z face is not lane-thin (thicker than y is long) has no
    ``.window`` entry: the z faces fall back to the recipe up to PR 47
    (``.xla`` packs, ``.pallasb`` unpacks where the batched kernel is on
    the menu, else XLA's), everything else as on the flagship."""
    from tenzing_tpu.models.halo import HaloArgs

    menus = _halo_menus(HaloArgs(nq=3, lx=64, ly=2, lz=512, radius=3))
    assert not any(c.endswith(".window") for m in menus.values() for c in m)
    for op_name, menu in menus.items():
        want = ALIAS_RECIPE[op_name]
        if op_name[-1] == "z" and not op_name.startswith("xfer_"):
            want = ".pallasb" if (
                op_name.startswith("unpack_")
                and op_name + ".pallasb" in menu) else ".xla"
        elif op_name + want not in menu:
            want = ".xla"  # a y menu without the flat kernel
        assert workloads.halo_alias_prefer(op_name, menu) == op_name + want
    assert workloads.halo_alias_prefer(
        "unpack_pz", ["unpack_pz.xla", "unpack_pz.pallas",
                      "unpack_pz.pallasb"]) == "unpack_pz.pallasb"
    assert workloads.alias_unpack_choice(
        "unpack_pz", ["unpack_pz.xla"]) is None


def test_a_recorded_schedule_seeds_the_first_climb():
    """With a recorded winner the first climb replicates its menu choices on
    its lane count, with a third (halo) or half (moe) of the budget."""
    from tenzing_tpu.core.platform import Platform

    req = DriverRequest(workload="spmv", smoke=True)
    g, _ = workloads.graph_for(req)
    rec = workloads.naive_schedule("spmv", g, None)
    plat = Platform.make_n_lanes(2)
    halo = WORKLOADS["halo"].climb_config(
        DriverRequest(workload="halo"), plat, [rec])
    assert [(len(c[0].lanes), c[4], c[5]) for c in halo] == [
        (1, 14, "recorded"), (3, 17, "halo_alias"), (6, 13, "halo_alias")]
    moe = WORKLOADS["moe"].climb_config(
        DriverRequest(workload="moe"), plat, [rec])
    assert [(len(c[0].lanes), c[4], c[5]) for c in moe] == [
        (1, 22, "recorded"), (2, 22, "moe_bf16")]
    chosen = halo[0][6]
    assert chosen and all(v.startswith(".") for v in chosen.values())
    # the policy a fleet worker rebuilds from the spec is the climb's own
    base, suffix = next(iter(chosen.items()))
    assert halo[0][2](base, [base + ".zz", base + suffix]) == base + suffix


@pytest.mark.parametrize("name,glob", [
    ("halo", "experiments/halo_search_tpu_r[45]*.csv"),
    ("spmv", ""),
    ("attn", "experiments/attn_search_tpu_r[45]*.csv"),
    ("mla_decode", ""),
    ("dsa_decode", ""),
    ("kda_decode", ""),
    ("moe", "experiments/moe_search_tpu_r[45]*.csv")])
def test_recorded_database_default_per_row(name, glob):
    assert WORKLOADS[name].seed_csv == glob


# -- (c) the table is below the driver ---------------------------------------

def test_workloads_imports_neither_the_driver_nor_jax():
    code = (
        "import sys, types\n"
        "import tenzing_tpu.bench.workloads as w\n"
        "bad = [m for m in ('jax', 'tenzing_tpu.bench.driver',\n"
        "                   'tenzing_tpu.solve.local', 'tenzing_tpu.learn',\n"
        "                   'tenzing_tpu.fault')\n"
        "       if m in sys.modules]\n"
        "assert not bad, bad\n"
        "# the serve package itself still imports the driver, for\n"
        "# DriverRequest; the fingerprint's arithmetic needs no backend\n"
        "from tenzing_tpu.serve.fingerprint import fingerprint_of\n"
        "for name in w.WORKLOADS:\n"
        "    req = types.SimpleNamespace(workload=name, smoke=False,\n"
        "        lanes=None, halo_n=512, m=None, spmv_bw=None,\n"
        "        moe_tokens=8192)\n"
        "    fp = fingerprint_of(req)\n"
        "    assert fp.workload == name and fp.exact_digest\n"
        "assert 'jax' not in sys.modules\n"
        "print('ok')\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120)
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr


# -- (d) one object under both names ------------------------------------------

@pytest.mark.parametrize("name", [
    # the benchmark's builders
    "naive_schedule", "halo_alias_prefer",
    # bench.py's re-export block
    "ALIAS_UNPACK", "BUILDERS", "DriverConfigError", "alias_unpack_choice",
    "build_attn", "build_halo", "build_moe", "build_spmv", "metric_for",
    "workload_cost",
    # serve/, search/fleet.py
    "graph_for", "workload_shape", "search_lanes", "generic_xla_prefer",
    "moe_bf16_prefer", "recorded_prefer"])
def test_the_driver_reexports_the_tables_own_objects(name):
    import bench

    assert getattr(driver, name) is getattr(workloads, name)
    if hasattr(bench, name):
        assert getattr(bench, name) is getattr(workloads, name)


def test_one_config_error_class():
    """serve/daemon.py catches ``driver.DriverConfigError``: what the table
    raises has to be that class."""
    with pytest.raises(driver.DriverConfigError):
        workloads.workload_shape(DriverRequest(workload="hallo"))


# -- (f) the frame of the four provenance reports -----------------------------

REPORTS = [("profile-winner", "step ops on", None),
           ("fuse-winner", "run fused programs on",
            {"error": "degraded: no device"}),
           ("chunked provenance", None, None),
           ("synth provenance", None, None)]


@pytest.mark.parametrize("name,needs_device,skipped", REPORTS)
def test_winner_report_stamps_the_error_of_a_body_that_raises(
        name, needs_device, skipped, capsys):
    def body(t0):
        raise ValueError("x" * 300)

    block = driver.winner_report(name, True, needs_device, body)
    assert block == {"error": "ValueError: " + "x" * 200}
    assert capsys.readouterr().err == \
        f"{name} failed (ValueError: {'x' * 200})\n"


@pytest.mark.parametrize("name,needs_device,skipped", REPORTS)
def test_winner_report_on_a_degraded_run(name, needs_device, skipped,
                                         capsys):
    ran = []
    block = driver.winner_report(
        name, True, needs_device, lambda t0: ran.append(t0) or {"b": 1},
        degraded=True, skipped=skipped)
    err = capsys.readouterr().err
    if needs_device:
        # today's messages, to the letter
        assert err == (f"{name}: skipped (device lost — no hardware to "
                       f"{needs_device})\n")
        assert block == skipped and not ran
    else:
        # the chunk and synth reports need no device for their menus
        assert err == "" and block == {"b": 1} and len(ran) == 1


def test_winner_report_not_enabled_is_no_block():
    assert driver.winner_report("fuse-winner", False, "x", lambda t0: 1 / 0,
                                degraded=True, skipped={"e": 1}) is None
