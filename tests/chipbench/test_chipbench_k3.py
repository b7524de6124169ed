"""Graph500 kernel 3's part of the benchmark without the chip: the plain
reference against itself (every rule broken in turn, and named), the
driver's fast failure on an answer without a tree, the six readers on a
small scoped trace and without one, the cost function, and one rehearsal
of ``g500-s20k3.sssp-sat`` through the real command at scale 9."""

import os

import numpy as np
import pytest

from chipbench import k3cost, k3scopes, scopes
from chipbench.k3ref import RULES, K3Reference
from chipbench.spec import CHECKOUT, Spec

import tiny_scoped_trace as T
from rehearse import check_line, run_cell, small_benchmark

HERE = os.path.dirname(os.path.abspath(__file__))
SCOPED = os.path.join(HERE, "data", "tiny_scoped.xplane.pb")
NS = 1e-9
CELL = "g500-s20k3.sssp-sat"
READERS = ["sssp_device_ms", "sssp_round_ms", "sssp_parents_ms",
           "sssp_rounds", "sssp_gather_share", "sssp_hbm_share"]


# --- the reference against itself -----------------------------------------

#   0 --1/4-- 1 --1/4-- 3        5 --1/2-- 6        7
#   0 --1/4-- 2 --1/4-- 3 --1/2-- 4
EDGES = [(0, 1, .25), (0, 2, .25), (1, 3, .25), (2, 3, .25), (3, 4, .5),
         (5, 6, .5)]
INF = np.inf
DIST = np.array([0, .25, .25, .5, 1., INF, INF, INF])
PARENTS = np.array([0, 0, 0, 2, 3, -1, -1, -1])


@pytest.fixture(scope="module")
def ref():
    r = np.array([e[0] for e in EDGES] + [e[1] for e in EDGES])
    c = np.array([e[1] for e in EDGES] + [e[0] for e in EDGES])
    w = np.array([e[2] for e in EDGES] * 2)
    order = np.argsort(r * 8 + c)
    return K3Reference(8, r[order], c[order], w[order])


def test_reference_accepts_either_of_two_equal_parents(ref):
    assert np.array_equal(ref.distances(0), DIST)
    assert ref.check_exact(DIST.astype(np.float32), 0) is None
    assert ref.check_tree(DIST, PARENTS, 0) is None
    other = PARENTS.copy()
    other[3] = 1
    assert ref.check_tree(DIST, other, 0) is None
    # a component of two, and a vertex alone
    assert ref.check_tree(
        np.array([INF] * 5 + [0, .5, INF]),
        np.array([-1] * 5 + [5, 5, -1]), 5) is None
    assert ref.check_tree(
        np.array([INF] * 7 + [0.]), np.array([-1] * 7 + [7]), 7) is None


@pytest.mark.parametrize("rule,dist,parents", [
    (1, {0: .25}, {}),                 # the root not at distance 0
    (1, {}, {0: 1}),                   # the root not its own parent
    (2, {}, {4: -1}),                  # a reached vertex without a parent
    (2, {}, {4: 1}),                   # (parent, v) is not an edge
    (2, {4: 1.25}, {}),                # d[v] != d[parent] + w
    (2, {1: .125}, {}),                # too short: its own equation fails
    (4, {7: np.nan}, {}),              # unreached, not +inf
    (4, {}, {7: 3}),                   # unreached, with a parent
    (5, {}, {1: 3, 3: 1}),             # a parent cycle
    (5, {5: 2., 6: 2.5}, {5: 6, 6: 5}),  # reached, no path to the root
])
def test_reference_names_the_rule_an_answer_breaks(ref, rule, dist, parents):
    d, p = DIST.copy(), PARENTS.copy()
    for k, v in dist.items():
        d[k] = v
    for k, v in parents.items():
        p[k] = v
    bad = ref.check_tree(d, p, 0)
    assert bad is not None and bad.startswith(f"root 0: rule {rule} "), bad
    assert RULES[rule] in bad


def test_reference_rule_3_on_an_edge_outside_the_tree(ref):
    """A tree that is consistent along its own edges and wrong across
    another: 3 reached through 1 at 3/4 when 2 offers 1/2."""
    r = np.array([0, 0, 1, 1, 2, 2, 3, 3])
    c = np.array([1, 2, 0, 3, 0, 3, 1, 2])
    w = np.array([.25, .25, .25, .5, .25, .25, .5, .25])
    g = K3Reference(4, r, c, w)
    assert g.check_tree([0, .25, .25, .5], [0, 0, 0, 2], 0) is None
    bad = g.check_tree([0, .25, .25, .75], [0, 0, 0, 1], 0)
    assert bad is not None and "rule 3" in bad
    assert "reference says 0.5" in g.check_exact([0, .25, .25, .75], 0)
    # an edge from the reached set to a vertex called unreached
    bad = g.check_tree([0, .25, .25, INF], [0, 0, 0, -1], 0)
    assert bad is not None and "rule 3" in bad and "leaves" in bad


def test_reference_follows_parents_across_a_zero_weight_edge():
    """The specification draws weights from [0, 1).  Across a zero-weight
    edge both ends close a shortest path for each other: rule 2 holds of
    a tree in which they choose each other, only following the parents
    (rule 5) shows it is none."""
    #   0 --1-- 1 --1/2-- 2 ==0== 3 --1/2-- 1
    r = np.array([0, 1, 1, 1, 2, 2, 3, 3])
    c = np.array([1, 0, 2, 3, 1, 3, 1, 2])
    w = np.array([1, 1, .5, .5, .5, 0, .5, 0])
    g = K3Reference(4, r, c, w)
    d = [0, 1, 1.5, 1.5]
    assert g.check_exact(d, 0) is None
    for tree in ([0, 0, 1, 1], [0, 0, 3, 1], [0, 0, 1, 2]):
        assert g.check_tree(d, tree, 0) is None
    bad = g.check_tree(d, [0, 0, 3, 2], 0)
    assert bad is not None and "rule 5" in bad and RULES[5] in bad


def test_reference_refuses_what_its_checks_rest_on():
    with pytest.raises(ValueError, match="negative"):
        K3Reference(2, [0, 1], [1, 0], [-.5, -.5])
    with pytest.raises(ValueError, match="not sorted"):
        K3Reference(2, [1, 0], [0, 1], [.5, .5])


# --- the driver ------------------------------------------------------------


def test_driver_ends_the_run_on_an_answer_without_a_tree():
    spec = Spec(os.path.join(CHECKOUT, "BENCHMARK.json"))
    assert spec.traffic("sssp-sat")["driver"] == "serve_closed_k3"
    drv = spec.load_module("drivers", "serve_closed_k3")
    drv.require_tree({"dist": 0, "parents": 0, "batch_niter": 3})
    with pytest.raises(SystemExit) as e:
        drv.require_tree({"dist": 0, "batch_niter": 3})  # the parent's
    assert "'dist' and 'parents'" in str(e.value) and e.value.code != 0
    # every answer gets the O(1) root check; only the sample is kept
    s = drv.K3Sampler(3, 8, 2)
    good = {"dist": np.array([0., 1.]), "parents": np.array([0, 0])}
    for i in range(8):
        s.take(i, 0, good)
    assert len(s.kept) == 2 and not s.problems
    s.take(9, 1, good)
    assert "root 1 is not its own parent at distance 0" in s.problems[0]


# --- the readers -----------------------------------------------------------

#: ``tiny_scoped_trace``'s program under this kind's scopes, and its last
#: copy made the parents pass
K3_TABLE = dict(
    {i: nm.replace("bfs.level", "sssp.round").replace("bfs.init", "sssp.init")
     for i, nm in T.TABLE.items()},
    **{"copy.8": "jit(serve_sssp_w16)/jit(_sssp_batch_impl)/sssp.parents/"
                 "jit(_ell_minplus_parents)/ell.bucket0/gather/gather"},
)


def test_scopes_of_this_kind_on_the_small_trace():
    red = k3scopes.reduce_scopes(SCOPED, {T.MODULE: K3_TABLE})
    by = red["by_scope"]
    assert not any(k.startswith("bfs.") for k in by)
    assert by["sssp.init"] == pytest.approx(500 * NS)
    assert by["sssp.parents/ell.bucket0/gather"] == pytest.approx(500 * NS)
    assert by["sssp.round/ell.bucket0/fold"] == pytest.approx(1250 * NS)
    assert red["unscoped_s"] == 0
    assert sum(by.values()) == pytest.approx(red["device_s"])
    assert [[round(s / NS) for s in lv] for lv in red["levels"]] == [
        [3000, 4000, 2000], [3000, 4000]]
    ctx = {"_scoped": red}
    assert k3scopes.round_ms(ctx) == pytest.approx(3000 * NS * 1e3)
    assert k3scopes.scope_ms(ctx, "sssp.parents") == pytest.approx(500e-6)
    assert k3scopes.share(ctx) == pytest.approx(
        100 * (5425 + 500 + 1250) / 9000)
    # the same trace under the BFS names holds nothing of this kind's
    red = k3scopes.reduce_scopes(SCOPED, {T.MODULE: T.TABLE})
    assert k3scopes.scope_ms({"_scoped": red}, "sssp.parents") is None
    # and scopes.py's own reading of it is what it was
    assert "bfs.level/ell.bucket0/gather" in scopes.reduce_scopes(
        SCOPED, {T.MODULE: T.TABLE})["by_scope"]


def test_least_bytes_of_a_batch():
    n, slots, width = 1 << 20, 36_953_104, 16
    sweep = 8 * slots + 2 * 4 * n * width
    assert sweep == 429_842_560  # the issue's "430 MB a round at least"
    assert k3cost.sssp_batch_least_bytes(n, slots, width, 15) == (
        16 * sweep + 4 * n * width)


@pytest.mark.parametrize("name", READERS)
def test_readers_give_a_number_on_a_trace_and_none_without(name):
    from combblas_tpu import obs

    spec = Spec(os.path.join(CHECKOUT, "BENCHMARK.json"))
    read = spec.load_module("layers", name).read
    obs.reset()
    # nothing traced, no counter, a program without these scopes: None
    assert read({"device": {"kind": "TPU v5 lite"}}) is None
    bare = k3scopes.reduce_scopes(SCOPED, {})
    assert bare["by_scope"] is None
    empty = {"_scoped": bare, "device": {"kind": "TPU v5 lite"}}
    if name not in ("sssp_device_ms",):
        assert read(empty) is None
    from chipbench import devtrace

    ctx = {
        "_scoped": k3scopes.reduce_scopes(SCOPED, {T.MODULE: K3_TABLE}),
        "trace": devtrace.reduce_xplane(SCOPED),
        "device": {"kind": "TPU v5 lite"},
        "sssp_cost": {"n": 64, "slots": 96, "width": 16},
    }
    obs.enable(install_hooks=False)
    try:
        # (other batches than the slice's two executions, which ran 3
        # and 2 rounds: the share of the roofline reads the trace's)
        obs.count("serve.sssp.rounds", 3, width=16)
        obs.count("serve.sssp.rounds", 4, width=16)
        obs.count("serve.sssp.batches", 1, width=16)
        obs.count("serve.sssp.batches", 1, width=16)
        value = read(ctx)
    finally:
        obs.disable()
        obs.reset()
    want = {
        "sssp_device_ms": 9000e-6, "sssp_round_ms": 3000e-6,
        "sssp_parents_ms": 500e-6, "sssp_rounds": 3.5,
        "sssp_gather_share": 100 * 7175 / 9000,
        "sssp_hbm_share": 100 * (
            k3cost.sssp_batch_least_bytes(64, 96, 16, 2.5) / 819e9) / 9000e-9,
    }[name]
    assert value == pytest.approx(want)


def test_the_cell_is_appended_and_its_readers_wait_for_a_benchmark_pr():
    spec = Spec(os.path.join(CHECKOUT, "BENCHMARK.json"))
    # the driver takes new per_layer entries only at the end of the list,
    # test_chipbench_scopes.py holds PR 23's eleven there: the six
    # readers are files the k3 driver logs, and no entry (PERF.md sec. 7)
    names = [m["name"] for m in spec.doc["per_layer"]]
    assert not set(READERS) & set(names)
    drv = spec.load_module("drivers", "serve_closed_k3")
    assert list(drv.LAYERS) == READERS
    assert spec.doc["workloads"][-1]["name"] == CELL
    assert spec.doc["configs"][-1]["name"] == "g500-s20-k3-1x1"
    cell = spec.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "g500-s20-k3-1x1", "sssp-sat", 1)
    reported = {m["name"] for m in spec.metrics_for(CELL, "end_to_end")}
    assert reported == {"qps", "setup_s"}
    mine = {m["name"] for m in spec.metrics_for(CELL, "per_layer")}
    assert {"sat_lane_fill", "execute_ms", "scatter_ms", "launch_ms",
            "readback_ms", "to_global_ms", "readback_mb_per_query",
            "scatter_copied_mb", "batch_gap_ms", "hbm_peak_gb"} <= mine
    assert not any(m.startswith(("bfs_", "k2_")) for m in mine)
    cfg = spec.config("g500-s20-k3-1x1")
    assert list(cfg["reduced"]) == ["scale"] and cfg["kinds"] == ["sssp"]
    assert (cfg["scale"], cfg["edgefactor"], cfg["graph_seed"]) == (20, 16, 1)
    mix = spec.traffic("sssp-sat")
    assert (mix["kind"], mix["in_flight"]) == ("sssp", 32)
    assert mix["check"] == {"exact": 2, "tree": 4}


# --- the cell, rehearsed ----------------------------------------------------


def test_the_cell_through_the_real_command(tmp_path):
    bench = small_benchmark(str(tmp_path))
    # (a seed beyond 32 signed bits, as the driver's are)
    r, line = run_cell(bench, CELL, seed=2300001111, seconds=2)
    assert r.returncode == 0, r.stderr[-2000:]
    m = check_line(line)
    assert set(m) == {"qps", "setup_s"} and m["qps"] > 0
    assert "kernel 3: checked 4 sampled answers" in r.stderr
    r, line = run_cell(bench, CELL, trace=1, seed=4, seconds=2)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "deployment g500-s20-k3-1x1: snapshot" in r.stderr
    m = check_line(line)
    assert {"sat_lane_fill", "execute_ms", "scatter_ms",
            "launch_ms", "readback_ms", "to_global_ms",
            "readback_mb_per_query", "scatter_copied_mb", "batch_gap_ms",
            "load_s", "warmup_s", "compiles_in_window"} <= set(m)
    assert m["compiles_in_window"] == 0 and m["sat_lane_fill"] > 90
    # the kind's own readings are logged, not in the line: the counter's
    # on any platform, the device trace's only where there is a device plane
    logged = dict(ln.split("layer ", 1)[1].split(": ", 1)
                  for ln in r.stderr.splitlines() if "layer sssp_" in ln)
    assert list(logged) == READERS and not set(READERS) & set(m)
    assert 2 <= float(logged.pop("sssp_rounds")) <= 64
    assert set(logged.values()) == {"nothing to read"}
    # two [n, 16] blocks of four bytes a batch, over its 16 requests (a
    # little more where the drain's last batch was not full)
    assert m["readback_mb_per_query"] == pytest.approx(
        2 * 4 * 512 / 1e6, rel=0.05)
