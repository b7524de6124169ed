"""HipMCL's part of the benchmark without the chip: the generator by
hand on a tiny case and its stated properties, the plain reference
against a dense numpy MCL written out here, the driver's checks each
tripped by one planted fault (a wrong label, one column's mass moved,
the bfloat16 control) and its fast exit on a program without the entry,
the cost function by hand, the seven readers on a small trace of a job
of several programs (and without one), what the cell added to
``BENCHMARK.json`` (order checks, no place pinned), and one rehearsal of
``hipmcl-fam.mcl-batch`` through the real command."""

import json
import os

import numpy as np
import pytest
import scipy.sparse as sp

from chipbench import (devtrace, famgraph, mclcontrol, mclcost, mclref,
                       mclscopes)
from chipbench.spec import CHECKOUT, Spec

from rehearse import check_line, run_cell, small_benchmark

NS = 1e-9
CELL, CONFIG, MIX = "hipmcl-fam.mcl-batch", "hipmcl-fam-1x1", "mcl-batch"
SQ_CELL, SQ_CONFIG = "g500-sq.spgemm-batch", "g500-sq-1x1"
READERS = ["mcl_device_ms", "mcl_expand_ms", "mcl_select_ms",
           "mcl_host_gap_ms", "mcl_iters", "mcl_hbm_share",
           "mcl_hbm_peak_gb"]
LIMITS = dict(chaos_rel=0.05, chaos_abs=1e-4, stored_rel=1e-2,
              column_l1_max=1e-2, column_l1_mean=1e-4)
#: a small graph of the generator, and a select / recover that bind
SMALL = dict(degree=24, smax=96)
KW = dict(select=40, recover=60)


def _spec():
    return Spec(os.path.join(CHECKOUT, "BENCHMARK.json"))


# --- the generator ----------------------------------------------------------


def test_family_sizes_fill_n_from_the_power_law():
    rng = np.random.default_rng(3)
    sizes = famgraph.family_sizes(4096, rng, 8, 1024, 1.5)
    assert sizes.sum() == 4096 and sizes.min() >= 8 and sizes.max() <= 1024 + 7
    # far more small families than large ones
    assert np.median(sizes) < 40 < sizes.max()
    # a remainder under smin joins the family before it
    class Fixed:
        def __init__(self, us):
            self.us = iter(us)

        def random(self):
            return next(self.us)

    assert famgraph.family_sizes(20, Fixed([0.0, 0.0, 0.0]), 8, 16, 1.5).tolist(
        ) == [8, 12]


@pytest.mark.parametrize("scale,seed", [(8, 1), (9, 2), (10, 1)])
def test_family_graph_has_its_stated_properties(scale, seed):
    n, rows, cols, vals, fam = famgraph.family_graph(scale, seed, **SMALL)
    assert n == 1 << scale and len(fam) == n
    assert rows.dtype == cols.dtype == np.int32 and vals.dtype == np.float32
    # no loops, symmetric with one weight an edge, every pair once
    assert not np.any(rows == cols)
    A = sp.csr_matrix((vals, (rows, cols)), shape=(n, n))
    assert A.nnz == len(rows) and (A != A.T).nnz == 0
    # inside a family heavy, across light
    same = fam[rows] == fam[cols]
    assert vals[same].min() >= np.float32(0.3) and vals[same].max() <= 1.0
    assert vals[~same].min() >= np.float32(0.05)
    assert vals[~same].max() <= np.float32(0.3)
    # most of a vertex's weight stays in its family
    assert vals[same].sum() > 4 * vals[~same].sum()
    sizes = np.bincount(fam)
    assert sizes.min() >= 8 and sizes.max() <= 96 + 7
    # nothing in the order of the ids tells a family
    assert np.mean(fam[:-1] == fam[1:]) < 0.2
    # the same seed gives the same graph, another seed another
    again = famgraph.family_graph(scale, seed, **SMALL)
    assert all(np.array_equal(a, b) for a, b in zip(
        (rows, cols, vals, fam), again[1:]))
    assert len(famgraph.family_graph(scale, seed + 1, **SMALL)[1]) != len(rows)


# --- the reference ----------------------------------------------------------


def _dense_mcl(n, rows, cols, vals, *, select, recover, recover_pct=0.9,
               prune=1e-4, eps=1e-3, max_iters=64, inflation=2.0):
    """Upstream's loop on a dense float64 matrix, a column at a time."""
    a = np.zeros((n, n))
    a[rows, cols] = vals
    np.fill_diagonal(a, 1.0)
    a /= a.sum(axis=0)
    chaos, stored = [], []
    for _ in range(max_iters):
        c = a @ a
        c[c < prune] = 0
        for j in range(n):
            col = c[:, j]
            nz = np.sort(col[col > 0])[::-1]
            if len(nz) <= select:
                continue
            th = nz[select - 1]
            if col[col >= th].sum() < recover_pct * col.sum():
                th = min(th, nz[recover - 1]) if len(nz) >= recover else 0
            col[col < th] = 0
        c /= c.sum(axis=0)
        chaos.append(float(np.max(
            (c.max(axis=0) - (c * c).sum(axis=0)) * (c > 0).sum(axis=0))))
        a = c ** inflation
        a /= a.sum(axis=0)
        stored.append(int((a > 0).sum()))
        if chaos[-1] < eps:
            break
    a[a < prune] = 0
    return a, chaos, stored


@pytest.mark.parametrize("scale,seed", [(7, 1), (8, 1), (8, 3)])
def test_reference_equals_a_dense_mcl_written_out(scale, seed):
    n, rows, cols, vals, fam = famgraph.family_graph(scale, seed, **SMALL)
    kw = dict(select=24, recover=36)
    ref = mclref.mcl_reference(n, rows, cols, vals, keep=(1, 2), **kw)
    a, chaos, stored = _dense_mcl(n, rows, cols, vals, **kw)
    assert ref["iters"] == len(chaos) and ref["stored"] == stored
    np.testing.assert_allclose(ref["chaos"], chaos, rtol=1e-9, atol=1e-12)
    assert ref["counts"][0]["bound"] > 0 < ref["counts"][0]["recovered"]
    # labels: the smallest vertex of each component of the symmetrised
    # attractor matrix
    sym = (a + a.T) > 0
    lab = np.arange(n)
    for _ in range(n):
        new = np.where(sym, lab[None, :], n).min(axis=1)
        new = np.minimum(new, lab)
        if np.array_equal(new, lab):
            break
        lab = new
    assert np.array_equal(ref["labels"], lab)
    assert ref["clusters"] == len(np.unique(lab))
    # the kept states are column-stochastic, and cut to columns on request
    for m in ref["matrices"].values():
        np.testing.assert_allclose(m.sum(axis=0), 1.0, rtol=1e-12)
    cut = mclref.mcl_reference(
        n, rows, cols, vals, keep=(2,), columns=np.array([5, 3]), **kw)
    assert (cut["matrices"][2] != ref["matrices"][2][:, [5, 3]]).nnz == 0
    # the multiplies of the first expansion, by hand
    s = sp.csc_matrix((np.ones(len(rows)), (rows, cols)), shape=(n, n))
    s.setdiag(1)
    assert ref["counts"][0]["products"] == int((s @ s).sum())


def test_select_column_by_hand():
    v = np.array([0.4, 0.2, 0.2, 0.1, 0.05, 0.05])
    # the 2nd largest is 0.2, the tie kept: 0.8 of the mass, under 0.9,
    # so the column recovers to its 4th largest
    kept, rec = mclref.select_column(v, 2, 4, 0.9)
    assert rec and kept.tolist() == [True, True, True, True, False, False]
    kept, rec = mclref.select_column(v, 2, 4, 0.75)
    assert not rec and kept.tolist() == [True, True, True, False, False,
                                         False]
    # fewer candidates than the recovery asks for: all of them
    kept, rec = mclref.select_column(v, 2, 9, 0.9)
    assert rec and kept.all()
    assert mclref.select_column(v, 6, 9, 0.9) == (pytest.approx(
        np.ones(6, bool)), False)
    assert mclref.fingerprint([0, 0, 2]) == (2 * 3 * 0x9E3779B1) % 2**32


# --- the checks -------------------------------------------------------------


@pytest.fixture(scope="module")
def s9():
    n, rows, cols, vals, _ = famgraph.family_graph(9, 1, **SMALL)
    pool = np.arange(0, n, 2)
    ref = mclref.mcl_reference(
        n, rows, cols, vals, keep=range(1, 13), columns=pool, **KW)
    ref["pool"] = pool
    return n, rows, cols, vals, ref


def _digest(run):
    return {"iters": run["iters"],
            "chaos": np.asarray(run["chaos"], np.float32),
            "stored": np.asarray(run["stored"]), "clusters": run["clusters"],
            "fingerprint": mclref.fingerprint(run["labels"]),
            "tiers": ("mxu",) * 12 + ("scan",) * (run["iters"] - 12)}


def test_checks_refuse_a_wrong_label_and_a_column_s_mass_moved(s9):
    n, rows, cols, vals, ref = s9
    drv = _spec().load_module("drivers", "library_cluster")
    assert drv.LEAST_JOBS == 4
    good = _digest(ref)
    columns = np.arange(0, len(ref["pool"]), 3)
    want = drv.checked_iterations(good["tiers"], ref["matrices"])
    assert want == [1, 2, 3, 12]
    assert drv.checked_iterations(
        ("windowed",) * 5 + ("scan",) * 4, ref["matrices"]) == [1, 2, 3, 5]
    assert drv.checked_iterations(("scan",) * 2, ref["matrices"]) == [1, 2]

    def state(it):
        coo = ref["matrices"][it].tocoo()
        return coo.row, ref["pool"][coo.col], coo.data.copy()

    checked = {"digest": good, "states": {it: state(it) for it in want}}
    assert drv.check_jobs(ref, [good] * 5, checked, LIMITS, n, columns) == []
    # ONE vertex given its neighbour's label: the fingerprint moves
    labels = ref["labels"].copy()
    v = int(np.flatnonzero(labels != labels[0])[0])
    labels[v] = labels[0]
    wrong = dict(good, fingerprint=mclref.fingerprint(labels))
    found = drv.check_jobs(ref, [wrong] * 5, None, LIMITS, n, columns)
    assert len(found) == 1 and "label fingerprint" in found[0]
    # the same wrong label in one later job: not the first's digest
    found = drv.check_jobs(
        ref, [good, good, wrong, good], None, LIMITS, n, columns)
    assert found == ["job 2: its digest is not the first job's"]
    # a cluster more, an iteration more, a chaos off, entries off
    for change, said in (
            (dict(clusters=good["clusters"] + 1), "clusters"),
            (dict(iters=good["iters"] + 1), "iterations"),
            (dict(chaos=good["chaos"] * np.float32(1.2)), "chaos of"),
            (dict(stored=good["stored"] * 2), "stored after")):
        found = drv.check_jobs(
            ref, [dict(good, **change)], None, LIMITS, n, columns)
        assert len(found) == 1 and said in found[0], (said, found)
    # a state with one sampled column's mass moved between two entries
    r, c, d = state(2)
    col = int(ref["pool"][columns[4]])
    at = np.flatnonzero(c == col)
    assert len(at) >= 2
    d[at[0]] += 0.02
    d[at[1]] -= min(0.02, d[at[1]])
    moved = {"digest": good, "states": dict(checked["states"])}
    moved["states"][2] = (r, c, d)
    found = drv.check_jobs(ref, [good], moved, LIMITS, n, columns)
    assert len(found) == 1 and found[0].startswith(
        f"after iteration 2: column {col} lies")
    # the same fault in a column the seed did not sample passes, and a
    # checked job whose digest differs does not
    other = np.setdiff1d(np.arange(len(ref["pool"])), columns)[:40]
    assert drv.check_jobs(ref, [good], moved, LIMITS, n, other) == []
    assert drv.check_jobs(
        ref, [good], {"digest": wrong, "states": {}}, LIMITS, n,
        columns) == ["the checked job's digest is not the first job's"]
    # every column off by a little: the mean's limit, not the largest's
    r, c, d = state(1)
    tilt = np.where(np.arange(len(d)) % 2, 1 + 4e-4, 1 - 4e-4)
    bad, worst, mean = mclref.check_matrix(
        n, (r, c, d * tilt), ref["matrices"][1][:, columns],
        ref["pool"][columns], LIMITS, "x")
    assert worst < LIMITS["column_l1_max"] and "in the mean" in bad


def test_driver_ends_the_run_at_once_on_a_program_without_the_entry():
    """The parent of the PR that added ``mcl_job``: the run ends before
    the graph is built, non-zero, with a sentence."""
    drv = _spec().load_module("drivers", "library_cluster")

    class Job:
        mix = {"entry": "combblas_tpu.models.mcl:no_such_entry"}
        cfg = {}

    with pytest.raises(SystemExit) as e:
        drv.run(Job())
    assert "no 'combblas_tpu.models.mcl:no_such_entry'" in str(e.value)
    assert e.value.code != 0


def test_the_control_refuses_one_bfloat16_pass(tmp_path):
    """``chipbench.mclcontrol``: through the driver's own ``check_jobs``
    and the configuration's limits, the clustering whose products read
    bfloat16 inputs comes out NOT correct; read in float32 or float64 it
    comes out correct."""
    # the nearest, ties to even
    assert mclcontrol.round_to(
        np.array([1.0, 1 + 2**-9, 1 + 2**-8, 1 + 3 * 2**-9, 1 + 3 * 2**-8,
                  0.1]),
        "bfloat16").tolist() == [1.0, 1.0, 1.0, 1 + 2**-7, 1 + 2**-6,
                                 0.10009765625]
    assert mclcontrol.round_to(np.array([0.1]), "float32")[0] == float(
        np.float32(0.1))

    def extra(doc):
        _cut(str(tmp_path))

    bench = small_benchmark(str(tmp_path), scale=9, extra=extra)
    spec = Spec(bench)
    built = mclcontrol.build(spec)
    out = {how: mclcontrol.control(spec, 2300001111, how, built)
           for how in mclcontrol.INPUTS}
    assert out["float64"]["correct"] and out["float32"]["correct"]
    # 1, 2, 3 and the last iteration over the rule's line (16 cells a
    # multiply), of a run without tiers
    assert out["float64"]["column_l1_max"] == {1: 0, 2: 0, 3: 0, 8: 0}
    low = out["bfloat16"]
    assert low["correct"] is False and low["problems"]
    # float32 inputs sit far under the limits the bfloat16 ones pass
    lim = spec.config(CONFIG)["limits"]
    assert max(out["float32"]["column_l1_mean"].values()) < lim[
        "column_l1_mean"] / 10
    assert max(low["column_l1_mean"].values()) > lim["column_l1_mean"]


def _cut(root):
    """The rehearsal's configuration and mix: a graph a CPU clusters in
    a fraction of a second, a select and a recovery that bind on it."""
    p = os.path.join(root, "chipbench", "configs", CONFIG + ".json")
    with open(p) as f:
        cfg = json.load(f)
    cfg["family_graph"].update(SMALL)
    cfg["mcl"].update(KW)
    with open(p, "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(
            CHECKOUT, "chipbench", "traffic", MIX + ".json")) as f:
        mix = json.load(f)
    mix["trace"] = {"start_s": 0.5, "seconds": 1.0}
    mix["check"].update(columns=64, pool=128)
    with open(os.path.join(
            root, "chipbench", "traffic", MIX + ".json"), "w") as f:
        json.dump(mix, f)


# --- the cost ---------------------------------------------------------------


def test_least_bytes_of_a_job_by_hand():
    # three iterations: operands 10, 7, 4 read twice, 7, 4, 3 written
    assert mclcost.mcl_job_least_bytes(10, [7, 4, 3]) == 12 * (
        2 * 10 + 7 + 2 * 7 + 4 + 2 * 4 + 3) == 672
    assert mclcost.mcl_job_least_bytes(5, [5]) == 180
    # 2.1e14 flop in 3 s of device time is 35.5% of the matrix unit
    assert mclcost.dense_flop_share(2.1e14, 3.0, 197.0) == pytest.approx(
        35.5329949)


# --- the readers ------------------------------------------------------------

START, DENSE, SCAN_A, SCAN_B, CC, DIGEST = (
    "jit__mcl_start", "jit__mcl_dense_iter", "jit__mcl_scan_expand",
    "jit__mcl_scan_select", "jit_cc_fastsv", "jit__mcl_labels_digest")
_P = "jit(f)/jit(main)/"
#: what ``combblas_tpu.obs.opnames`` would hold: the scan programs' two
#: launches are two programs of one module name each
TABLES = {
    START: {"fusion.1": _P + "mcl.symbolic/reduce_sum"},
    DENSE: {"fusion.1": _P + "mcl.expand/dot_general",
            "while.3": _P + "mcl.select/cond/while",
            "fusion.2": _P + "mcl.chaos/reduce_max",
            "fusion.5": _P + "mcl.inflate/div"},
    SCAN_A + "#0": {"fusion.1": _P + "mcl.expand/sq.extract/sort",
                    "fusion.2": _P + "mcl.select/select_n"},
    SCAN_A + "#1": {"fusion.2": _P + "mcl.expand/sq.extract/sort",
                    "fusion.1": _P + "mcl.select/select_n"},
    SCAN_B + "#0": {"fusion.1": _P + "mcl.select/div",
                    "fusion.2": _P + "mcl.symbolic/reduce_sum"},
    SCAN_B + "#1": {"fusion.1": _P + "mcl.select/div",
                    "fusion.2": _P + "mcl.symbolic/reduce_sum"},
    DIGEST: {"fusion.1": _P + "mcl.interpret/reduce_sum"},
}
SYM, DOT, BODY, CH, INF, SORT, SEL, DIV, CCT, DIG = (
    100, 2000, 600, 50, 80, 300, 40, 30, 250, 20)
GAP = 500  # the host between two programs
_OPS = ["fusion.1", "fusion.2", "while.3", "fusion.4", "fusion.5",
        "copy.9"]
_MODS = [START + "(1)", DENSE + "(2)", SCAN_A + "(3)", SCAN_B + "(4)",
         SCAN_A + "(5)", SCAN_B + "(6)", CC + "(7)", DIGEST + "(8)"]
_ID = {name: i + 1 for i, name in enumerate(
    _OPS + _MODS + ["mcl.job", "mcl.iter", "mcl.interpret"])}


def _ev(name, start, end):
    return (f"events {{ metadata_id: {_ID[name]} offset_ps: {start * 1000} "
            f"duration_ps: {(end - start) * 1000} }}")


def _job(t0, slow=0):
    """One job's events from ``t0``: two dense iterations, two sparse
    ones and the interpretation.  ``(ops, modules, iters, end)``;
    ``slow`` lengthens the first dense product."""
    ops, mods, iters, t = [], [], [], t0 + GAP

    def program(mod, steps):
        nonlocal t
        start = t
        for name, ns in steps:
            if name == "while.3":  # the loop holds one unnamed body op
                ops.append(_ev("while.3", t, t + ns))
                ops.append(_ev("fusion.4", t + 10, t + ns - 10))
            else:
                ops.append(_ev(name, t, t + ns))
            t += ns
        mods.append(_ev(mod, start, t))
        t += GAP

    def iteration(*programs):
        start = t - GAP // 2
        for p in programs:
            program(*p)
        iters.append(_ev("mcl.iter", start, t - GAP // 2 - 10))

    program(_MODS[0], [("fusion.1", SYM)])
    dense = [("fusion.1", DOT), ("while.3", BODY), ("fusion.2", CH),
             ("fusion.5", INF)]
    iteration((_MODS[1], [("fusion.1", DOT + slow)] + dense[1:]))
    iteration((_MODS[1], dense))
    iteration((_MODS[2], [("fusion.1", SORT), ("fusion.2", SEL)]),
              (_MODS[3], [("fusion.1", DIV), ("fusion.2", SYM)]))
    iteration((_MODS[4], [("fusion.2", SORT), ("fusion.1", SEL)]),
              (_MODS[5], [("fusion.1", DIV), ("fusion.2", SYM)]))
    program(_MODS[6], [("copy.9", CCT)])
    program(_MODS[7], [("fusion.1", DIG)])
    return ops, mods, iters, t


def _trace(annotated=True, ahead=0) -> bytes:
    """``ahead``: nanoseconds the device's plane runs ahead of the
    host's (a job's annotation then opens AFTER its first program
    seems to start)."""
    from jax.profiler import ProfileData

    ops, mods, host = [_ev("copy.9", 100, 200)], [], []
    t = 1000
    for slow in (0, 400):
        o, m, its, end = _job(t, slow)
        ops += o
        mods += m
        host += its + [_ev("mcl.job", t + ahead, end + ahead),
                       _ev("mcl.interpret", end - 900, end - 10)]
        t = end + 100
    # a third job, cut by the trace's end
    o, m, its, end = _job(t)
    ops += o[:3]
    mods += m[:2]
    host += its[:1] + [_ev("mcl.job", t + ahead, end + ahead)]
    meta = " ".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: '
        + (f'"%{n} = f32[64]{{0}} fusion(%p)"' if n in _OPS else f'"{n}"')
        + " } }" for n, i in _ID.items())

    def plane(pid, name, lines):
        body = " ".join(
            f'lines {{ id: {k + 1} name: "{nm}" timestamp_ns: 0 '
            + " ".join(evs) + " }" for k, (nm, evs) in enumerate(lines))
        return f'planes {{ id: {pid} name: "{name}" {body} {meta} }}'

    return ProfileData.text_proto_to_serialized_xspace(
        plane(1, "/device:TPU:0", (("XLA Modules", mods), ("XLA Ops", ops)))
        + " " + plane(2, "/host:CPU", (
            ("python3", host if annotated else []),)))


_DENSE_IT = DOT + BODY + CH + INF
_SCAN_IT = SORT + SEL + DIV + SYM
#: device nanoseconds of one job, by hand (the mean of the two whole)
DEVICE = SYM + 2 * _DENSE_IT + 2 * _SCAN_IT + CCT + DIG + 400 / 2


@pytest.fixture
def no_slack(monkeypatch):
    """The hand-made trace's jobs are microseconds long: the millisecond
    a job may reach back before its annotation would take in the stray
    operation that opens the trace."""
    monkeypatch.setattr(mclscopes, "ALIGN_S", 0.0)


def test_a_job_of_many_programs_reduced_by_its_own_annotations(no_slack):
    red = mclscopes.reduce_jobs(_trace(), TABLES)
    assert red["jobs"] == 2
    assert red["device_s"] == pytest.approx(DEVICE * NS)
    assert red["wall_s"] == pytest.approx((DEVICE + 10 * GAP) * NS)
    by = red["by_scope"]
    assert set(by) == set(_scopes())
    assert by["mcl.expand"] == pytest.approx((2 * DOT + 200 + 2 * SORT) * NS)
    # the loop's unnamed body takes the loop's scope; the second scan
    # launch's table, not the first's
    assert by["mcl.select"] == pytest.approx(
        (2 * BODY + 2 * SEL + 2 * DIV) * NS)
    assert by["mcl.symbolic"] == pytest.approx(3 * SYM * NS)
    assert by["mcl.chaos"] == pytest.approx(2 * CH * NS)
    assert by["mcl.inflate"] == pytest.approx(2 * INF * NS)
    # the components' program carries no MCL scope and no table: it is
    # the interpretation's
    assert by["mcl.interpret"] == pytest.approx((CCT + DIG) * NS)
    assert red["unscoped_s"] == 0
    assert sum(by.values()) == pytest.approx(red["device_s"])
    assert red["modules"][DENSE] == [2, pytest.approx(
        (2 * _DENSE_IT + 200) * NS)]
    assert red["modules"][SCAN_A][0] == 2 and red["modules"][CC][0] == 1
    # by iteration: wall and the device's busy time inside
    its = red["iters"]
    assert len(its) == 4
    # (the first takes the job's first program, which counts its
    # multiplies, with it)
    assert its[0][1] == pytest.approx((SYM + _DENSE_IT + 200) * NS)
    assert its[1][1] == pytest.approx(_DENSE_IT * NS)
    assert its[2][1] == its[3][1] == pytest.approx(_SCAN_IT * NS)
    assert its[2][0] == pytest.approx((_SCAN_IT + 2 * GAP - 10) * NS)
    assert mclscopes.label(_P + "mcl.expand/sq.dot/dot_general") == (
        "mcl.expand")
    assert mclscopes.label("jit(f)/sq.extract/sort") is None
    assert mclscopes.label(None) is None
    ctx = {"_mcl_scoped": red}
    assert mclscopes.scope_ms(ctx, ("mcl.expand",)) == pytest.approx(
        (2 * DOT + 200 + 2 * SORT) * 1e-6)
    assert mclscopes.scope_ms(ctx, ("sq.dot",)) is None
    # under no table the job is still timed; the components' program
    # alone is charged
    bare = mclscopes.reduce_jobs(_trace(), {})
    assert bare["by_scope"] == {"mcl.interpret": pytest.approx(CCT * NS)}
    assert bare["device_s"] == pytest.approx(DEVICE * NS)
    # a program that writes no annotation (the parent) has no job
    assert mclscopes.reduce_jobs(_trace(annotated=False), TABLES) is None
    # the device's plane a little ahead of the host's: the job's first
    # program (GAP after its start here) seems to start before the
    # annotation opens and ends inside it; it is still the job's
    skewed = mclscopes.reduce_jobs(_trace(ahead=GAP + SYM // 2), TABLES)
    assert skewed["modules"][START] == [1, pytest.approx(SYM * NS)]
    assert skewed["by_scope"] == pytest.approx(by)
    assert skewed["device_s"] == pytest.approx(DEVICE * NS)


def _scopes():
    from combblas_tpu.models.mcl import MCL_SCOPES

    return MCL_SCOPES


@pytest.mark.parametrize("name", READERS)
def test_readers_give_a_number_on_a_trace_and_none_without(name, no_slack):
    from combblas_tpu import obs

    read = _spec().load_module("layers", name).read
    obs.reset()
    # nothing traced, no counter, no peak, no job: None, never 0 and
    # never an exception
    assert read({"device": {"kind": "TPU v5 lite"}}) is None
    assert read({"_mcl_scoped": None, "job_walls": [],
                 "device": {"kind": "TPU v5 lite"}}) is None
    trace = _trace()
    least = mclcost.mcl_job_least_bytes(10, [7, 4, 3, 3])
    red = mclscopes.reduce_jobs(trace, TABLES)
    walls = [1.9e-5, 2e-5, 2.2e-5, 1.8e-5, 2.1e-5]
    ctx = {"_mcl_scoped": red, "trace": devtrace.reduce_xplane(trace),
           "device": {"kind": "TPU v5 lite",
                      "memory_peak_bytes": 5_400_000_000},
           "least_bytes": least, "job_walls": walls}
    obs.enable(install_hooks=False)
    try:
        for _ in range(3):  # the warm-up job and two more
            obs.count("mcl.job.jobs")
            obs.count("mcl.job.iters", 2, tier="windowed")
            obs.count("mcl.job.iters", 2, tier="scan")
        value = read(ctx)
    finally:
        obs.disable()
        obs.reset()
    device_s = DEVICE * NS
    want = {
        "mcl_device_ms": 1e3 * device_s,
        "mcl_expand_ms": (2 * DOT + 200 + 2 * SORT) * 1e-6,
        "mcl_select_ms": (2 * BODY + 2 * SEL + 2 * DIV) * 1e-6,
        "mcl_host_gap_ms": 1e3 * (2e-5 - device_s),
        "mcl_iters": 4.0,
        "mcl_hbm_share": 100 * (least / 819e9) / device_s,
        "mcl_hbm_peak_gb": 5.4,
    }[name]
    assert value == pytest.approx(want)
    if name == "mcl_hbm_share":
        assert 0 < value < 100


# --- what the cell added ----------------------------------------------------


def test_the_cell_its_configuration_and_its_seven_readers_are_appended():
    """Order checks only: whatever a later PR appends, these hold."""
    spec = _spec()
    cells = [w["name"] for w in spec.doc["workloads"]]
    configs = [c["name"] for c in spec.doc["configs"]]
    assert cells.index(SQ_CELL) < cells.index(CELL)
    assert configs.index(SQ_CONFIG) < configs.index(CONFIG)
    cell = spec.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, MIX, 1)
    assert len(cell["why"]) <= 200
    reported = {m["name"] for m in spec.metrics_for(CELL, "end_to_end")}
    assert reported == {"mteps", "setup_s"}
    # it joined one list that was there, after the cells that were there
    joined = [m for sec in ("end_to_end", "per_layer")
              for m in spec.doc[sec] if CELL in m.get("workloads", ())]
    assert [m["name"] for m in joined] == ["mteps"] + READERS
    at = joined[0]["workloads"].index
    assert at("g500-s20.k2-batch") < at(SQ_CELL) < at(CELL)
    # the seven, in the issue's order, after the product's seven, listing
    # this cell alone
    names = [m["name"] for m in spec.doc["per_layer"]]
    assert [n for n in names if n in READERS] == READERS
    assert names.index("sq_hbm_peak_gb") < names.index(READERS[0])
    for m in joined[1:]:
        assert m["workloads"] == [CELL] and m["moves"] == "mteps"
        assert m["layer"] == "algorithms + local kernels"
    by = {m["name"]: m for m in joined}
    assert [(by[n]["unit"], by[n]["better"], by[n]["source"])
            for n in READERS] == [
        ("ms", "lower", "device_trace"), ("ms", "lower", "device_trace"),
        ("ms", "lower", "device_trace"), ("ms", "lower", "host_clock"),
        ("count", "lower", "program_counter"),
        ("%", "higher", "device_trace"), ("GB", "lower", "program_counter")]
    mine = {m["name"] for m in spec.metrics_for(CELL, "per_layer")}
    assert set(READERS) | {"compiles_in_window", "load_s", "warmup_s",
                           "graph_ready_s", "upload_s"} <= mine
    assert not any(m.startswith(("bfs_", "k2_", "cc_", "tc_", "sq_"))
                   for m in mine)
    # and no other cell reports them
    for other in cells:
        if other != CELL:
            assert not set(READERS) & {m["name"] for m in spec.metrics_for(
                other, "per_layer")}
    # every file of the cell is new beside the ones that were there
    for rel in ("famgraph.py", "mclref.py", "mclcontrol.py", "mclcost.py",
                "mclscopes.py", "drivers/library_cluster.py",
                "traffic/mcl-batch.json", "configs/hipmcl-fam-1x1.json"):
        assert os.path.isfile(os.path.join(CHECKOUT, "chipbench", rel))


def test_the_configuration_states_its_source_its_cut_and_its_guarantees():
    spec = _spec()
    cfg = spec.config(CONFIG)
    entry = next(c for c in spec.doc["configs"] if c["name"] == CONFIG)
    assert len(entry["source"]) <= 200 and "MCL.cpp" in entry["source"]
    cell = next(w for w in spec.doc["workloads"] if w["name"] == CELL)
    assert len(entry["why"]) <= 200 and len(cell["why"]) <= 200
    assert "HipMCL" in cfg["source"] and "InitParam" in cfg["source"]
    assert entry["reduced"] == ["scale"] == list(cfg["reduced"])
    assert f"-> {cfg['scale']}" in cfg["reduced"]["scale"]
    assert cfg["grid"] == [1, 1] and cfg["kinds"] == []
    assert cfg["scale"] in (14, 15)
    # the five published numbers, unchanged
    m = cfg["mcl"]
    assert (m["inflation"], m["select"], m["recover"], m["recover_pct"],
            m["prune"]) == (2.0, 1100, 1400, 0.9, 1e-4)
    assert (m["eps"], m["mode"]) == (1e-3, "bf16x3")
    assert cfg["family_graph"] == dict(
        famgraph.DEFAULTS, w_in=[0.3, 1.0], w_out=[0.05, 0.3])
    assert {"graph", "values", "eps", "precision", "job", "counts",
            "graph_seed", "reference"} <= set(cfg["assumed"])
    assert "STAND-IN" in cfg["assumed"]["graph"]
    counts = cfg["assumed"]["counts"]
    # by the reference's count the select binds and recovery fires
    assert counts["select_bound_cols"][0] >= 1000
    assert counts["recovered_cols"][0] >= 300
    assert len(counts["products"]) == counts["iters"]
    # no iteration's chaos within a factor 1.5 of eps: the iteration
    # count is an equality too
    assert all(max(c / m["eps"], m["eps"] / max(c, 1e-300)) > 1.5
               for c in counts["chaos"])
    assert set(cfg["limits"]) == {
        "chaos_rel", "chaos_abs", "stored_rel", "column_l1_max",
        "column_l1_mean", "why"}
    assert set(cfg["limits"]["why"]) == set(cfg["limits"]) - {"why"} | {"what"}
    assert {"digest", "reference", "states", "window"} == set(
        cfg["guarantees"])
    mix = spec.traffic(MIX)
    assert mix["driver"] == "library_cluster"
    assert mix["entry"] == "combblas_tpu.models.mcl:mcl_job"
    assert mix["job"] == {}  # nothing names a tier, a loop or a backend
    assert mix["trace"] == {"start_s": 10.0, "seconds": 25.0}


# --- the cell, rehearsed ----------------------------------------------------


def test_the_cell_through_the_real_command(tmp_path):
    def extra(doc):
        _cut(str(tmp_path))

    bench = small_benchmark(str(tmp_path), scale=9, extra=extra)
    # (a seed beyond 32 signed bits, as the driver's are)
    r, line = run_cell(bench, CELL, seed=2300001111, seconds=2)
    assert r.returncode == 0, r.stderr[-2000:]
    m = check_line(line)
    assert set(m) == {"mteps", "setup_s"} and m["mteps"] > 0
    seed = _spec().config(CONFIG)["graph_seed"]
    n, rows, cols, vals, fam = famgraph.family_graph(9, seed, **SMALL)
    assert (f"family graph of {n} vertices and {len(rows) // 2} undirected "
            "edges built") in r.stderr
    # the rehearsal runs the tiers the rule picks for the chip at its size
    assert "warm-up job: " in r.stderr and "tiers mxu mxu" in r.stderr
    assert f"{len(np.unique(fam))} clusters" in r.stderr
    assert "the reference (computed" in r.stderr
    assert line["attempted"] >= 4
    # traced: the cell's own counter, the boot's spans; no device plane
    # on a CPU, so no device metric
    r, line = run_cell(bench, CELL, trace=1, seed=7, seconds=2)
    assert r.returncode == 0, r.stderr[-2000:]
    m = check_line(line)
    assert m["mcl_iters"] >= 10 and m["compiles_in_window"] == 0
    assert {"load_s", "warmup_s", "graph_ready_s", "upload_s"} <= set(m)
    assert not {"mcl_device_ms", "mcl_expand_ms", "mcl_hbm_share"} & set(m)
    assert "the reference (kept" in r.stderr
    assert "snapshot and uploaded" in r.stderr
