"""``chipbench/scopes.py`` and ``chipbench/parts.py`` without running a
cell: the scope reduction on a small recorded trace with scopes (two
whole executions and a cut third), None on a trace or a program without
scopes, the batches and gaps from stage records, and the new
``per_layer`` entries against the contract."""

import json
import os

import pytest

from chipbench import devtrace, parts, scopes
from chipbench.spec import CHECKOUT, Spec

import tiny_scoped_trace as T

HERE = os.path.dirname(os.path.abspath(__file__))
SCOPED = os.path.join(HERE, "data", "tiny_scoped.xplane.pb")
TINY = os.path.join(HERE, "data", "tiny.xplane.pb")
NS = 1e-9

NEW = ["launch_ms", "readback_ms", "to_global_ms", "readback_mb_per_query",
       "scatter_copied_mb", "batch_gap_ms", "bfs_gather_share",
       "bfs_level_ms", "k2_gather_share", "k2_level_ms", "k2_parents_ms"]


def test_scoped_trace_is_the_text_beside_it():
    from jax.profiler import ProfileData

    # (a protobuf map has no fixed order on the wire, so the two are
    # compared as read, not byte for byte: as tiny.xplane.pb is)
    fresh = ProfileData.text_proto_to_serialized_xspace(T.TEXT)
    tables = {T.MODULE: T.TABLE}
    assert scopes.reduce_scopes(fresh, tables) == scopes.reduce_scopes(
        SCOPED, tables)
    assert devtrace.reduce_xplane(fresh) == devtrace.reduce_xplane(SCOPED)


@pytest.mark.parametrize("op_name,want", [
    (T.TABLE["fusion.1"], "bfs.level/ell.bucket0/gather"),
    (T.TABLE["while.5"], "bfs.level"),
    (T.TABLE["fusion.9"], "bfs.init"),
    ("jit(f)/bfs.parents/jit(g)/ell.bucket12/fold/reduce_max",
     "bfs.parents/ell.bucket12/fold"),
    ("jit(f)/bfs.level/while/body/vec.realign/ppermute",
     "bfs.level/vec.realign"),
    # the primitive called gather is not the scope called gather
    ("jit(f)/jit(_take)/gather", None),
    ("reduce_max", None), ("", None), (None, None),
])
def test_label_keeps_the_scopes_of_an_op_name(op_name, want):
    assert scopes.label(op_name) == want


def test_names_as_a_v5e_writes_them():
    assert scopes.instruction(
        "%fusion.249 = s32[7793664,16]{0,1:T(8,128)} fusion(s32[1048577,16]"
    ) == "fusion.249"
    assert scopes.module_name(
        "jit_serve_bfs_w16(1172754028435489344)") == "jit_serve_bfs_w16"


def test_reduction_by_scope_and_by_level():
    red = scopes.reduce_scopes(SCOPED, {T.MODULE: T.TABLE})
    assert red["module"] == T.MODULE
    assert red["executions"] == 2  # the third is cut by the trace's end
    assert red["device_s"] == pytest.approx(9000 * NS)
    by = red["by_scope"]
    # per execution: run 1 has three levels, run 2 has two
    assert by["bfs.init"] == pytest.approx(500 * NS)
    assert by["bfs.level/ell.bucket0/gather"] == pytest.approx(
        (1990 + 2990 + 990 + 1990 + 2890) / 2 * NS)
    assert by["bfs.level/ell.bucket0/fold"] == pytest.approx(1250 * NS)
    assert by["bfs.level/ell.bucket0/scatter_rows"] == pytest.approx(
        (4 * 300 + 200) / 2 * NS)
    assert by["bfs.level/ell.reduce"] == pytest.approx(500 * NS)
    # the loop's own: the condition, what no child covers, and the
    # compiler's dynamic-slice.10, which is in no table and runs inside
    assert by["bfs.level"] == pytest.approx(
        ((40 + 50 + 40) + (30 + 90)) / 2 * NS)
    assert scopes.by_phase(by)["bfs.level/gather"] == by[
        "bfs.level/ell.bucket0/gather"]
    assert red["unscoped_s"] == pytest.approx(500 * NS)  # copy.8
    # everything adds up to the program's device time
    assert sum(by.values()) + red["unscoped_s"] == pytest.approx(
        red["device_s"])
    # levels, first to last; the lone last condition joins the last one
    assert [[round(s / NS) for s in lv] for lv in red["levels"]] == [
        [3000, 4000, 2000], [3000, 4000]]
    assert scopes.level_table(red["levels"]) == pytest.approx(
        [3000 * NS, 4000 * NS, 2000 * NS])
    # levels plus what lies outside the loop is the execution
    for lv, total in zip(red["levels"], (10000, 8000)):
        assert sum(lv) + (500 + 500) * NS == pytest.approx(total * NS)
    ctx = {"_scoped": red}
    assert scopes.level_ms(ctx) == pytest.approx(3000 * NS * 1e3)
    assert scopes.share(ctx) == pytest.approx(
        100 * (5425 + 1250) / 9000)
    assert scopes.scope_ms(ctx, "bfs.init") == pytest.approx(500e-6)
    assert scopes.scope_ms(ctx, "bfs.parents") is None
    # the host plane's annotations, on the trace's own clock
    assert [e[0] for e in red["host"]][:3] == [
        "serve.batch", "serve.execute.launch", "serve.execute.device"]


def test_idle_is_charged_to_the_innermost_annotation():
    red = scopes.reduce_scopes(SCOPED, {T.MODULE: T.TABLE})
    reduced = devtrace.reduce_xplane(SCOPED, window=(900 * NS, 21000 * NS))
    idle = dict(scopes.idle_by_annotation(reduced, red["host"]))
    # busy [1000, 11000) [12000, 20000); idle [900, 1000) under launch,
    # [11000, 12000): readback 600, to_global 100, the batch's own 100,
    # scatter 100, nothing 50, the next batch 50; [20000, 21000): 900
    # under the second batch, then nothing
    assert idle["serve.execute.launch"] == pytest.approx(100 * NS)
    assert idle["serve.execute.readback"] == pytest.approx(600 * NS)
    assert idle["serve.execute.to_global"] == pytest.approx(100 * NS)
    assert idle["serve.scatter"] == pytest.approx(100 * NS)
    assert idle["serve.batch"] == pytest.approx((100 + 50 + 900) * NS)
    assert idle["no-annotation"] == pytest.approx((50 + 100) * NS)
    assert "serve.execute.device" not in idle
    assert sum(idle.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"])


@pytest.mark.parametrize("source,tables", [
    (SCOPED, {}),                        # the program published nothing
    (SCOPED, {T.MODULE: {"fusion.1": "jit(f)/jit(g)/gather"}}),
    (TINY, {T.MODULE: T.TABLE}),         # a trace of another program
])
def test_readers_return_none_where_there_is_no_scope(source, tables):
    red = scopes.reduce_scopes(source, tables)
    assert red is not None and red["executions"] >= 1
    assert red["by_scope"] is None and red["levels"] is None
    ctx = {"_scoped": red}
    assert scopes.share(ctx) is None
    assert scopes.level_ms(ctx) is None
    assert scopes.scope_ms(ctx, "bfs.parents") is None


def test_no_trace_no_reading():
    # an untraced run, a rehearsal without a device plane, the parent's
    # program without opnames: None, never 0, and nothing raised
    assert scopes.scoped({"trace": None}) is None
    assert scopes.share({"trace": None}) is None
    assert scopes.level_ms({}) is None
    assert isinstance(scopes.published_tables(), dict)


def _record(t0, queue_wait, execute, scatter, parts_=None, width=16):
    ex = {"stage": "execute", "s": execute}
    if parts_ is not None:
        ex["parts"] = [{"stage": k, "s": v} for k, v in parts_.items()]
    stages = [{"stage": "queue_wait", "s": queue_wait},
              {"stage": "assemble", "s": 0.001}, ex,
              {"stage": "scatter", "s": scatter}]
    return {"t0": t0, "wall_s": sum(s["s"] for s in stages),
            "stages": stages, "labels": {"status": "ok", "width": width}}


def test_batches_parts_and_gaps_from_stage_records():
    p1 = {"launch": 0.01, "device": 2.0, "readback": 0.2, "to_global": 0.04}
    p2 = {"launch": 0.03, "device": 2.0, "readback": 0.4, "to_global": 0.07}
    ctx = {"stages": [
        # batch 1: popped at 10.5, two members, scatter ends at 12.761
        _record(10.0, 0.5, 2.25, 0.005, p1),
        _record(10.2, 0.3, 2.25, 0.010, p1),
        # batch 2: popped at 12.8
        _record(11.0, 1.8, 2.5, 0.002, p2),
        # a failed request and an unsampled program are not batches
        dict(_record(11.5, 1.0, 9.9, 0.0), labels={"status": "error"}),
    ]}
    bs = parts.batches(ctx)
    assert [b["requests"] for b in bs] == [2, 1]
    assert bs[0]["t_pop"] == pytest.approx(10.5)
    assert bs[0]["t_done"] == pytest.approx(10.5 + 0.001 + 2.25 + 0.010)
    assert parts.part_ms(ctx, "readback") == pytest.approx(300.0)
    assert parts.part_ms(ctx, "launch") == pytest.approx(20.0)
    assert parts.batch_gap_ms(ctx) == pytest.approx(
        1e3 * (12.8 - 12.761))
    # the parent's records: no t0, no parts
    old = [{k: v for k, v in r.items() if k != "t0"} for r in ctx["stages"]]
    for r in old:
        r["stages"] = [{"stage": s["stage"], "s": s["s"]}
                       for s in r["stages"]]
    assert parts.batches({"stages": old}) == []
    assert parts.part_ms({"stages": old}, "launch") is None
    assert parts.batch_gap_ms({"stages": old}) is None
    assert parts.part_ms({}, "launch") is None
    # records with t0 but no parts (a ShardedEngine): gaps yes, parts no
    bare = {"stages": [_record(1.0, 0.5, 2.0, 0.01),
                       _record(3.0, 0.6, 2.1, 0.01)]}
    assert parts.part_ms(bare, "launch") is None
    assert parts.batch_gap_ms(bare) == pytest.approx(
        1e3 * (3.6 - (1.0 + 0.5 + 0.001 + 2.0 + 0.01)))


def test_counter_is_none_where_the_program_has_no_such_series():
    from combblas_tpu import obs

    obs.reset()
    assert parts.counter("serve.readback.bytes") is None
    obs.enable(install_hooks=False)
    try:
        obs.count("serve.readback.bytes", 100, kind="bfs", width=1)
        obs.count("serve.readback.bytes", 28, kind="bfs", width=16)
        assert parts.counter("serve.readback.bytes") == 128
    finally:
        obs.disable()
        obs.reset()


@pytest.mark.parametrize("name", NEW)
def test_new_per_layer_entries_follow_the_contract(name):
    spec = Spec(os.path.join(CHECKOUT, "BENCHMARK.json"))
    layers = {m["name"]: m for m in spec.doc["per_layer"]}
    m = layers[name]
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}
    # appended after PR 22's entries, in the issue's order
    names = [x["name"] for x in spec.doc["per_layer"]]
    assert names[-len(NEW):] == NEW
    # the layer is one PERF.md section 3 names, letter for letter
    with open(os.path.join(CHECKOUT, "PERF.md")) as f:
        assert f"| {m['layer']} |" in f.read()
    # each listed cell reports the end-to-end metric this one moves
    e2e = {x["name"]: x for x in spec.doc["end_to_end"]}[m["moves"]]
    assert m["workloads"] and set(m["workloads"]) <= set(e2e["workloads"])
    # and its reader is a file of its own
    assert callable(spec.load_module("layers", name).read)
