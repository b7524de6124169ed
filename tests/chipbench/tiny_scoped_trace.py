"""The small recorded trace ``chipbench/scopes.py`` is checked on, as
text, with the table a program would publish for it.

``data/tiny_scoped.xplane.pb`` is this text converted with
``ProfileData.text_proto_to_serialized_xspace`` (a test keeps the two
equal).  One device plane and one host plane, events named as a v5e
names them (the compiler's instruction text, no scope); times in
nanoseconds:

device 0  XLA Modules  jit_serve_bfs_w16(77) [1000, 11000) [12000, 20000),
                       and a third cut by the trace's end [21000, 22000)
          XLA Ops      copy.6 [100, 200)                      (no program)
             run 1     fusion.9 [1000, 1500)                  bfs.init
                       while.5 [1500, 10500) = three levels and the
                         condition's last evaluation:
                         compare.7 +10, fusion.1 (gather), fusion.2 (fold),
                         fusion.3 (scatter_rows), all-reduce.4 (ell.reduce)
                           level 1 [1500, 4500)  gather 1990
                           level 2 [4500, 8500)  gather 2990
                           level 3 [8500, 10500) gather  990, then
                         compare.7 [10400, 10410) alone, and
                         dynamic-slice.10 [10410, 10450): the compiler's
                         own, in no table, so it is the loop's
                       copy.8 [10500, 11000)                  (not in table)
             run 2     fusion.9 [12000, 12500); while.5 [12500, 19500) =
                       two levels [12500, 15500) [15500, 19500) and the
                       lone compare.7 [19400, 19410); copy.8 [19500, 20000)
             cut       fusion.9 [21000, 22000)
host      serve.batch [900, 11800) holding serve.execute.launch
          [900, 1000), .device [1000, 11000), .readback [11000, 11600),
          .to_global [11600, 11700); serve.scatter [11800, 11900);
          serve.batch [11950, 20900); chipbench_anchor at 500
"""

MODULE = "jit_serve_bfs_w16"

_PATH = "jit(serve_bfs_w16)/jit(_bfs_batch_impl)/"
_BODY = _PATH + "bfs.level/while/body/jit(dist_spmv_ell_masked_multi)/"
#: what ``combblas_tpu.obs.opnames`` would hold for the program
TABLE = {
    "fusion.9": _PATH + "bfs.init/jit(_where)/select_n",
    "while.5": _PATH + "bfs.level/while",
    "compare.7": _PATH + "bfs.level/while/cond/lt",
    "fusion.1": _BODY + "ell.bucket0/gather/gather",
    "fusion.2": _BODY + "ell.bucket0/fold/reduce_max",
    "fusion.3": _BODY + "ell.bucket0/scatter_rows/scatter-max",
    "all-reduce.4": _BODY + "ell.reduce/pmax",
}

_META = {
    1: "%fusion.1 = s32[96,16]{0,1:T(8,128)} fusion(s32[65,16]{0,1} %p)",
    2: "%fusion.2 = s32[12,16]{0,1:T(8,128)} fusion(s32[96,16]{0,1} %f)",
    3: "%fusion.3 = s32[64,16]{0,1:T(8,128)} fusion(s32[12,16]{0,1} %g)",
    4: "%all-reduce.4 = s32[64,16]{0,1} all-reduce(s32[64,16]{0,1} %h)",
    5: "%while.5 = (s32[64,16]{0,1}, pred[]) while(%t), body=%b",
    6: "%copy.6 = s32[16]{0} copy(s32[16]{0} %s)",
    7: "%compare.7 = pred[] compare(s32[] %l, s32[] %m), direction=LT",
    8: "%copy.8 = s32[64,16]{1,0} copy(s32[64,16]{0,1} %w)",
    9: "%fusion.9 = s32[64,16]{0,1} fusion(s32[16]{0} %s), kind=kLoop",
    10: MODULE + "(77)",
    11: "chipbench_anchor",
    12: "serve.batch",
    13: "serve.execute.launch",
    14: "serve.execute.device",
    15: "serve.execute.readback",
    16: "serve.execute.to_global",
    17: "serve.scatter",
    18: "%dynamic-slice.10 = s32[8]{0} dynamic-slice(s32[64]{0} %q, %i)",
}
_METADATA = " ".join(
    f'event_metadata {{ key: {k} value {{ id: {k} name: "{v}" }} }}'
    for k, v in _META.items()
)


def _ev(meta: int, start_ns: int, end_ns: int) -> str:
    return (f"events {{ metadata_id: {meta} offset_ps: {start_ns * 1000} "
            f"duration_ps: {(end_ns - start_ns) * 1000} }}")


def _level(t0: int, gather: int, t1: int) -> list:
    """compare +10, gather, fold 500, scatter_rows 300, reduce 200."""
    a = t0 + 10
    b = a + gather
    assert b + 1000 == t1, (t0, gather, t1)
    return [_ev(7, t0, a), _ev(1, a, b), _ev(2, b, b + 500),
            _ev(3, b + 500, b + 800), _ev(4, b + 800, b + 1000)]


def _plane(pid: int, name: str, lines: dict) -> str:
    body = " ".join(
        f'lines {{ id: {i + 1} name: "{ln}" timestamp_ns: 0 '
        + " ".join(evs) + " }"
        for i, (ln, evs) in enumerate(lines.items())
    )
    return f'planes {{ id: {pid} name: "{name}" {body} {_METADATA} }}'


TEXT = " ".join([
    _plane(1, "/device:TPU:0", {
        "XLA Modules": [_ev(10, 1000, 11000), _ev(10, 12000, 20000),
                        _ev(10, 21000, 22000)],
        "XLA Ops": (
            [_ev(6, 100, 200), _ev(9, 1000, 1500), _ev(5, 1500, 10500)]
            + _level(1500, 1990, 4500) + _level(4500, 2990, 8500)
            + _level(8500, 990, 10500)[:1] + [
                _ev(1, 8510, 9500), _ev(2, 9500, 10000),
                _ev(3, 10000, 10200), _ev(4, 10200, 10400),
                _ev(7, 10400, 10410), _ev(18, 10410, 10450),
                _ev(8, 10500, 11000),
                _ev(9, 12000, 12500), _ev(5, 12500, 19500)]
            + _level(12500, 1990, 15500) + [
                _ev(7, 15500, 15510), _ev(1, 15510, 18400),
                _ev(2, 18400, 18900), _ev(3, 18900, 19200),
                _ev(4, 19200, 19400), _ev(7, 19400, 19410),
                _ev(8, 19500, 20000), _ev(9, 21000, 22000)]
        ),
    }),
    _plane(2, "/host:CPU", {
        "python3": [_ev(11, 500, 600)],
        "serve-worker": [
            _ev(12, 900, 11800), _ev(13, 900, 1000), _ev(14, 1000, 11000),
            _ev(15, 11000, 11600), _ev(16, 11600, 11700),
            _ev(17, 11800, 11900), _ev(12, 11950, 20900)],
    }),
])
