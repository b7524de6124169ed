"""The small recorded trace the reduction is checked on, as text.

``data/tiny.xplane.pb`` is this text converted with
``ProfileData.text_proto_to_serialized_xspace`` (a test keeps the two
equal).  Two device planes and one host plane; times in nanoseconds:

device 0   XLA Modules  jit_impl [1000, 9000) [12000, 20000), and one cut by
                        the trace's end: [22000, 23000)
           XLA Ops      copy.3 [100, 200)
                        while.1 [1000, 9000)  = fusion.1 [1000, 4000)
                                               + all-reduce.2 [4000, 6000)
                                               + fusion.1 [7000, 9000)
                        while.1 [12000, 20000) = fusion.1 [12000, 16000)
                                               + all-reduce.2 [16000, 18000)
                        copy.3 [22000, 23000)
device 1   XLA Modules  jit_impl [1000, 8000)
           XLA Ops      copy.3 [100, 200)
                        while.1 [1000, 8000)  = fusion.1 [1000, 5000)
                                               + all-reduce.2 [5000, 8000)
                        copy.3 [8500, 8600)
host       chipbench_anchor at 500
"""


def _ev(meta: int, start_ns: int, end_ns: int) -> str:
    return (f"events {{ metadata_id: {meta} offset_ps: {start_ns * 1000} "
            f"duration_ps: {(end_ns - start_ns) * 1000} }}")


_META = {1: "while.1", 2: "fusion.1", 3: "all-reduce.2", 4: "copy.3",
         5: "jit_impl", 6: "chipbench_anchor"}
_METADATA = " ".join(
    f'event_metadata {{ key: {k} value {{ id: {k} name: "{v}" }} }}'
    for k, v in _META.items()
)


def _plane(pid: int, name: str, lines: dict) -> str:
    body = " ".join(
        f'lines {{ id: {i + 1} name: "{ln}" timestamp_ns: 0 '
        + " ".join(evs) + " }"
        for i, (ln, evs) in enumerate(lines.items())
    )
    return f'planes {{ id: {pid} name: "{name}" {body} {_METADATA} }}'


TEXT = " ".join([
    _plane(1, "/device:TPU:0", {
        "XLA Modules": [_ev(5, 1000, 9000), _ev(5, 12000, 20000),
                        _ev(5, 22000, 23000)],
        "XLA Ops": [
            _ev(4, 100, 200),
            _ev(1, 1000, 9000), _ev(2, 1000, 4000), _ev(3, 4000, 6000),
            _ev(2, 7000, 9000),
            _ev(1, 12000, 20000), _ev(2, 12000, 16000),
            _ev(3, 16000, 18000),
            _ev(4, 22000, 23000),
        ],
    }),
    _plane(2, "/device:TPU:1", {
        "XLA Modules": [_ev(5, 1000, 8000)],
        "XLA Ops": [_ev(4, 100, 200), _ev(1, 1000, 8000),
                    _ev(2, 1000, 5000), _ev(3, 5000, 8000),
                    _ev(4, 8500, 8600)],
    }),
    _plane(3, "/host:CPU", {"python": [_ev(6, 500, 600)]}),
])
