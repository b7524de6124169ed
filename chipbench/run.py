"""One cell, once, in one process that owns the cell's chips.

    python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

Loads the cell's configuration, warms only the cell's own programs,
measures for ``--seconds``, checks answers outside the window, and
prints as the LAST line of stdout one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics`` and ``device`` (and, traced,
``breakdown``).  ``--trace 0`` reports the cell's end-to-end metrics
with all program telemetry off; ``--trace 1`` reports its per-layer
metrics from stage records, counters and a profiler slice.

It reads no environment variable of its own and sets none.  With no TPU
it exits non-zero and prints no result; ``JAX_PLATFORMS=cpu`` given by
name is the rehearsal the tests use: the device is named ``cpu`` and
every metric carries the prefix ``rehearsal.``, so no CPU number ever
appears under a device metric's name.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

from . import deploy, devtrace  # noqa: E402
from .deploy import log  # noqa: E402
from .spec import CHECKOUT, Spec  # noqa: E402


class CompileWatch:
    """Counts programs compiled or fetched from the persistent cache, by
    JAX's own monitoring events: the window must see none."""

    EVENTS = (
        "/jax/core/compile/backend_compile_duration",
        "/jax/compilation_cache/cache_retrieval_time_sec",
    )

    def __init__(self):
        from jax import monitoring

        self.count = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event in self.EVENTS:
            self.count += 1


class Job:
    """What a driver is handed."""

    def __init__(self, spec, cell, cfg, mix, args, device):
        self.spec, self.cell, self.cfg, self.mix = spec, cell, cfg, mix
        self.seed = args.seed
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.device = device
        self.compiles = CompileWatch()
        self.tracer = None
        if self.trace:
            from combblas_tpu import obs

            obs.enable()
            obs.trace.set_sample_rate(1.0)
            tr = mix.get("trace", {})
            seconds = min(float(tr.get("seconds", 8.0)), self.seconds)
            start_s = min(float(tr.get("start_s", 0.25 * self.seconds)),
                          max(self.seconds - seconds, 0.0))
            self.tracer = devtrace.SliceTracer(
                os.path.join(spec.cache_dir(), "profile", cell["name"]),
                start_s, seconds, log,
            )

    def deploy(self):
        return deploy.deploy(self.cfg, self.spec.cache_dir())


def layer_metrics(spec: Spec, cell_name: str, reported_e2e, ctx) -> dict:
    """Every per-layer metric of this cell whose end-to-end metric the
    cell reports, read by the metric's own reader.  A reader that finds
    nothing returns None and the metric is left out."""
    out = {}
    for m in spec.metrics_for(cell_name, "per_layer"):
        if m["moves"] not in reported_e2e:
            continue
        value = spec.load_module("layers", m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument(
        "--bench", default=os.path.join(CHECKOUT, "BENCHMARK.json"),
        help="the BENCHMARK.json to read (tests point this at their own)",
    )
    args = ap.parse_args(argv)

    spec = Spec(args.bench)
    cell = spec.cell(args.workload)
    cfg = spec.config(cell["config"])
    mix = spec.traffic(cell["traffic"])
    driver = spec.load_module("drivers", mix["driver"])

    device = deploy.start_backend(int(cell["chips"]))
    log(f"cell {cell['name']} on {device}, seed {args.seed}, "
        f"{args.seconds} s, trace {args.trace}")
    job = Job(spec, cell, cfg, mix, args, device)
    res = driver.run(job)

    values = dict(res["values"])
    values["setup_s"] = res["t_first_send"] - T_PROCESS_START
    e2e = spec.metrics_for(cell["name"], "end_to_end")
    missing = [m["name"] for m in e2e if values.get(m["name"]) is None]
    problems = list(res["problems"])
    if missing:
        problems.append(f"end-to-end metrics not measured: {missing}")
    if res["compiles_in_window"]:
        problems.append(
            f"{res['compiles_in_window']} programs compiled or loaded "
            "inside the window"
        )
    for p in problems:
        log(f"PROBLEM: {p}")

    device = dict(device, memory_peak_bytes=deploy.memory_peak_bytes())
    line = {
        "correct": not problems,
        "attempted": int(res["attempted"]),
        "failed": int(res["failed"]),
    }
    prefix = "rehearsal." if device["platform"] != "tpu" else ""
    if not args.trace:
        line["metrics"] = {
            prefix + m["name"]: {
                "value": float(values[m["name"]]), "unit": m["unit"],
            }
            for m in e2e if values.get(m["name"]) is not None
        }
    else:
        ctx = dict(res["ctx"], values=values, device=device, cfg=cfg,
                   mix=mix, seconds=job.seconds,
                   compiles_in_window=res["compiles_in_window"])
        reduced, offset = res["ctx"].get("trace"), res["ctx"].get(
            "trace_offset")
        reported = {m["name"] for m in e2e}
        line["metrics"] = {
            prefix + k: v for k, v in
            layer_metrics(spec, cell["name"], reported, ctx).items()
        }
        if reduced is not None and reduced["devices"]:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            line["breakdown"] = {
                "device_ops": devtrace.top_ops(reduced),
                "idle_gaps": (
                    devtrace.idle_gaps(
                        reduced, res["ctx"].get("host_spans", []), offset
                    ) if offset is not None else []
                ),
            }
    line["device"] = device
    log(f"setup_s {values['setup_s']:.2f} ({res['ctx'].get('load_how')}); "
        f"wall {time.perf_counter() - T_PROCESS_START:.1f} s")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
