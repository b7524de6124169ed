"""What the two served drivers share: the server from bring-up (through
the user entry points) to the checks, and the stage records turned into
the per-batch facts and host spans the layer readers and the idle-gap
attribution use."""

from __future__ import annotations

import time

import numpy as np

from .deploy import log


class Session:
    """One served cell's server, from bring-up to the checks: what the
    closed and the open driver do alike around their own send loops."""

    def __init__(self, job):
        """Load the configuration, then ``engine.serve`` -> warm only
        this cell's plans -> ``start``."""
        from combblas_tpu.serve import ServeConfig

        cfg, mix = job.cfg, job.mix
        self.job = job
        self.dep = job.deploy()
        self.srv = self.dep.engine.serve(
            ServeConfig(lane_widths=tuple(cfg["lane_widths"]))
        )
        t0 = time.perf_counter()
        self.srv.warmup(
            kinds=(mix["kind"],), widths=tuple(cfg["lane_widths"])
        )
        self.warmup_s = time.perf_counter() - t0
        log(f"warm-up of {mix['kind']} x {cfg['lane_widths']}: "
            f"{self.warmup_s:.1f} s")
        self.srv.start()
        self.mark = self.dep.engine.trace_mark()
        self.failures = []  # repr of each failed request's exception

    def open_window(self) -> float:
        """Counter marks, the profiler's timer, and the first send's
        time."""
        self._c0 = self.job.compiles.count
        self._p0 = compile_counters() if self.job.trace else 0
        t_first = time.perf_counter()
        if self.job.tracer:
            self.job.tracer.begin(t_first)
        return t_first

    def close_window(self, sampler: "Sampler"):
        """After the drain: counters, the server's own account, the
        reduced trace, then the checks.  Returns ``(compiles in the
        window, problems, ctx for the layer readers)``."""
        job, dep = self.job, self.dep
        compiles = job.compiles.count - self._c0
        if job.trace:
            compiles = max(compiles, compile_counters() - self._p0)
        stats = self.srv.stats()
        problems = server_problems(stats, dep.engine, self.mark)
        reduced, offset = (
            job.tracer.finish() if job.tracer else (None, None)
        )
        self.srv.close(drain=False, timeout=5.0)
        ctx = {
            "load_s": dep.load_s, "load_how": dep.how,
            "warmup_s": self.warmup_s, "stats": stats,
            "trace": reduced, "trace_offset": offset,
        }
        if job.trace:
            from combblas_tpu.obs import trace as obs_trace

            records = obs_trace.records()
            batches = batches_from_stages(records)
            ctx.update(stages=records, batches=batches,
                       host_spans=host_spans(batches))
        t0 = time.perf_counter()
        problems += check_sample(
            dep, sampler, int(job.mix["check"]["exact"])
        )
        log(f"checked {len(sampler.kept)} sampled answers in "
            f"{time.perf_counter() - t0:.1f} s")
        if self.failures:
            log(f"{len(self.failures)} failed; first: {self.failures[0]}")
        log_plans(stats, ctx.get("batches"))
        return compiles, problems, ctx


class Sampler:
    """Keeps the answers of a seeded sample of request indices for the
    checks after the window; every other answer gets the O(1) root check
    and is dropped."""

    def __init__(self, seed: int, population: int, count: int):
        rng = np.random.default_rng([seed, 0x5A3B])
        count = min(count, population)
        self.want = set(
            int(i) for i in rng.choice(population, count, replace=False)
        )
        self.kept = {}
        self.problems = []

    def take(self, idx: int, root: int, result: dict) -> None:
        lv, pa = result["levels"], result["parents"]
        if int(lv[root]) != 0 or int(pa[root]) != root:
            self.problems.append(
                f"request {idx}: root {root} is not its own parent at "
                "level 0"
            )
        if idx in self.want:
            self.kept[idx] = (root, lv, pa)


def check_sample(dep, sampler: Sampler, exact_roots: int) -> list[str]:
    """Exact levels against scipy on the first ``exact_roots`` sampled
    answers; the Graph500 tree rules over all edges on every one."""
    ref = dep.reference()
    problems = list(sampler.problems)
    for k, idx in enumerate(sorted(sampler.kept)):
        root, lv, pa = sampler.kept[idx]
        if k < exact_roots:
            bad = ref.check_exact(lv, root)
            if bad:
                problems.append(f"request {idx}: {bad}")
        bad = ref.check_tree(lv, pa, root)
        if bad:
            problems.append(f"request {idx}: {bad}")
    if len(sampler.kept) < min(exact_roots, len(sampler.want)):
        problems.append(
            f"only {len(sampler.kept)} sampled answers completed"
        )
    return problems


def server_problems(st: dict, engine, mark: int) -> list[str]:
    """The configuration's guarantees a run can show, from
    ``srv.stats()``: zero retraces after warm-up, no failed or retried
    batch, no failed request."""
    out = []
    if engine.retraces_since(mark):
        out.append(f"{engine.retraces_since(mark)} retraces after warm-up")
    if st["retry_batches"] or st["worker_errors"]:
        out.append(f"{st['retry_batches']} retried batches, "
                   f"{st['worker_errors']} worker errors")
    for kind, pk in st["per_kind"].items():
        bad = {k: pk[k] for k in ("poisoned", "retried", "timeout",
                                  "rejected", "invalid") if pk[k]}
        if bad:
            out.append(f"failed {kind} requests: {bad}")
    return out


def batches_from_stages(records) -> list[dict]:
    """Per-batch facts from per-request stage records.  Requests of one
    batch share their ``execute`` seconds exactly (one pair of marks for
    the whole batch), which is what groups them.  Per batch: the host
    times (``time.time`` clock) at which the worker popped it, finished
    assembling, finished ``execute`` and finished the scatter pass."""
    groups = {}
    for rec in records:
        st = {s["stage"]: s["s"] for s in rec["stages"]}
        if "execute" not in st or rec["labels"].get("status") != "ok":
            continue
        groups.setdefault(st["execute"], []).append((rec, st))
    out = []
    for execute_s, members in groups.items():
        # every member's admission time plus its own wait ends at the pop
        rec, st = members[0]
        t_pop = rec["ts"] + st.get("queue_wait", 0.0)
        t_asm = t_pop + st.get("assemble", 0.0)
        t_exec = t_asm + execute_s
        scatter = max(m[1].get("scatter", 0.0) for m in members)
        out.append({
            "requests": len(members),
            "width": rec["labels"].get("width"),
            "execute_s": execute_s,
            "scatter_s": scatter,
            "t_pop": t_pop, "t_asm": t_asm, "t_exec": t_exec,
            "t_done": t_exec + scatter,
        })
    return sorted(out, key=lambda b: b["t_pop"])


def host_spans(batches) -> list[tuple]:
    spans = []
    for b in batches:
        spans.append(("assemble", b["t_pop"], b["t_asm"]))
        spans.append(("execute", b["t_asm"], b["t_exec"]))
        spans.append(("scatter", b["t_exec"], b["t_done"]))
    return spans


def counter_total(name: str) -> int:
    """A program counter summed over its label sets."""
    from combblas_tpu import obs

    return int(sum(
        rec.get("value", 0) for rec in obs.registry.snapshot()
        if rec.get("name") == name and rec.get("kind") == "counter"
    ))


def compile_counters() -> int:
    return counter_total("trace.serve") + counter_total(
        "compile_cache.misses"
    )


def log_plans(stats: dict, batches=None) -> None:
    """How often each plan ran, and (traced) what a batch of each width
    took: which lanes the traffic really used."""
    runs = {k: v["executions"] for k, v in stats["plans"].items()
            if v["executions"]}
    log(f"plan executions (warm-up included): {runs}")
    by_width = {}
    for b in batches or []:
        by_width.setdefault(b["width"], []).append(b["execute_s"])
    if by_width:
        log("execute per batch by width: " + ", ".join(
            f"{w}: {len(v)} x {1e3 * float(np.median(v)):.0f} ms"
            for w, v in sorted(by_width.items(), key=lambda kv: str(kv[0]))
        ))
