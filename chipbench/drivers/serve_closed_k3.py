"""``serve_closed``'s loop for Graph500 kernel 3: a kind whose answer is
a distance array and a parent array (``dist``, ``parents``).

``in_flight`` requests always outstanding, every completion replaced at
once by a new root, until the window ends; then the rest drain.  Every
answer gets the O(1) root check; a seeded sample is kept and checked
outside the window against ``chipbench/k3ref.py``: exact distances on
the first ``check.exact`` of them, the specification's five rules over
all edges on all ``check.tree``.  An answer without ``parents`` (a
program from before the kind returned its tree) ends the run at once,
non-zero, with a message.

Mix parameters: ``serve_closed``'s (``kind``, ``in_flight``, ``drain_s``,
``check``, ``trace``).  Traced, ``ctx["sssp_cost"]`` holds what
``k3cost.sssp_batch_least_bytes`` needs besides the rounds, and the
kind's six readings (``chipbench/layers/sssp_*.py``) are logged: the
result line cannot carry them until ``BENCHMARK.json`` lists them, which
takes a ``benchmark`` PR (``PERF.md`` section 7).
"""

from __future__ import annotations

import queue
import time

from chipbench import cost, graph, k3ref, loadgen, serving


LAYERS = ("sssp_device_ms", "sssp_round_ms", "sssp_parents_ms",
          "sssp_rounds", "sssp_gather_share", "sssp_hbm_share")


class K3Sampler(serving.Sampler):
    """``serving.Sampler`` (the same seeded sample of request indices)
    for ``dist`` + ``parents``."""

    def take(self, idx: int, root: int, result: dict) -> None:
        d, pa = result["dist"], result["parents"]
        if float(d[root]) != 0.0 or int(pa[root]) != root:
            self.problems.append(
                f"request {idx}: root {root} is not its own parent at "
                "distance 0"
            )
        if idx in self.want:
            self.kept[idx] = (root, d, pa)


def require_tree(result: dict) -> None:
    """End the run where the program's answer is not kernel 3's."""
    if "dist" not in result or "parents" not in result:
        raise SystemExit(
            f"chipbench: the program's sssp answer holds {sorted(result)}: "
            "Graph500 kernel 3 needs 'dist' and 'parents'"
        )


def check_sample(ref: k3ref.K3Reference, sampler: K3Sampler,
                 exact_roots: int) -> list[str]:
    problems = list(sampler.problems)
    for k, idx in enumerate(sorted(sampler.kept)):
        root, d, pa = sampler.kept[idx]
        found = [ref.check_exact(d, root)] if k < exact_roots else []
        found.append(ref.check_tree(d, pa, root))
        problems += [f"request {idx}: {bad}" for bad in found if bad]
    if len(sampler.kept) < min(exact_roots, len(sampler.want)):
        problems.append(
            f"only {len(sampler.kept)} sampled answers completed"
        )
    return problems


def log_layers(job, ctx: dict) -> None:
    """Each of the kind's readers on this run's ``ctx``, logged; a reader
    that finds nothing (no device plane, no counter) says so."""
    ctx = dict(ctx, device=job.device)
    for name in LAYERS:
        value = job.spec.load_module("layers", name).read(ctx)
        serving.log(f"layer {name}: " + (
            "nothing to read" if value is None else repr(float(value))))


def run(job) -> dict:
    mix = job.mix
    ses = serving.Session(job)
    dep = ses.dep
    kind, in_flight = mix["kind"], int(mix["in_flight"])
    sampler = K3Sampler(job.seed, 2 * in_flight, int(mix["check"]["tree"]))
    roots = graph.draw_roots(dep.deg, job.seed, 4096)
    root_of = lambda i: int(roots[i % len(roots)])
    done = queue.SimpleQueue()
    completions = []

    def submit(i: int) -> None:
        ses.srv.submit(kind, root_of(i)).add_done_callback(
            lambda f, i=i: done.put((i, time.perf_counter(), f))
        )

    t_first = ses.open_window()
    t_end = t_first + job.seconds
    for sent in range(in_flight):
        submit(sent)
    sent = open_ = in_flight
    deadline = t_end + float(mix["drain_s"])
    while open_:
        try:
            i, t_done, fut = done.get(
                timeout=max(deadline - time.perf_counter(), 0.01)
            )
        except queue.Empty:
            break
        open_ -= 1
        if fut.exception() is not None:
            ses.failures.append(repr(fut.exception()))
        else:
            # (the worker is a daemon thread: nothing to close first, and
            # a close would wait out the batch now on the device)
            require_tree(fut.result())
            sampler.take(i, root_of(i), fut.result())
            completions.append(t_done)
        del fut
        if time.perf_counter() < t_end:
            submit(sent)
            sent += 1
            open_ += 1
    # the session's own checks are BFS's: hand them a sampler that wants
    # and keeps nothing, and add this kind's
    compiles, problems, ctx = ses.close_window(
        serving.Sampler(job.seed, 0, 0)
    )
    t0 = time.perf_counter()
    ref = k3ref.K3Reference(
        dep.n, dep.rows, dep.cols,
        graph.edge_weights(dep.rows, dep.cols, int(job.cfg["graph_seed"])),
    )
    problems += check_sample(ref, sampler, int(mix["check"]["exact"]))
    serving.log(f"kernel 3: checked {len(sampler.kept)} sampled answers "
                f"in {time.perf_counter() - t0:.1f} s")
    whole = [w for w in loadgen.waves(completions) if w[0] <= t_end]
    ctx["waves"] = whole
    ctx["sssp_cost"] = {
        "n": dep.n, "slots": cost.ell_slots(dep.engine.E_weighted),
        "width": max(int(w) for w in job.cfg["lane_widths"]),
    }
    serving.log(f"{sum(c for _, c in whole)} completions in {len(whole)} "
                "whole waves inside the window")
    if job.trace:
        log_layers(job, ctx)
    return {
        "attempted": sent,
        "failed": sent - len(completions),
        "problems": problems,
        "compiles_in_window": compiles,
        "t_first_send": t_first,
        "values": {"qps": loadgen.wave_rate(completions, until=t_end)},
        "ctx": ctx,
    }
