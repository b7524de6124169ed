"""``serve_closed``'s loop over a deployment of another graph law: the
random geometric graph of ``rgggraph.py``, brought up by
``deepdeploy.deploy_rgg``.  The loop itself is ``serve_closed``'s,
imported, not copied: same sends, same window, same checks
(``graph.Reference``: exact hop counts on the first ``check.exact``
sampled answers, the tree rules over all edges on all ``check.tree``).

Mix parameters: ``serve_closed``'s.  ``ctx["deep"]`` adds what the
``deep_*`` readers hold a wave's device time against, from scipy alone:
the directed edges and the vertices of a drawn root's component, mean
over the roots the window sent, and how deep the exactly checked answers
were.
"""

from __future__ import annotations

import numpy as np

from chipbench import deepdeploy, graph, serving


class DeepReference(graph.Reference):
    """``graph.Reference`` that remembers how deep the answers it
    checked exactly were."""

    def __init__(self, *args):
        super().__init__(*args)
        self.checked_depths = []

    def check_exact(self, levels, root: int):
        self.checked_depths.append(int(np.max(levels)))
        return super().check_exact(levels, root)


class DeepJob:
    """The job ``serve_closed`` is handed, deploying through
    ``deepdeploy``."""

    def __init__(self, job):
        self._job = job
        self.dep = None

    def __getattr__(self, name):
        return getattr(self._job, name)

    def deploy(self):
        self.dep = deepdeploy.deploy_rgg(
            self._job.cfg, self._job.spec.cache_dir())
        self.dep._ref = DeepReference(
            self.dep.n, self.dep.rows, self.dep.cols)
        return self.dep


def component_work(ref: graph.Reference, roots):
    """``(edges, vertices)`` a query: the directed edges (degree sum)
    and the vertices of a root's component, mean over ``roots``."""
    from scipy.sparse import csgraph

    _, label = csgraph.connected_components(ref.G, directed=False)
    edges = np.bincount(label, weights=ref.deg)
    vertices = np.bincount(label)
    mine = label[np.asarray(roots)]
    return float(edges[mine].mean()), float(vertices[mine].mean())


def run(job) -> dict:
    deep = DeepJob(job)
    res = job.spec.load_module("drivers", "serve_closed").run(deep)
    dep = deep.dep
    roots = graph.draw_roots(dep.deg, job.seed, 4096)
    sent = roots[np.arange(res["attempted"]) % len(roots)]
    edges, vertices = component_work(dep.reference(), sent)
    depths = dep.reference().checked_depths
    res["ctx"]["deep"] = {
        "edges_per_query": edges, "vertices_per_query": vertices,
        "checked_depths": depths,
    }
    serving.log(f"deep: a query's component holds {edges:.0f} directed "
                f"edges and {vertices:.0f} vertices (mean over "
                f"{len(sent)} roots); answers checked exactly were "
                f"{depths} levels deep")
    return res
