"""A boot's spans (``docs/observability.md`` "A boot's timeline"): every
boundary between the snapshot and a started server records a span on
``perf_counter``, children under parents, JAX's own trace / lower /
compile seconds attached where they ran, and nothing at all with
telemetry off."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from combblas_tpu import obs
from combblas_tpu.obs.spans import NULL_SPAN
from combblas_tpu.parallel.grid import Grid
from combblas_tpu.serve import GraphEngine, ServeConfig
from combblas_tpu.utils import checkpoint


@pytest.fixture(autouse=True)
def _clean_obs():
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


def _coo(n=96, m=700, seed=0):
    rng = np.random.default_rng(seed)
    r, c = rng.integers(0, n, m), rng.integers(0, n, m)
    keep = r != c
    r, c = r[keep], c[keep]
    return np.concatenate([r, c]), np.concatenate([c, r]), n


def _family(spans, parent_name):
    """``(parent, [children])`` of the one span called ``parent_name``."""
    parent, = [s for s in spans if s["path"] == parent_name]
    kids = [s for s in spans
            if s["path"].rpartition("/")[0] == parent_name]
    return parent, kids


def _check_family(parent, kids, names):
    assert {k["name"] for k in kids} == set(names)
    assert sum(k["wall_s"] for k in kids) <= parent["wall_s"] + 1e-4
    end = parent["t0"] + parent["wall_s"]
    for k in kids:
        assert parent["t0"] <= k["t0"]
        assert k["t0"] + k["wall_s"] <= end + 1e-4
    # the log is in closing order: children before their parent, and
    # along the children the monotonic clock only grows
    starts = [k["t0"] for k in kids]
    assert starts == sorted(starts)


def test_from_coo_and_load_version_record_their_children(tmp_path):
    rows, cols, n = _coo()
    grid = Grid.make(1, 1)
    obs.enable()
    t_before = time.perf_counter()
    eng = GraphEngine.from_coo(grid, rows, cols, n, keep_coo=True,
                               kinds=("bfs",))
    spans = obs.spans()
    load, kids = _family(spans, "serve.load")
    _check_family(load, kids, {"bucket", "upload", "companion"})
    assert load["t0"] >= t_before and "ts" in load
    init, = [s for s in spans if s["path"] == "serve.engine.init"]
    assert init["t0"] >= load["t0"] + load["wall_s"] - 1e-4

    path = str(tmp_path / "v.npz")
    checkpoint.save_version(path, eng.version)
    obs.reset()
    version = checkpoint.load_version(path, grid)
    restore, kids = _family(obs.spans(), "serve.restore")
    _check_family(restore, kids, {"read", "upload", "companion"})
    attrs = restore["attrs"]
    assert attrs["path"] == path
    assert 0 < attrs["file_bytes"] < attrs["host_bytes"]
    assert attrs["device_bytes"] == version.device_bytes() > 0
    # the operator's series stays beside the span
    assert obs.registry.get_histogram(
        "serve.checkpoint.load_s")["count"] == 1
    # the restored version serves what the built one does
    a = eng.execute("bfs", np.array([3], np.int32))
    b = GraphEngine(grid, version=version, kinds=("bfs",)).execute(
        "bfs", np.array([3], np.int32))
    assert np.array_equal(a["levels"], b["levels"])


def test_a_fresh_plans_warmup_carries_jaxs_own_seconds():
    rows, cols, n = _coo(seed=1)
    eng = GraphEngine.from_coo(Grid.make(1, 1), rows, cols, n,
                               keep_coo=True, kinds=("bfs",))
    obs.enable()
    srv = eng.serve(ServeConfig(lane_widths=(2,)))
    srv.warmup(kinds=("bfs",))
    srv.close()
    spans = obs.spans()
    assert [s["name"] for s in spans if "/" not in s["path"]] == [
        "serve.server.init", "serve.warmup.companion", "serve.warmup"]
    warm, = [s for s in spans if s["path"] == "serve.warmup"]
    # a bfs plan's span also says what its levels gather from (PR 35)
    assert warm["attrs"] == {"kind": "bfs", "width": 2, "payload": "bits",
                             "table_bytes": 4 * (n + 1)}
    parts = {p["stage"]: p["s"] for p in warm["parts"]}
    assert list(parts) == ["build", "execute", "probe"]
    assert sum(parts.values()) == pytest.approx(warm["wall_s"], rel=0.05)
    names = {e["name"] for e in warm["events"]}
    assert {"trace", "lower"} <= names and names & {"compile", "fetch"}
    # each on the span's clock, inside the execute part
    t_build = warm["t0"] + parts["build"]
    for e in warm["events"]:
        assert t_build <= e["t"] <= t_build + parts["execute"] + 1e-3
        assert 0 <= e["s"] <= parts["execute"]
    # the probe is a span of its own under the plan's
    probe, = [s for s in spans
              if s["path"] == "serve.warmup/obs.opnames.publish"]
    assert probe["wall_s"] <= parts["probe"] + 1e-4
    assert "jit_serve_bfs_w2" in obs.opnames.tables()


def test_a_duration_with_no_span_open_lands_top_level():
    obs.enable()
    t0 = time.perf_counter()
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
    t1 = time.perf_counter()
    events = [e for e in obs.events()
              if e["name"] in obs.JAX_DURATION_EVENTS.values()]
    assert {"trace", "lower", "compile"} <= {e["name"] for e in events}
    for e in events:
        assert t0 <= e["t"] <= t1 and 0 <= e["s"] <= t1 - t0 and "ts" in e
    assert obs.spans() == []
    # the registry grows by no series a JAX event
    assert not [r for r in obs.registry.snapshot()
                if r["name"].startswith("/jax/")]


def test_boot_sites_are_free_with_telemetry_off(tmp_path):
    rows, cols, n = _coo(seed=2)
    grid = Grid.make(1, 1)
    obs.install_jax_hooks()
    assert obs.span("serve.restore") is NULL_SPAN
    # the hooks a site reaches on the null span
    assert NULL_SPAN.mark("build") is None
    assert NULL_SPAN.annotate(file_bytes=1) is None
    assert NULL_SPAN.sync_on(object()) is None
    eng = GraphEngine.from_coo(grid, rows, cols, n, keep_coo=True,
                               kinds=("bfs",))
    path = str(tmp_path / "v.npz")
    checkpoint.save_version(path, eng.version)
    eng = GraphEngine(grid, version=checkpoint.load_version(path, grid),
                      kinds=("bfs",))
    srv = eng.serve(ServeConfig(lane_widths=(1,)))
    srv.warmup(kinds=("bfs",))
    srv.close()
    assert obs.spans() == [] and obs.events() == []
    assert obs._spans.empty() and obs.opnames.tables() == {}
