"""Tier-1 guard: ``COMBBLAS_*`` env knobs are parsed in ONE place.

Every knob (serve, dynamic, obs, shard) is parsed in
``tuner/config.py`` (precedence documented once, identical "0 means
default" semantics everywhere).  This test locks the invariant in: any
new ``os.environ`` read of a ``COMBBLAS_`` name outside the allowlist
below fails tier-1, so scattered knob parsing cannot creep back.  No
knob routes a product: the fifteen that did (PR 43 took them out) stay
out.

Allowed:

* ``tuner/config.py`` — the one parser;
* ``obs/__init__.py`` — ``COMBBLAS_OBS`` / ``COMBBLAS_OBS_SYNC`` only:
  the telemetry gate must resolve at import time without pulling the
  tuner package into every obs consumer.
"""

import os
import re

import combblas_tpu

PKG_ROOT = os.path.dirname(os.path.abspath(combblas_tpu.__file__))

#: file (relative, /-separated) -> allowed COMBBLAS_* names, or "*".
ALLOWED = {
    "tuner/config.py": "*",
    "obs/__init__.py": {"COMBBLAS_OBS", "COMBBLAS_OBS_SYNC"},
}

_NAME = re.compile(r"COMBBLAS_[A-Z0-9_]+")
_BENCH_NAME = re.compile(r"(?<![A-Z0-9_])BENCH_[A-Z0-9_]+")


def _env_read_names(lines, idx, name_re=_NAME, window=2):
    """``name_re`` names within ``window`` lines of an os.environ read
    (catches the name sitting on the call line or a continuation)."""
    lo = max(0, idx - window)
    hi = min(len(lines), idx + window + 1)
    names = set()
    for ln in lines[lo:hi]:
        names.update(name_re.findall(ln))
    return names


def _stray_env_reads(root, name_re, allowed=None, skip_dirs=()):
    """``rel:line: [names]`` for every os.environ read under ``root``
    that sits beside a ``name_re`` name the file is not allowed."""
    allowed = allowed or {}
    violations = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [
            d for d in dirnames
            if d != "__pycache__"
            and os.path.join(dirpath, d) not in skip_dirs
        ]
        for fn in filenames:
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            rel = os.path.relpath(path, root).replace(os.sep, "/")
            ok = allowed.get(rel, set())
            if ok == "*":
                continue
            with open(path, encoding="utf-8") as f:
                lines = f.readlines()
            for i, line in enumerate(lines):
                if "os.environ" not in line and "environ[" not in line:
                    continue
                stray = _env_read_names(lines, i, name_re) - set(ok)
                if stray:
                    violations.append(
                        f"{rel}:{i + 1}: {sorted(stray)}"
                    )
    return violations


def test_no_stray_combblas_env_reads():
    violations = _stray_env_reads(PKG_ROOT, _NAME, ALLOWED)
    assert not violations, (
        "COMBBLAS_* env reads outside tuner/config.py (add an accessor "
        "there instead — precedence and '0 means default' semantics "
        "live in one place):\n" + "\n".join(violations)
    )


def test_no_bench_env_reads():
    """The pre-chip bench stack and its ``BENCH_*`` switches are gone
    (PR 28): neither the package nor the tests read one.  The
    benchmark (``chipbench/``, ``tests/chipbench/``) takes arguments,
    not environment switches."""
    tests_root = os.path.dirname(os.path.abspath(__file__))
    violations = _stray_env_reads(PKG_ROOT, _BENCH_NAME) + [
        "tests/" + v for v in _stray_env_reads(
            tests_root, _BENCH_NAME,
            skip_dirs=(os.path.join(tests_root, "chipbench"),),
        )
    ]
    assert not violations, (
        "BENCH_* env reads (measure through `python3 -m chipbench.run`; "
        "a library knob is a COMBBLAS_* name in tuner/config.py):\n"
        + "\n".join(violations)
    )


def test_dynamic_knobs_centralized():
    """The round-11 knobs exist and parse through tuner/config."""
    from combblas_tpu.tuner import config

    assert config.ENV_DYNAMIC_SPILL.startswith("COMBBLAS_")
    assert 0 < config.dynamic_spill_frac() <= 1.0


#: The names that chose, sized or remembered a product's kernel until
#: PR 43: a product is routed by an argument or by its operands' counts.
ROUTING_NAMES = (
    "COMBBLAS_SPGEMM_TIER", "COMBBLAS_SPGEMM_BACKEND",
    "COMBBLAS_SPGEMM_BLOCK_ROWS", "COMBBLAS_SPGEMM_BLOCK_COLS",
    "COMBBLAS_SPGEMM_DISPATCH", "COMBBLAS_SPGEMM_BUCKET_CAPS",
    "COMBBLAS_SPGEMM_MERGE", "COMBBLAS_SPGEMM3D_TIER",
    "COMBBLAS_SPMM_BACKEND", "COMBBLAS_PLAN_STORE",
    "COMBBLAS_PLAN_STORE_MAX", "COMBBLAS_PLAN_STORE_COMPACT_MIN",
    "COMBBLAS_TUNER_PROBE", "COMBBLAS_TUNER_PROBE_BUDGET_S",
    "COMBBLAS_TUNER_PROBE_MAX_DIM",
)


def test_no_routing_knob_is_named_in_the_package_or_its_documents():
    """None of the fifteen names occurs in ``combblas_tpu/``, ``docs/``
    or ``README.md``, as code, comment or prose."""
    repo = os.path.dirname(PKG_ROOT)
    assert len(set(ROUTING_NAMES)) == 15
    paths = [os.path.join(repo, "README.md")]
    for top in (PKG_ROOT, os.path.join(repo, "docs")):
        for dirpath, dirnames, filenames in os.walk(top):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            paths += [
                os.path.join(dirpath, fn) for fn in filenames
                if fn.endswith((".py", ".md"))
            ]
    assert len(paths) > 80  # the sweep swept the package
    found = []
    for path in paths:
        with open(path, encoding="utf-8") as f:
            text = f.read()
        found += [
            f"{os.path.relpath(path, repo)}: {name}"
            for name in ROUTING_NAMES if name in text
        ]
    assert not found, "\n".join(found)


def test_durability_knobs_centralized(monkeypatch, tmp_path):
    """The round-16 durability knobs parse through tuner/config with
    the shared conventions: unset/"0"/"off" disable the WAL dir,
    explicit argument beats the env, a bogus fsync policy raises
    NAMING the knob, and the integer knobs clamp sane."""
    import pytest

    from combblas_tpu.tuner import config

    for name in (
        config.ENV_WAL, config.ENV_WAL_FSYNC,
        config.ENV_CHECKPOINT_EVERY, config.ENV_CHECKPOINT_RETAIN,
    ):
        assert name.startswith("COMBBLAS_")
    # conftest pins these to defaults: durability off, fsync always
    assert config.wal_dir() is None
    assert config.wal_fsync() == config.DEFAULT_WAL_FSYNC == "always"
    assert config.checkpoint_every() == config.DEFAULT_CHECKPOINT_EVERY
    assert (
        config.checkpoint_retain() == config.DEFAULT_CHECKPOINT_RETAIN
    )
    monkeypatch.setenv(config.ENV_WAL, str(tmp_path))
    monkeypatch.setenv(config.ENV_WAL_FSYNC, "off")
    monkeypatch.setenv(config.ENV_CHECKPOINT_EVERY, "3")
    monkeypatch.setenv(config.ENV_CHECKPOINT_RETAIN, "5")
    assert config.wal_dir() == str(tmp_path)
    assert config.wal_fsync() == "off"
    assert config.checkpoint_every() == 3
    assert config.checkpoint_retain() == 5
    # argument > env; "off"/"0" disable explicitly; vetting raises
    assert config.wal_dir("off") is None
    assert config.wal_dir("0") is None
    assert config.wal_fsync("always") == "always"
    assert config.checkpoint_every(1) == 1
    assert config.checkpoint_retain(0) == 1  # clamped: retain >= 1
    with pytest.raises(ValueError, match=config.ENV_WAL_FSYNC):
        config.wal_fsync("sometimes")


def test_fleet_obs_knobs_centralized(monkeypatch, tmp_path):
    """The round-18 fleet-observability knobs parse through
    tuner/config with the shared conventions: unset/"0"/"off" disable
    the fleetlog path, explicit argument beats the env, and the
    heartbeat-snapshot cadence clamps sane."""
    from combblas_tpu.tuner import config

    for name in (config.ENV_FLEETLOG, config.ENV_OBS_HB_METRICS_S):
        assert name.startswith("COMBBLAS_")
    # conftest pins these to "0" => defaults: no operator fleetlog
    # redirect, default heartbeat-snapshot cadence
    assert config.fleetlog_path() is None
    assert (
        config.obs_hb_metrics_interval() == config.DEFAULT_OBS_HB_METRICS_S
    )
    log = tmp_path / "fleet.jsonl"
    monkeypatch.setenv(config.ENV_FLEETLOG, str(log))
    monkeypatch.setenv(config.ENV_OBS_HB_METRICS_S, "2.5")
    assert config.fleetlog_path() == str(log)
    assert config.obs_hb_metrics_interval() == 2.5
    # argument > env; "off"/"0" disable explicitly; cadence clamps
    assert config.fleetlog_path("off") is None
    assert config.fleetlog_path("0") is None
    assert config.obs_hb_metrics_interval(0.001) == 0.05
    assert (
        config.obs_hb_metrics_interval(0)
        == config.DEFAULT_OBS_HB_METRICS_S
    )


def test_net_knobs_centralized(monkeypatch):
    """The round-19 net-frontend knobs parse through tuner/config
    with the shared conventions: unset/"0" = default
    (port 0 = ephemeral bind), explicit argument beats the env, the
    count knobs clamp sane, and a bogus value raises NAMING the
    knob."""
    import pytest

    from combblas_tpu.tuner import config

    for name in (
        config.ENV_NET_PORT, config.ENV_NET_MAX_CONNS,
        config.ENV_NET_ACCEPT_BACKLOG,
    ):
        assert name.startswith("COMBBLAS_")
    # conftest pins these to "0" => defaults: ephemeral port, default
    # conn/backlog caps
    assert config.net_port() == config.DEFAULT_NET_PORT == 0
    assert config.net_max_conns() == config.DEFAULT_NET_MAX_CONNS
    assert config.net_accept_backlog() == config.DEFAULT_NET_ACCEPT_BACKLOG
    monkeypatch.setenv(config.ENV_NET_PORT, "19219")
    monkeypatch.setenv(config.ENV_NET_MAX_CONNS, "64")
    monkeypatch.setenv(config.ENV_NET_ACCEPT_BACKLOG, "16")
    assert config.net_port() == 19219
    assert config.net_max_conns() == 64
    assert config.net_accept_backlog() == 16
    # argument > env, clamped sane
    assert config.net_port(0) == 0
    assert config.net_max_conns(1) == 1
    assert config.net_max_conns(-3) == 1  # clamp >= 1
    assert config.net_accept_backlog(-1) == 1
    # vetting raises NAMING the knob
    with pytest.raises(ValueError, match=config.ENV_NET_PORT):
        config.net_port(70000)
    with pytest.raises(ValueError, match=config.ENV_NET_PORT):
        config.net_port("not-a-port")
    with pytest.raises(ValueError, match=config.ENV_NET_MAX_CONNS):
        config.net_max_conns("many")


def test_shard_wire_knobs_centralized(monkeypatch):
    """The round-21 sharded wire-protocol knobs parse through
    tuner/config with the shared conventions: unset/""/"0" = default,
    explicit argument beats the env, the density fraction is vetted
    to (0, 1], and a bogus value raises NAMING the knob."""
    import pytest

    from combblas_tpu.tuner import config

    for name in (config.ENV_SHARD_FRONTIER, config.ENV_SHARD_DENSITY,
                 config.ENV_SHARD_WIRE):
        assert name.startswith("COMBBLAS_")
    # conftest pins ""/"0" => defaults
    assert config.shard_frontier() == config.DEFAULT_SHARD_FRONTIER
    assert config.shard_frontier() == "auto"
    assert config.shard_density() == config.DEFAULT_SHARD_DENSITY
    assert config.shard_wire() == config.DEFAULT_SHARD_WIRE == "f32"
    monkeypatch.setenv(config.ENV_SHARD_FRONTIER, "sparse")
    monkeypatch.setenv(config.ENV_SHARD_DENSITY, "0.5")
    monkeypatch.setenv(config.ENV_SHARD_WIRE, "bf16")
    assert config.shard_frontier() == "sparse"
    assert config.shard_density() == 0.5
    assert config.shard_wire() == "bf16"
    # explicit argument beats the env
    assert config.shard_frontier("dense") == "dense"
    assert config.shard_density(0.1) == 0.1
    assert config.shard_wire("f32") == "f32"
    # "0" falls through to the default
    assert config.shard_density(0) == config.DEFAULT_SHARD_DENSITY
    # vetting raises NAMING the knob
    with pytest.raises(ValueError, match=config.ENV_SHARD_FRONTIER):
        config.shard_frontier("csr")
    with pytest.raises(ValueError, match=config.ENV_SHARD_DENSITY):
        config.shard_density(1.5)
    with pytest.raises(ValueError, match=config.ENV_SHARD_DENSITY):
        config.shard_density("most")
    with pytest.raises(ValueError, match=config.ENV_SHARD_WIRE):
        config.shard_wire("fp8")


def test_pool_fleet_knobs_centralized(monkeypatch):
    """The round-14 pool/fleet knobs parse through tuner/config with
    the shared conventions (unset/empty/"0" = default; explicit
    argument beats the env)."""
    from combblas_tpu.tuner import config

    for name in (
        config.ENV_POOL_BYTE_BUDGET, config.ENV_POOL_QUANTUM,
        config.ENV_FLEET_REPLICAS,
    ):
        assert name.startswith("COMBBLAS_")
    # conftest pins these to "0" => defaults
    assert config.pool_byte_budget() == config.DEFAULT_POOL_BYTE_BUDGET
    assert config.pool_quantum() == config.DEFAULT_POOL_QUANTUM
    assert config.fleet_replicas() == config.DEFAULT_FLEET_REPLICAS
    monkeypatch.setenv(config.ENV_POOL_BYTE_BUDGET, str(1 << 20))
    monkeypatch.setenv(config.ENV_POOL_QUANTUM, "8")
    monkeypatch.setenv(config.ENV_FLEET_REPLICAS, "3")
    assert config.pool_byte_budget() == 1 << 20
    assert config.pool_quantum() == 8
    assert config.fleet_replicas() == 3
    # argument > env, clamped sane
    assert config.pool_byte_budget(4096) == 4096
    assert config.pool_quantum(1) == 1
    assert config.fleet_replicas(5) == 5
