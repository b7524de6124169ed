"""Bring-up guards (ISSUE 21): nothing in the product hides the device.

* importing the package (and every launcher module) starts no backend —
  a router or launcher that imports it must not take the chip its
  children need;
* the compile cache is placed from OUTSIDE: with
  ``JAX_COMPILATION_CACHE_DIR`` set the program sets no directory in
  code, unset it resolves to the fixed ``<checkout>/.jax_cache``;
* the launchers' child environment passes the platform through and
  never assigns ``JAX_PLATFORMS``;
* ``chip_smoke.py`` refuses to run anywhere but on a TPU, and its last
  stdout line carries exactly ``ok`` and ``device``.
"""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_import_starts_no_backend():
    code = (
        "import combblas_tpu, combblas_tpu.serve.procfleet, "
        "combblas_tpu.serve.shard, combblas_tpu.serve.net, "
        "combblas_tpu.models.bfs, combblas_tpu.models.cc\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized(), "
        "list(xla_bridge._backends)\n"
    )
    r = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True,
        text=True, timeout=120,
    )
    assert r.returncode == 0, r.stderr[-2000:]


def test_compile_cache_is_placed_from_outside(tmp_path, monkeypatch):
    import jax

    from combblas_tpu.utils import compile_cache as cc

    prior = cc._configured_dir
    saved = {
        k: getattr(jax.config, k) for k in (
            "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes",
        )
    }
    updates = []
    real_update = jax.config.update
    monkeypatch.setattr(
        jax.config, "update",
        lambda k, v: (updates.append(k), real_update(k, v))[1],
    )
    cc._reset_for_tests()
    try:
        # placed by the environment: no directory is set in code, the
        # committed dir (entries gauge) follows it
        monkeypatch.setenv(cc.ENV_CACHE_DIR, str(tmp_path / "outside"))
        cc.enable_compile_cache()
        assert "jax_compilation_cache_dir" not in updates
        assert cc.configured_dir() == str(tmp_path / "outside")
        with pytest.raises(ValueError, match="already enabled"):
            cc.enable_compile_cache(str(tmp_path / "elsewhere"))
        # unset: the fixed <checkout>/.jax_cache, never a temporary name
        cc._reset_for_tests()
        del updates[:]
        monkeypatch.delenv(cc.ENV_CACHE_DIR)
        cc.enable_compile_cache()
        assert "jax_compilation_cache_dir" in updates
        assert cc.configured_dir() == os.path.join(REPO, ".jax_cache")
    finally:
        monkeypatch.undo()
        cc._configured_dir = prior
        for k, v in saved.items():
            jax.config.update(k, v)


def test_child_env_passes_the_platform_through(monkeypatch):
    from combblas_tpu.serve.procfleet import child_env

    flag = "--xla_force_host_platform_device_count"
    # inherited cpu: own virtual partition, other flags kept, the
    # platform untouched (still exactly what the router was given)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("XLA_FLAGS", f"--xla_foo=1 {flag}=8")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    env = child_env(1, devices=4)
    assert env["JAX_PLATFORMS"] == "cpu"
    assert env["XLA_FLAGS"].split() == ["--xla_foo=1", f"{flag}=4"]
    assert env["JAX_COMPILATION_CACHE_DIR"] == "/some/dir"
    assert "TPU_VISIBLE_CHIPS" not in env
    # any other inherited platform (unset = JAX takes the accelerator):
    # nothing is assigned, no host-device flag is added, and child i is
    # confined to chip i
    for platform in (None, "tpu"):
        if platform is None:
            monkeypatch.delenv("JAX_PLATFORMS")
        else:
            monkeypatch.setenv("JAX_PLATFORMS", platform)
        monkeypatch.setenv("XLA_FLAGS", "--xla_foo=1")
        env = child_env(3)
        assert env.get("JAX_PLATFORMS") == platform
        assert env["XLA_FLAGS"] == "--xla_foo=1"
        assert env["TPU_VISIBLE_CHIPS"] == "3"
        assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
        with pytest.raises(ValueError, match="exactly one chip"):
            child_env(0, devices=2)


def test_chip_smoke_refuses_the_cpu():
    """``JAX_PLATFORMS=cpu`` (this suite's environment): non-zero exit,
    no result line, no query run."""
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "not 'tpu'" in r.stderr


def test_chip_smoke_last_line_is_the_bare_verdict(capsys):
    """The driver parses the LAST stdout line and refuses any key beyond
    ``ok`` and ``device {platform, kind, count}``; the walls go on the
    line before it, which ends ``"claim": null``."""
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py")
    )
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    dev = {"platform": "tpu", "device_kind": "TPU v5 lite", "n_devices": 1,
           "jax": "0", "jaxlib": "0", "libtpu": "0", "cache_dir": "/c"}
    serve1 = dict(dev, scale=20, nnz=7, build_s=1.0)
    smoke.emit_result(serve1, {"serve1": serve1, "mesh4": "skipped: 1 chip"})
    summary, verdict = map(json.loads, capsys.readouterr().out.splitlines())
    assert verdict == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }
    assert list(summary)[-1] == "claim" and summary["claim"] is None
    assert summary["serve1"] == {"scale": 20, "nnz": 7, "build_s": 1.0}
