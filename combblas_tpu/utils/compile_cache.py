"""Persistent XLA compilation cache switch, shared by every driver.

One definition so ``chip_smoke.py`` and ``chipbench`` run under
identical cache behavior.  Library constructors never call it: a process
entry point does, once, at start.

PLACEMENT: where ``JAX_COMPILATION_CACHE_DIR`` is set, the cache lives
there and this module sets NO directory in code (JAX reads the variable
itself; children of a launcher inherit it through the environment).
Otherwise the directory is the fixed ``<checkout>/.jax_cache`` — the
path is part of the cache key, so a directory that moves never hits.

IDEMPOTENCE CONTRACT: the cache dir is process-global jax config, so
the first ``enable_compile_cache`` call wins. Re-enabling with no
argument ("ensure the cache is on") or with the SAME (resolved) dir is
a no-op; an EXPLICIT different dir raises — silently retargeting the
cache mid-process would split compiled artifacts across two dirs and
make hit/miss counters unattributable. ``_reset_for_tests()`` is the
explicit test-only escape hatch.

When telemetry is on (``combblas_tpu.obs``), enabling the cache also
installs the jax.monitoring bridge so persistent-cache hits/misses
surface as the ``compile_cache.hits`` / ``compile_cache.misses``
counters (and each fetch's and compile's seconds as an event of the span
it ran under: ``obs.JAX_DURATION_EVENTS``), and registers a pull-provider publishing the
``compile_cache.entries`` gauge (files currently in the cache dir) into
every report/JSONL dump.
"""

from __future__ import annotations

import os

from .. import obs

CACHE_DIR = os.path.normpath(
    os.path.join(os.path.dirname(__file__), "..", "..", ".jax_cache")
)

#: The variable JAX itself reads for ``jax_compilation_cache_dir``.
ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"

#: The dir the process committed to on the first successful enable call
#: (None = not yet enabled). See the idempotence contract above.
_configured_dir: str | None = None


def _env_dir() -> str | None:
    """The directory the environment places the cache in, if any."""
    return os.environ.get(ENV_CACHE_DIR) or None


def configured_dir() -> str | None:
    """The cache dir this process committed to, or None when the cache
    was never enabled — the public accessor (the underlying global is
    an internal invariant of the idempotence contract)."""
    return _configured_dir


def _record_cache_entries() -> None:
    """obs provider: persistent-cache entry count, polled at export time
    (a push on every compile would race the async cache writer)."""
    if _configured_dir is not None:
        try:
            entries = sum(
                1 for e in os.scandir(_configured_dir) if e.is_file()
            )
        except OSError:
            entries = 0
        obs.gauge("compile_cache.entries", entries, dir=_configured_dir)


def enable_compile_cache(cache_dir: str | None = None) -> None:
    global _configured_dir
    import jax

    if obs.ENABLED:
        obs.install_jax_hooks()
    env_dir = _env_dir()
    # abspath: "cache" and os.path.abspath("cache") are the same dir,
    # and the committed identity must not drift under a later chdir
    resolved = os.path.abspath(cache_dir or env_dir or CACHE_DIR)
    committed = _configured_dir or (
        os.path.abspath(env_dir) if env_dir else None
    )
    if committed is not None and cache_dir is not None and (
        resolved != committed
    ):
        raise ValueError(
            f"compile cache already enabled at {committed!r}; "
            f"cannot retarget to {resolved!r} in the same process "
            "(jax_compilation_cache_dir is process-global — see the "
            "placement and idempotence notes in utils/compile_cache.py)"
        )
    if _configured_dir is not None:
        # cache_dir=None means "ensure enabled", not "move to the
        # default dir" — every argless caller (chip_smoke.py,
        # chipbench) must keep working after someone committed a
        # custom dir
        return  # idempotent re-enable
    if not env_dir:
        jax.config.update("jax_compilation_cache_dir", resolved)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _configured_dir = resolved
    obs.register_provider(_record_cache_entries)


def _reset_for_tests() -> None:
    """Forget the committed cache dir (TEST-ONLY: lets a test exercise
    the idempotence contract without poisoning the process for later
    callers — restore the prior value afterwards)."""
    global _configured_dir
    _configured_dir = None
