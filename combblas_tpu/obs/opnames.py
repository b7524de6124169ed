"""Where the compiler's instructions came from: per compiled program,
the table ``{HLO instruction: op_name}``.

A device trace names each operation by the compiler's instruction
(``fusion.249``) and carries no ``jax.named_scope``: the scopes live
only in the ``op_name`` metadata of the compiled text.  A program that
wants its scopes read back from a trace (the served plans at warm-up,
``bfs_batch_compact`` on its first call) publishes that text here while
telemetry is on; a trace reader joins events to scopes by instruction
name, per module name (``jit_serve_bfs_w16``).  Publishing compiles
nothing new on a warm persistent cache and is never done with telemetry
off.  What it does cost (lowering again, the cache fetch, the text of
the whole program, the parse) runs under the span
``obs.opnames.publish``, so a traced boot can say what it paid for being
traced.  ``obs.reset()`` clears the tables.
"""

from __future__ import annotations

import re
import threading

_MODULE = re.compile(r"^HloModule ([\w.\-]+)", re.M)
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = [^\n]*?"
    r"metadata=\{[^}\n]*?op_name=\"([^\"]*)\"", re.M,
)

_lock = threading.Lock()
_tables: dict[str, dict[str, str]] = {}
_published: set = set()


def parse(hlo_text: str) -> tuple[str | None, dict[str, str]]:
    """``(module name, {instruction: op_name})`` of one compiled
    program's text (``compiled.as_text()``)."""
    m = _MODULE.search(hlo_text)
    return (
        m.group(1) if m else None,
        dict(_INSTRUCTION.findall(hlo_text)),
    )


def publish(hlo_text, nth: int | None = None) -> str | None:
    """Keep the table of one compiled program under its module name
    (a later program of the same name replaces it).  Returns the name.
    ``hlo_text``: the compiled text, or a zero-argument callable that
    makes it (so that making it is inside the span too).  ``nth``: for
    a function one job launches under several static signatures, each
    its own program of the one module name: this launch's place among
    them, kept as ``<module>#<nth>`` (a trace reader takes a job's
    executions of the module in order)."""
    from .. import obs

    with obs.span("obs.opnames.publish"):
        if callable(hlo_text):
            hlo_text = hlo_text()
        name, table = parse(hlo_text)
        if name is None:
            return None
        if nth is not None:
            name = f"{name}#{nth}"
        with _lock:
            _tables[name] = table
        return name


def publish_once(key, make_text, nth: int | None = None) -> None:
    """``publish(make_text())`` the first time ``key`` is seen: for a
    program with no warm-up of its own, whose every call asks."""
    with _lock:
        if key in _published:
            return
        _published.add(key)
    publish(make_text, nth)


def tables() -> dict[str, dict[str, str]]:
    """``{module name: {instruction: op_name}}`` published so far."""
    with _lock:
        return {k: dict(v) for k, v in _tables.items()}


def clear() -> None:
    with _lock:
        _tables.clear()
        _published.clear()
