"""``combblas_tpu.serve.net`` — the TCP network front door (round 19).

Layer 9 of the serving stack (``serve/__init__.py`` has the map):
``frontend.py`` listens on a stdlib TCP socket and bridges the
versioned wire protocol (``protocol.py``, spoken over the shared
``serve/frame.py`` codec — one codec, two transports) to any
in-process backend (``Server``/``PoolServer``/``FleetRouter``/
``ProcessFleet``); ``client.py`` is the blocking client; and
``loadgen.py`` is the OPEN-LOOP Poisson load harness (latency from
the scheduled arrival: no coordinated omission).  docs/serving.md "Network front door" has the
protocol frames, the status taxonomy table, and deadline semantics.
"""

from .client import NetClient
from .frontend import NetFrontend
from .protocol import (
    ERROR_STATUSES,
    PROTOCOL_VERSION,
    wire_error,
    wire_exception,
)

__all__ = [
    "NetClient",
    "NetFrontend",
    "PROTOCOL_VERSION",
    "ERROR_STATUSES",
    "wire_error",
    "wire_exception",
]
