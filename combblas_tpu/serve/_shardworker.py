"""Slice subprocess entry point (round 20, sharded serving).

``python -m combblas_tpu.serve._shardworker --fd N`` is what
``ProcSlice`` spawns: one OS process hosting ONE row slab of the
sharded graph (a ``shard.SliceRuntime``) with its OWN JAX runtime on
ONE device of the platform the router was launched under (a slice IS
the host in the multi-host story; the mesh lives across processes, not
inside one).  The launcher prepares that environment
(``procfleet.child_env``); nothing here chooses a device.

Protocol: the ``_procworker`` conventions verbatim — framed request/
reply on the inherited socketpair (``{"id": n, "op": ...}`` →
``{"id": n, "ok": ...}``), unsolicited ``{"hb": {...}}`` heartbeats
carrying depth/frontier/serving so the router's ``ReplicaProc``
machinery distinguishes wedged from busy, and op dispatch shared with
the in-process slice through :func:`shard.dispatch_slice_op` — one
protocol, two transports.

Unlike ``_procworker``, graph payloads DO cross the socket at first
boot: the slab COO rides the frame codec's native ndarray channel
(``__ndb__`` hoisting) because no whole-graph checkpoint exists to
load from — sharding is the point.  Respawn boots recover from the
slice's own home directory (slab snapshot + per-slice WAL suffix)
and ship nothing.
"""

from __future__ import annotations

import argparse
import os
import socket
import sys
import threading
import time
import traceback

# import-light; reads COMBBLAS_OBS (pinned by the parent) at import
from .. import obs


class ShardWorker:
    """The child-side dispatcher: one SliceRuntime, one channel."""

    def __init__(self, channel, hb_interval_s: float = 0.25,
                 metrics_interval_s: float = 1.0):
        self.ch = channel
        self.rt = None
        self.hb_interval_s = hb_interval_s
        self.metrics_interval_s = metrics_interval_s
        self._last_snap_t = 0.0
        self._hb_stop = threading.Event()
        self._busy = 0
        self._busy_lock = threading.Lock()

    def _reply(self, rid, result=None, exc: Exception | None = None):
        from .frame import ChannelClosed

        try:
            if exc is None:
                self.ch.send({"id": rid, "ok": True, "result": result})
            else:
                self.ch.send({
                    "id": rid, "ok": False,
                    "etype": type(exc).__name__,
                    "error": str(exc),
                    "retry_after_s": getattr(exc, "retry_after_s",
                                             None),
                })
        except ChannelClosed:
            pass  # parent died; the recv loop exits on its own

    # -- heartbeat ---------------------------------------------------------

    def _hb_loop(self):
        from .frame import ChannelClosed

        while not self._hb_stop.wait(self.hb_interval_s):
            rt = self.rt
            if rt is None:
                continue
            hb = {
                "t": time.time(),
                "pid": os.getpid(),
                "depth": self._busy,
                "serving": True,
                "slice": rt.idx,
                "wal_frontier": int(rt.version.wal_seq),
                "graph_version": int(rt.version.vid),
            }
            if obs.ENABLED:
                now = time.monotonic()
                if now - self._last_snap_t >= self.metrics_interval_s:
                    self._last_snap_t = now
                    try:
                        obs.count("serve.shard.hb_snapshots")
                        hb["metrics"] = obs.metrics_snapshot()
                    except Exception:
                        pass  # liveness outranks telemetry
            try:
                self.ch.send({"hb": hb})
            except ChannelClosed:
                return

    # -- boot --------------------------------------------------------------

    def _op_boot(self, m: dict) -> dict:
        from ..parallel.grid import Grid
        from .shard import SliceRuntime

        grid = Grid.make(1, 1)
        kinds = tuple(m["kinds"])
        common = dict(
            fsync=m.get("fsync"),
            max_iters=m.get("max_iters"),
            propagate_hops=int(m.get("propagate_hops", 2)),
            checkpoint_every=int(m.get("checkpoint_every", 0)),
            checkpoint_retain=int(m.get("checkpoint_retain", 2)),
        )
        if m.get("recover"):
            self.rt = SliceRuntime.recover(
                grid, int(m["idx"]), m["home"], kinds, **common
            )
        else:
            import numpy as np

            feats = m.get("features")
            self.rt = SliceRuntime.build(
                grid, int(m["idx"]), int(m["row0"]), int(m["row1"]),
                int(m["nrows"]), int(m["ncols"]),
                np.asarray(m["rows"]), np.asarray(m["cols"]),
                m.get("weights"), kinds,
                features=None, home=m.get("home"), **common,
            )
            if feats is not None:
                # the build path slices features by global row bounds;
                # the wire ships the PRE-SLICED slab — attach directly
                self.rt.attach_features(np.asarray(feats))
                if m.get("home"):
                    np.save(
                        os.path.join(m["home"], "features.npy"),
                        np.asarray(feats),
                    )
        warmed = {}
        if m.get("warmup", True):
            try:
                warmed = {
                    f"{k}/{w}": s
                    for (k, w), s in self.rt.warmup(
                        widths=m.get("warmup_widths")
                    ).items()
                }
            except Exception as e:
                warmed = {"error": repr(e)}
        self.hb_interval_s = float(
            m.get("hb_interval_s", self.hb_interval_s)
        )
        threading.Thread(
            target=self._hb_loop, name="combblas-shard-hb",
            daemon=True,
        ).start()
        return {
            "pid": os.getpid(),
            "slice": self.rt.idx,
            "rows": [self.rt.row0, self.rt.row1],
            "nnz": int(self.rt.version.nnz),
            "wal_seq": int(self.rt.version.wal_seq),
            "device_bytes": self.rt.device_bytes(),
            "warmed": warmed,
        }

    # -- dispatch ----------------------------------------------------------

    def dispatch(self, m: dict) -> bool:
        from .shard import dispatch_slice_op

        rid = m.get("id")
        op = m.get("op")
        try:
            if op == "boot":
                self._reply(rid, result=self._op_boot(m))
            elif op == "close":
                self._hb_stop.set()
                if self.rt is not None:
                    self.rt.close()
                self._reply(rid, result={"closed": True})
                return False
            else:
                with self._busy_lock:
                    self._busy += 1
                try:
                    self._reply(
                        rid, result=dispatch_slice_op(self.rt, op, m)
                    )
                finally:
                    with self._busy_lock:
                        self._busy -= 1
        except Exception as e:
            # a failed op fails ITS request, never the worker — the
            # router decides quarantine vs per-request handling
            if self.rt is not None:
                self.rt.worker_errors += 1
            self._reply(rid, exc=e)
        return True

    def run(self) -> None:
        import socket as _socket

        while True:
            try:
                m = self.ch.recv(timeout=1.0)
            except _socket.timeout:
                continue
            except Exception:
                break  # ChannelClosed / corrupt frame: parent gone
            if "hb" in m:
                continue
            if not self.dispatch(m):
                break
        self._hb_stop.set()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fd", type=int, required=True,
                    help="inherited socketpair fd (pass_fds)")
    ap.add_argument("--hb-interval-s", type=float, default=0.25)
    args = ap.parse_args(argv)
    sock = socket.socket(fileno=args.fd)
    from .frame import Channel

    worker = ShardWorker(
        Channel(sock, peer="parent"),
        hb_interval_s=args.hb_interval_s,
    )
    try:
        worker.run()
    except Exception:
        traceback.print_exc(file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
