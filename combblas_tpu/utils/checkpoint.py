"""Checkpoint / resume of distributed objects (≈ SURVEY §5 checkpointing).

The reference persists whole objects only (ParallelWriteMM /
ParallelBinaryWrite / SaveGathered, SpParMat.cpp:620-714,4128; vector
ParallelWrite) and rebuilds from files. Here distributed matrices/vectors
are pytrees of sharded arrays, so checkpointing is generic:

* ``save`` / ``load``: self-describing .npz + meta (host-gathered, portable,
  no extra deps) — the ParallelBinaryWrite analog.
* ``save_orbax`` / ``load_orbax``: orbax-backed sharded checkpoint for
  async, per-device-chunked persistence of big matrices (the
  "orbax-style async checkpoint of sharded arrays" called for by SURVEY §5).
"""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from ..parallel.grid import Grid
from ..parallel.spmat import SpParMat
from ..parallel.vec import DistVec


def _meta_of(obj) -> dict:
    if isinstance(obj, SpParMat):
        return {
            "kind": "SpParMat",
            "nrows": obj.nrows,
            "ncols": obj.ncols,
            "grid": [obj.grid.pr, obj.grid.pc],
        }
    if isinstance(obj, DistVec):
        meta = {
            "kind": "DistVec",
            "length": obj.length,
            "align": obj.align,
            "grid": [obj.grid.pr, obj.grid.pc],
        }
        # Persist the padding fill so cross-grid restore can rebuild blocks
        # whose padding slots fold correctly (e.g. -1 parents, -inf maxima).
        # Only the LAST element is read (always a padding slot when padding
        # exists) — not the whole vector.
        pa, L = obj.blocks.shape
        if pa * L > obj.length:
            meta["fill"] = np.asarray(obj.blocks[-1, -1]).item()
        return meta
    raise TypeError(f"unsupported checkpoint object: {type(obj)}")


def save(path: str, obj) -> None:
    """Write a .npz checkpoint (portable across grid shapes via re-shard on
    load when the device count differs)."""
    meta = _meta_of(obj)
    arrays = (
        {
            "rows": obj.rows, "cols": obj.cols, "vals": obj.vals,
            "nnz": obj.nnz,
        }
        if meta["kind"] == "SpParMat"
        else {"blocks": obj.blocks}
    )
    np.savez_compressed(
        path,
        __meta__=np.frombuffer(json.dumps(meta).encode(), np.uint8),
        **{k: np.asarray(v) for k, v in arrays.items()},
    )


def load(path: str, grid: Grid, fill=None):
    """Load a .npz checkpoint onto ``grid``.

    Same grid shape → direct device_put of the tile arrays. Different
    shape → rebuilt from global tuples (the reference's read-back path).
    """
    with np.load(path) as z:
        meta = json.loads(bytes(z["__meta__"]).decode())
        if meta["kind"] == "SpParMat":
            pr, pc = meta["grid"]
            if (pr, pc) == (grid.pr, grid.pc):
                sh = grid.tile_sharding()
                return SpParMat(
                    rows=jax.device_put(jnp.asarray(z["rows"]), sh),
                    cols=jax.device_put(jnp.asarray(z["cols"]), sh),
                    vals=jax.device_put(jnp.asarray(z["vals"]), sh),
                    nnz=jax.device_put(jnp.asarray(z["nnz"]), sh),
                    nrows=meta["nrows"], ncols=meta["ncols"], grid=grid,
                )
            # Re-shard via global tuples (grid-shape independent).
            rows, cols, vals = _npz_to_tuples(z, meta)
            return SpParMat.from_global_coo(
                grid, rows, cols, vals, meta["nrows"], meta["ncols"]
            )
        if meta["kind"] == "DistVec":
            return _restore_vec(np.asarray(z["blocks"]), meta, grid, fill)
        raise TypeError(meta["kind"])


def _restore_vec(blocks: np.ndarray, meta: dict, grid: Grid,
                 fill_override=None) -> DistVec:
    """Rebuild a DistVec preserving padding fill values.

    Matching grid shape → the saved padded blocks are device_put verbatim
    (padding slots keep whatever fill the vector was built with — reduce()
    folds padding, so 0-filling a -1/-inf-padded vector would corrupt it).
    Different shape → rebuild from the global values with the persisted
    fill (0 only when the saved vector had no padding slot to sample).
    """
    pr, pc = meta["grid"]
    pa = pr if meta["align"] == "row" else pc
    pa_now = grid.pr if meta["align"] == "row" else grid.pc
    if pa == pa_now and blocks.shape[0] == pa_now:
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..parallel.grid import COL_AXIS, ROW_AXIS

        sh = NamedSharding(
            grid.mesh, P(ROW_AXIS if meta["align"] == "row" else COL_AXIS)
        )
        return DistVec(
            blocks=jax.device_put(jnp.asarray(blocks), sh),
            length=meta["length"], align=meta["align"], grid=grid,
        )
    flat = blocks.reshape(-1)[: meta["length"]]
    fill = meta.get("fill", fill_override)
    if fill_override is not None:
        fill = fill_override
    if fill is None:
        import warnings

        warnings.warn(
            "cross-grid checkpoint restore: the saved vector had no padding "
            "slot to record its fill value; padding with 0. If the vector "
            "was built with a non-zero fill (e.g. -1 parents), pass "
            "fill=... to load()/load_orbax.",
            stacklevel=3,
        )
        fill = 0
    return DistVec.from_global(
        grid, flat, align=meta["align"],
        fill=np.asarray(fill, dtype=blocks.dtype),
    )


def _npz_to_tuples(z, meta):
    """Host: stored tile arrays → global (rows, cols, vals)."""
    pr, pc = meta["grid"]
    R, C, V, N = z["rows"], z["cols"], z["vals"], z["nnz"]
    lr = -(-meta["nrows"] // pr)
    lc = -(-meta["ncols"] // pc)
    rs, cs, vs = [], [], []
    for i in range(pr):
        for j in range(pc):
            m = R[i, j] < lr
            rs.append(R[i, j, m].astype(np.int64) + i * lr)
            cs.append(C[i, j, m].astype(np.int64) + j * lc)
            vs.append(V[i, j, m])
    return np.concatenate(rs), np.concatenate(cs), np.concatenate(vs)


# --- GraphVersion snapshots (round 14 — the serving fleet's warm start) ----

#: Schema tag of ``save_version`` snapshots; a mismatched tag is
#: refused at load (never guessed at).
VERSION_SCHEMA = "combblas_tpu.graph_version/v1"

#: The EllParMat fields of a GraphVersion, in a fixed serialization
#: order (absent twins are recorded as null bucket counts).
_VERSION_MATS = ("E", "E_weighted", "P_ell", "ET")


class SnapshotError(ValueError):
    """A snapshot that must not be loaded: corrupt, truncated, wrong
    schema, or wrong grid.  The message names the file — and
    ``load_latest_version`` treats any instance as "fall back to the
    previous retained snapshot" (round 16)."""


def snapshot_name(wal_seq: int) -> str:
    """Canonical snapshot file name for a version at WAL frontier
    ``wal_seq``: zero-padded so lexicographic order IS recovery order
    (``wal_seq`` is a global lineage — monotone across recoveries,
    unlike per-engine version ids)."""
    return f"ckpt-{int(wal_seq) + 1:012d}.npz"


def snapshot_seq(path: str) -> int:
    """The ``wal_seq`` stamp encoded in a snapshot's file name (the
    inverse of ``snapshot_name``; no file read)."""
    name = os.path.basename(path)
    return int(name[len("ckpt-"):-len(".npz")]) - 1


def list_snapshots(dirpath: str) -> list[str]:
    """Retained ``save_version`` snapshots in ``dirpath``, OLDEST
    first (the retention pruner drops a prefix; recovery walks the
    reverse)."""
    try:
        names = os.listdir(dirpath)
    except OSError:
        return []
    return sorted(
        os.path.join(dirpath, nm) for nm in names
        # a sibling process's in-flight atomic write (``*.npz.tmp``)
        # is not a snapshot — never list it as a candidate (round 17:
        # multi-process fleets checkpoint concurrently)
        if nm.startswith("ckpt-") and nm.endswith(".npz")
        and ".tmp" not in nm
    )


def load_latest_version(dirpath: str, grid, *, writable: bool = True):
    """The newest LOADABLE snapshot in ``dirpath`` as ``(version,
    path)`` — a corrupt/truncated newest file (the crash-mid-write
    artifact atomic replace makes rare, or disk damage) falls back to
    the previous retained snapshot with a warning naming the bad file.

    Concurrent-sibling tolerance (round 17, the process fleet): a
    file that VANISHES between listing and open (a sibling's
    retention pruner unlinked it, or its ``os.replace`` superseded
    it) is not corruption — it is skipped silently, and if nothing in
    the stale listing loads the directory is re-listed ONCE (the
    sibling that pruned our candidate also wrote a newer one).
    Raises ``dynamic.wal.RecoveryError`` when no candidate loads."""
    import warnings

    candidates = []
    errors = []
    for attempt in (0, 1):
        candidates = list_snapshots(dirpath)
        vanished = 0
        for path in reversed(candidates):
            try:
                return load_version(path, grid, writable=writable), path
            except FileNotFoundError:
                # pruned/replaced under us: never a SnapshotError —
                # no rejected-counter, no warning, just the next
                # candidate (and one fresh listing below)
                vanished += 1
                continue
            except SnapshotError as e:
                errors.append(str(e))
                from .. import obs

                obs.count("serve.recovery.snapshot_rejected")
                warnings.warn(
                    f"skipping unloadable snapshot (falling back to "
                    f"the previous retained one): {e}",
                    stacklevel=2,
                )
        if vanished == 0:
            break  # a re-list cannot surface anything new
    from ..dynamic.wal import RecoveryError

    raise RecoveryError(
        f"no loadable GraphVersion snapshot in {dirpath!r} "
        f"({len(candidates)} candidate(s)"
        + (f"; errors: {errors}" if errors else "")
        + ")"
    )


def save_version(path: str, version, *, extra_meta: dict | None = None) -> None:
    """Snapshot a serve ``GraphVersion`` to one self-describing .npz —
    the warm-start half of the replicated fleet (docs/serving.md
    "Multi-tenant pool & fleet").

    What makes this different from re-running ``from_coo`` on the
    replica: the BUCKET ARRAYS are persisted exactly as built —
    per-class cols/vals/rowids including the headroom-resolved padding
    rows — so ``load_version`` re-uploads bit-identical shapes with
    ``EllParMat.from_host_buckets`` (one ``device_put`` per array, no
    dedup sort, no host bucket pass) and a warmed plan cache keeps
    every compiled executable: ZERO retraces after ``swap()``, the
    regression-tested guarantee.  The BFS plan's CSC companion
    (``version.csc`` and whether it is current) is an operand like the
    buckets and is persisted the same way; a snapshot without one
    (written before there was one) loads, and its engine serves BFS
    with a stand-in marked not-current.  The host COO/weights ride along when
    the version retained them (``keep_coo=True``), so a restored
    replica can still serve the write lane.

    Round 16 (durability): the write is ATOMIC — the .npz lands in a
    sibling tmp file and ``os.replace``s into place, so a crash
    mid-save leaves the previous snapshot intact, never a truncated
    one under the real name — and the version's WAL position
    (``version.wal_seq``) is stamped into the meta: recovery replays
    exactly the log suffix this snapshot does not already contain.

    ``extra_meta`` (round 20, sharded serving): an arbitrary
    JSON-able dict stored under ``meta["extra"]`` and surfaced as
    ``version.extra_meta`` on load — slab snapshots use it to be
    SELF-DESCRIBING (``{"shard": {idx, row0, row1, ...}}``), so
    slice recovery needs only the slice's home directory, never the
    service manifest.
    """
    import time

    from .. import obs

    t0 = time.perf_counter()
    meta = {
        "kind": "GraphVersion",
        "v": VERSION_SCHEMA,
        "nrows": int(version.nrows),
        "ncols": int(version.ncols),
        "nnz": int(version.nnz),
        "feat_dim": int(version.feat_dim),
        "headroom": version.headroom,
        "wal_seq": int(getattr(version, "wal_seq", -1)),
        "grid": [version.E.grid.pr, version.E.grid.pc],
        "mats": {},
    }
    if extra_meta is not None:
        meta["extra"] = extra_meta
    arrays: dict = {
        "deg": np.asarray(version.deg),
    }
    if version.outdeg is not None:
        arrays["outdeg"] = np.asarray(version.outdeg)
    for nm in _VERSION_MATS:
        M = getattr(version, nm)
        if M is None:
            meta["mats"][nm] = None
            continue
        meta["mats"][nm] = {
            "nbuckets": len(M.buckets),
            "nrows": int(M.nrows),
            "ncols": int(M.ncols),
        }
        for i, (bc, bv, br) in enumerate(M.buckets):
            arrays[f"{nm}.{i}.c"] = np.asarray(jax.device_get(bc))
            arrays[f"{nm}.{i}.v"] = np.asarray(jax.device_get(bv))
            arrays[f"{nm}.{i}.r"] = np.asarray(jax.device_get(br))
    if version.dangling is not None:
        arrays["dangling"] = np.asarray(
            jax.device_get(version.dangling.blocks)
        )
    if version.X is not None:
        arrays["X"] = np.asarray(jax.device_get(version.X.blocks))
    if version.csc is not None:
        # the BFS plan's operand, as built: a boot only uploads it
        indptr, rowidx = version.csc
        arrays["csc.indptr"] = np.asarray(jax.device_get(indptr))
        arrays["csc.rowidx"] = np.asarray(jax.device_get(rowidx))
        meta["csc_current"] = bool(version.csc_current)
    if version.host_coo is not None:
        rows, cols, _nc = version.host_coo
        arrays["coo_rows"] = np.asarray(rows)
        arrays["coo_cols"] = np.asarray(cols)
        if version.host_weights is not None:
            arrays["coo_weights"] = np.asarray(version.host_weights)
    # atomic: write a sibling tmp (same filesystem — os.replace must
    # not cross devices) through a FILE OBJECT so np.savez cannot
    # append its own .npz suffix, fsync, then replace into place
    tmp = path + ".tmp"
    try:
        with open(tmp, "wb") as f:
            np.savez_compressed(
                f,
                __meta__=np.frombuffer(
                    json.dumps(meta).encode(), np.uint8
                ),
                **arrays,
            )
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    obs.observe("serve.checkpoint.save_s", time.perf_counter() - t0)


def load_version(path: str, grid: Grid, *, writable: bool = True):
    """Restore a ``save_version`` snapshot onto ``grid`` as a
    ``GraphVersion`` ready for ``GraphEngine(grid, version=...)`` or
    ``engine.swap()``.

    ``writable=False`` skips retaining the host bucket arrays the
    lazy merge-state derivation needs (round 16): a READ-ONLY replica
    loading a shared snapshot must not pin an O(nnz) host copy of the
    graph structure it will never merge into — only the write-lane
    owner (the fleet's home) loads writable.

    Same grid shape ONLY (the fleet's replicas share one mesh layout;
    cross-shape restore would re-bucket and forfeit the bit-identical
    shapes the zero-retrace guarantee rests on — rebuild from COO for
    that).  Uploads are one ``device_put`` per persisted array.

    A corrupt or truncated file is REFUSED with a ``SnapshotError``
    naming it (round 16) — never half-loaded; ``load_latest_version``
    turns that refusal into a fallback to the previous retained
    snapshot.
    """
    try:
        return _load_version(path, grid, writable)
    except SnapshotError:
        raise  # already diagnostic (schema / grid mismatch)
    except FileNotFoundError:
        # the file vanished between listing and open (a sibling's
        # pruner or os.replace) — NOT corruption: propagate so
        # load_latest_version retries over a fresh listing instead
        # of mis-counting a spurious SnapshotError
        raise
    except Exception as e:
        raise SnapshotError(
            f"refusing corrupt or truncated GraphVersion snapshot "
            f"{path!r}: {type(e).__name__}: {e}"
        ) from e


def _load_version(path: str, grid: Grid, writable: bool = True):
    import time

    from jax.sharding import NamedSharding, PartitionSpec as P

    from .. import obs
    from ..parallel.ellmat import EllParMat, upload_csc_companion
    from ..parallel.grid import COL_AXIS, ROW_AXIS
    from ..parallel.vec import DistMultiVec
    from ..serve.engine import GraphVersion

    t0 = time.perf_counter()
    # one span a boundary the restore crosses, on the clock a boot is
    # timed on: ``read`` (the file to host arrays), ``upload`` (host to
    # device), ``companion`` (the BFS plan's CSC operand)
    with obs.span("serve.restore", path=path) as restore:
        with obs.span("read"):
            with np.load(path) as z:
                meta = json.loads(bytes(z["__meta__"]).decode())
                if meta.get("v") != VERSION_SCHEMA:
                    raise SnapshotError(
                        f"{path!r} is not a GraphVersion snapshot (schema "
                        f"{meta.get('v')!r} != {VERSION_SCHEMA!r})"
                    )
                pr, pc = meta["grid"]
                if (pr, pc) != (grid.pr, grid.pc):
                    raise SnapshotError(
                        f"snapshot was taken on a {pr}x{pc} grid; "
                        f"load_version restores onto the SAME grid shape "
                        f"(got {grid.pr}x{grid.pc}) — rebuild from COO to "
                        "re-shard"
                    )
                # every member, decompressed once
                host = {k: z[k] for k in z.files if k != "__meta__"}
        host_mats = {}  # host (bc, bv, br) triples: the merge-state
        #                 derivation below needs them pre-upload
        for nm in _VERSION_MATS:
            info = meta["mats"].get(nm)
            if info is not None:
                host_mats[nm] = [
                    (host[f"{nm}.{i}.c"], host[f"{nm}.{i}.v"],
                     host[f"{nm}.{i}.r"])
                    for i in range(info["nbuckets"])
                ]
        with obs.span("upload") as upload:
            mats = {
                nm: EllParMat.from_host_buckets(
                    grid, host_mats[nm], meta["mats"][nm]["nrows"],
                    meta["mats"][nm]["ncols"],
                ) if nm in host_mats else None
                for nm in _VERSION_MATS
            }
            dangling = None
            if "dangling" in host:
                dangling = DistVec(
                    blocks=jax.device_put(
                        jnp.asarray(host["dangling"]),
                        NamedSharding(grid.mesh, P(COL_AXIS)),
                    ),
                    length=meta["ncols"], align="col", grid=grid,
                )
            X = None
            if "X" in host:
                X = DistMultiVec(
                    blocks=jax.device_put(
                        jnp.asarray(host["X"]),
                        NamedSharding(grid.mesh, P(ROW_AXIS)),
                    ),
                    length=meta["ncols"], align="row", grid=grid,
                )
            upload.sync_on((mats, dangling, X))
        csc = None
        if "csc.indptr" in host:
            with obs.span("companion") as companion:
                csc = upload_csc_companion(
                    grid, host["csc.indptr"], host["csc.rowidx"]
                )
                companion.sync_on(csc)
        host_coo = None
        host_weights = None
        if "coo_rows" in host:
            host_coo = (host["coo_rows"], host["coo_cols"], meta["ncols"])
            host_weights = host.get("coo_weights")
        version = GraphVersion(
            nrows=meta["nrows"], ncols=meta["ncols"], nnz=meta["nnz"],
            E=mats["E"],
            deg=host["deg"],
            outdeg=host.get("outdeg"),
            E_weighted=mats["E_weighted"],
            P_ell=mats["P_ell"],
            dangling=dangling,
            ET=mats["ET"],
            # a snapshot from before the companion has none: the engine
            # serves it with a stand-in marked not-current (every level
            # swept) until ``csc_companion()`` can rebuild one
            csc=csc,
            csc_current=bool(meta.get("csc_current", csc is not None)),
            host_coo=host_coo,
            host_weights=host_weights,
            X=X,
            feat_dim=meta["feat_dim"],
            headroom=meta["headroom"],
            wal_seq=int(meta.get("wal_seq", -1)),
        )
        # self-description channel (round 20): slab snapshots carry a
        # shard descriptor here; absent for whole-graph snapshots
        version.extra_meta = meta.get("extra")
        if host_coo is not None and writable:
            # round 16: the merge state must describe the RESTORED
            # bucket layout, sticky slots included — a later
            # apply_delta that bootstrapped a fresh host_build from
            # the COO would patch against the wrong slot map and
            # corrupt the graph (snapshots of incrementally merged
            # versions drift from fresh builds by design).  Derived
            # LAZILY (apply_delta consumes ``dyn_source`` on the
            # first merge): read-only replicas loading the same
            # snapshot must not each pay the O(nnz log nnz) key sort
            # and bucket copies — only the write-lane owner merges.
            e_buckets = host_mats["E"]
            t_buckets = host_mats.get("ET")
            deg_host = host["deg"]
            outdeg_host = host.get("outdeg")

            def _dyn_source():
                from ..dynamic.merge import state_from_host_buckets

                return state_from_host_buckets(
                    grid, e_buckets, t_buckets, host_coo,
                    host_weights, deg_host, outdeg_host,
                )

            version.dyn_source = _dyn_source
        if obs.ENABLED:
            restore.annotate(
                file_bytes=os.path.getsize(path),
                host_bytes=sum(int(a.nbytes) for a in host.values()),
                device_bytes=version.device_bytes(),
            )
    obs.observe("serve.checkpoint.load_s", time.perf_counter() - t0)
    return version


# --- orbax (async, sharded) -------------------------------------------------


def save_orbax(path: str, obj) -> None:
    """Sharded async-capable checkpoint via orbax (big-matrix path).

    Saves a plain dict of the object's sharded arrays (orbax persists each
    array per-device-chunked) + a small JSON meta sidecar.
    """
    import orbax.checkpoint as ocp

    path = os.path.abspath(path)
    meta = _meta_of(obj)
    state = (
        {"rows": obj.rows, "cols": obj.cols, "vals": obj.vals, "nnz": obj.nnz}
        if meta["kind"] == "SpParMat"
        else {"blocks": obj.blocks}
    )
    ckptr = ocp.StandardCheckpointer()
    ckptr.save(path, state)
    ckptr.wait_until_finished()
    with open(os.path.join(path, "cbtpu_meta.json"), "w") as f:
        json.dump(meta, f)


def load_orbax(path: str, grid: Grid, fill=None):
    import orbax.checkpoint as ocp

    path = os.path.abspath(path)
    with open(os.path.join(path, "cbtpu_meta.json")) as f:
        meta = json.load(f)
    ckptr = ocp.StandardCheckpointer()
    state = ckptr.restore(path)
    if meta["kind"] == "SpParMat":
        sh = grid.tile_sharding()
        assert meta["grid"] == [grid.pr, grid.pc], (
            "orbax path restores onto the same grid shape; use save/load "
            "(.npz) for cross-shape restore"
        )
        return SpParMat(
            rows=jax.device_put(jnp.asarray(state["rows"]), sh),
            cols=jax.device_put(jnp.asarray(state["cols"]), sh),
            vals=jax.device_put(jnp.asarray(state["vals"]), sh),
            nnz=jax.device_put(jnp.asarray(state["nnz"]), sh),
            nrows=meta["nrows"], ncols=meta["ncols"], grid=grid,
        )
    if meta["kind"] == "DistVec":
        return _restore_vec(np.asarray(state["blocks"]), meta, grid, fill)
    raise TypeError(meta["kind"])
