"""Single-experiment BFS/SpMV instrumentation probe.

Usage:  python benchmarks/instrument.py EXPERIMENT [ARGS...]

Each invocation runs ONE experiment in a fresh process and prints one JSON
line: a probe gets exactly one timed section, closed by a single scalar
D2H.  (The figures quoted below were measured in rounds 2-5 on a machine
that is gone — where a readback degraded later launches and
block_until_ready was not a barrier; not re-measured.)

Experiments (scale/edgefactor via BENCH_SCALE / BENCH_EDGEFACTOR):

  chain K R        R launches of a K-level fused BFS-step loop (lax.fori_loop,
                   no early exit — dense-regime level cost is frontier-
                   independent). Varying (K, R) at constant K*R separates
                   per-launch dispatch overhead from per-level kernel time.
  kernel VARIANT R one launch, R chained iterations of a local-kernel piece:
                   full     = gather + semiring fold + row scatter (the real
                              ELL local SpMV, level-equivalent minus realign)
                   fold     = gather + fold only (scatter replaced by a sum)
                   scatter  = row scatter only (folded values precomputed)
  membw MB R       the array is a jit-closure constant, embedded in the
                   compiled program (a >~100 MB body was refused by
                   the round-2 toolchain; not re-measured). Kept for the
                   record; use membw2.
  membw2 MB R      HBM read-bandwidth reference; array passed as an
                   argument (resident), R chained sums in one launch.
  args MB R        R launches of a trivial kernel over an MB-sized resident
                   argument: separates fixed dispatch cost from any
                   per-launch argument streaming (measured: ~105 ms fixed,
                   no streaming).
  gatherw W R      one launch, R iterations of the full bucket gather with
                   W payload lanes per index ([lc+1, W] table): the
                   multi-root batching question (measured: W=8 costs the
                   same as W=1; W=64 costs ~2x).
  pallas_gather R [W]  Mosaic 2D-gather feasibility probe (take_along_axis
                   from a VMEM table). NOTE arg order: R first, then W
                   (default 128). Currently fails lowering: Mosaic's
                   dynamic-gather is register-block-local, not a
                   large-table gather.

These are the "which phase is slow" numbers VERDICT r1 asked for; results
are committed to benchmarks/results/instrument_r2.json by the driver.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

SCALE = int(os.environ.get("BENCH_SCALE", "19"))
EDGEFACTOR = int(os.environ.get("BENCH_EDGEFACTOR", "16"))


def build_graph():
    import numpy as np

    from combblas_tpu.utils.rmat import rmat_symmetric_coo_host

    n = 1 << SCALE
    rows, cols = rmat_symmetric_coo_host(42, SCALE, EDGEFACTOR)
    key = rows * np.int64(n) + cols
    uniq = np.unique(key)
    rows_u = (uniq // n).astype(np.int64)
    cols_u = (uniq % n).astype(np.int64)
    return rows_u, cols_u, n


def upload_ell():
    import numpy as np

    from combblas_tpu.parallel.ellmat import EllParMat
    from combblas_tpu.parallel.grid import Grid

    rows_u, cols_u, n = build_graph()
    grid = Grid.make(1, 1)
    E = EllParMat.from_host_coo(
        grid, rows_u, cols_u, np.ones(len(rows_u), np.float32), n, n
    )
    return E, n, len(rows_u)


def ell_bytes(E) -> int:
    """HBM bytes read per full ELL SpMV (cols + vals once, ignoring the
    x-gather reuse and y writes — a lower bound on traffic)."""
    total = 0
    for bc, bv, br in E.buckets:
        total += bc.size * 4 + bv.size * 4 + br.size * 4
    return total


def timed(launch_fn, n_launches: int, sync_fn):
    """Run launch_fn() n_launches times, close with sync_fn() (one D2H)."""
    t0 = time.perf_counter()
    out = None
    for _ in range(n_launches):
        out = launch_fn(out)
    sync_fn(out)
    return time.perf_counter() - t0


def exp_chain(K: int, R: int):
    import jax
    import jax.numpy as jnp
    from jax import lax

    from combblas_tpu.parallel.ellmat import dist_spmv_ell_masked
    from combblas_tpu.parallel.vec import DistVec
    from combblas_tpu.semiring import SELECT2ND_MAX

    E, n, nnz = upload_ell()
    grid = E.grid
    lr = grid.local_rows(n)
    row_gids = jnp.arange(lr, dtype=jnp.int32).reshape(1, lr)

    def mk(b, align):
        return DistVec(blocks=b, length=n, align=align, grid=grid)

    @jax.jit
    def chainK(parents, x):
        def body(_, st):
            parents, x = st
            unvisited = mk(parents < 0, "row")
            y = dist_spmv_ell_masked(SELECT2ND_MAX, E, mk(x, "col"), unvisited)
            new = (y.blocks >= 0) & (parents < 0)
            parents = jnp.where(new, y.blocks, parents)
            x = mk(jnp.where(new, row_gids, -1), "row").realign("col").blocks
            return parents, x

        return lax.fori_loop(0, K, body, (parents, x))

    parents0 = jnp.where(row_gids == 0, 0, -1).astype(jnp.int32)
    x0 = jnp.where(row_gids == 0, 0, -1).astype(jnp.int32)
    # warmup compile
    p, x = chainK(parents0, x0)
    jax.block_until_ready((p, x))
    time.sleep(3.0)

    def launch(prev):
        if prev is None:
            prev = (parents0, x0)
        return chainK(*prev)

    dt = timed(launch, R, lambda out: int(jax.device_get(out[0][0, 0])))
    return {
        "experiment": f"chain K={K} R={R}",
        "levels": K * R,
        "launches": R,
        "dt_s": round(dt, 4),
        "ms_per_level": round(dt / (K * R) * 1e3, 3),
        "nnz": nnz,
        "ell_bytes_per_level": ell_bytes(E),
        "achieved_GBps": round(ell_bytes(E) * K * R / dt / 1e9, 2),
    }


def exp_kernel(variant: str, R: int):
    import jax
    import jax.numpy as jnp
    from jax import lax

    from combblas_tpu.parallel.ellmat import (
        _bucket_fold,
        _ell_local_spmv,
        _scatter_rows,
    )
    from combblas_tpu.semiring import SELECT2ND_MAX

    E, n, nnz = upload_ell()
    sr = SELECT2ND_MAX
    lr = E.local_rows
    lc = E.local_cols
    # strip the [pr, pc] tile dims — single-device local arrays
    buckets = [(bc[0, 0], bv[0, 0].astype(jnp.int32), br[0, 0]) for bc, bv, br in E.buckets]
    nb_tot = sum(b[0].shape[0] for b in buckets)

    if variant == "full":

        @jax.jit
        def run(x):
            def body(_, x):
                y = _ell_local_spmv(sr, buckets, x, lr, lc)
                return jnp.where(y >= 0, y, x)  # data dependence

            return lax.fori_loop(0, R, body, x)

    elif variant == "fold":

        @jax.jit
        def run(x):
            def body(_, x):
                zero = sr.zero(x.dtype)
                xpad = jnp.concatenate([x, zero[None]])
                acc = jnp.int32(0)
                for bc, bv, br in buckets:
                    g = xpad[jnp.minimum(bc, lc)]
                    prods = sr.mul(bv, g)
                    yb = _bucket_fold(sr, prods)
                    acc = acc + jnp.sum(yb)
                return x.at[0].set(acc)  # data dependence, no scatter

            return lax.fori_loop(0, R, body, x)

    elif variant == "scatter":
        ybs = [jnp.zeros((b[0].shape[0],), jnp.int32) for b in buckets]

        @jax.jit
        def run(x):
            def body(_, x):
                y = jnp.full((lr,), sr.zero(jnp.int32), jnp.int32)
                for (bc, bv, br), yb in zip(buckets, ybs):
                    y = _scatter_rows(sr, y, br, yb + x[0])
                return jnp.maximum(y, x)

            return lax.fori_loop(0, R, body, x)

    else:
        raise SystemExit(f"unknown kernel variant {variant}")

    x0 = jnp.full((lc,), -1, jnp.int32).at[0].set(0)
    out = run(x0)
    jax.block_until_ready(out)
    time.sleep(3.0)

    dt = timed(lambda prev: run(x0 if prev is None else prev), 1,
               lambda out: int(jax.device_get(out[0])))
    return {
        "experiment": f"kernel {variant} R={R}",
        "iters": R,
        "dt_s": round(dt, 4),
        "ms_per_iter": round(dt / R * 1e3, 3),
        "nnz": nnz,
        "n_buckets": len(buckets),
        "bucket_rows_total": int(nb_tot),
        "ell_bytes": ell_bytes(E),
        "achieved_GBps": round(ell_bytes(E) * R / dt / 1e9, 2),
    }


def exp_membw(mb: int, R: int):
    import jax
    import jax.numpy as jnp
    from jax import lax

    n = mb * 1024 * 1024 // 4
    a = jnp.arange(n, dtype=jnp.float32)

    @jax.jit
    def run(s):
        def body(_, s):
            return s + jnp.sum(a + s)

        return lax.fori_loop(0, R, body, s)

    out = run(jnp.float32(0))
    jax.block_until_ready(out)
    time.sleep(3.0)
    dt = timed(lambda prev: run(jnp.float32(0)), 1,
               lambda out: float(jax.device_get(out)))
    return {
        "experiment": f"membw {mb}MB R={R}",
        "dt_s": round(dt, 4),
        "ms_per_iter": round(dt / R * 1e3, 3),
        "achieved_GBps": round(mb / 1024 * R / dt, 1),
    }


def exp_scatter(variant: str, n_m: float, t_m: float, R: int):
    """Scatter/gather throughput probe — the SpGEMM-redesign question.

    N million values are scattered into a T-million-cell table R times in
    one launch. Variants:
      add         .at[idx].add, random unsorted indices
      min         .at[idx].min int32, random unsorted
      addsort     .at[idx].add, SORTED indices + indices_are_sorted hint
      segsum      jax.ops.segment_sum, sorted ids, NO hint (today's
                  segment_reduce path)
      segsumhint  segment_sum, sorted ids, indices_are_sorted=True
      gather      x[idx] baseline (known ~133M idx/s)
    Distinguishes the two contradictory round-2 scatter numbers (79 ms for
    22.6M row-scatter vs '0.2us/element') and prices the bucketed-
    accumulation SpGEMM before building it.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    N = int(n_m * 1e6)
    T = int(t_m * 1e6)
    rng = np.random.default_rng(0)
    idx_np = rng.integers(0, T, size=N, dtype=np.int32)
    if variant in ("addsort", "segsum", "segsumhint"):
        idx_np = np.sort(idx_np)
    idx = jax.device_put(jnp.asarray(idx_np))
    vals = jax.device_put(jnp.ones((N,), jnp.float32))

    if variant == "add":

        def op(idx, vals, s):
            t = jnp.zeros((T,), jnp.float32)
            return t.at[idx].add(vals + s * 1e-30, mode="drop")

    elif variant == "min":

        def op(idx, vals, s):
            t = jnp.full((T,), jnp.int32(2**31 - 1))
            return t.at[idx].min(
                jnp.arange(N, dtype=jnp.int32) + (s * 0).astype(jnp.int32),
                mode="drop",
            ).astype(jnp.float32)

    elif variant == "addsort":

        def op(idx, vals, s):
            t = jnp.zeros((T,), jnp.float32)
            return t.at[idx].add(
                vals + s * 1e-30, mode="drop", indices_are_sorted=True
            )

    elif variant == "segsum":

        def op(idx, vals, s):
            return jax.ops.segment_sum(
                vals + s * 1e-30, idx, num_segments=T
            )

    elif variant == "segsumhint":

        def op(idx, vals, s):
            return jax.ops.segment_sum(
                vals + s * 1e-30, idx, num_segments=T,
                indices_are_sorted=True,
            )

    elif variant == "gather":

        def op(idx, vals, s):
            x = vals + s * 1e-30
            pad = jnp.zeros((T,), jnp.float32).at[: min(N, T)].set(x[: min(N, T)])
            return pad[idx][:T]

    else:
        raise SystemExit(f"unknown scatter variant {variant}")

    @jax.jit
    def run(idx, vals):
        def body(_, s):
            out = op(idx, vals, s)
            return out[0] + s * 1e-30

        return lax.fori_loop(0, R, body, jnp.float32(0))

    out = run(idx, vals)
    jax.block_until_ready(out)
    time.sleep(3.0)
    dt = timed(lambda prev: run(idx, vals), 1,
               lambda out: float(jax.device_get(out)))
    return {
        "experiment": f"scatter {variant} N={n_m}M T={t_m}M R={R}",
        "dt_s": round(dt, 4),
        "ms_per_iter": round(dt / R * 1e3, 3),
        "Melem_per_s": round(N * R / dt / 1e6, 1),
        "ns_per_elem": round(dt / (N * R) * 1e9, 2),
    }


def _build_local_esc(scale: int, ef: int = 8):
    """Local A (SpTuples, row-sorted) + A as CSR + exact capacities for A^2."""
    import jax
    import numpy as np

    from combblas_tpu.ops.compressed import CSR
    from combblas_tpu.ops.tuples import SpTuples
    from combblas_tpu.utils.rmat import rmat_symmetric_coo_host

    n = 1 << scale
    rows, cols = rmat_symmetric_coo_host(5, scale, ef)
    key = rows * np.int64(n) + cols
    uniq = np.unique(key)
    ru = (uniq // n).astype(np.int64)
    cu = (uniq % n).astype(np.int64)
    nnz = len(ru)
    # exact flops on host: sum over entries of rowlen[col]
    rowlen = np.bincount(ru, minlength=n)
    flops = int(rowlen[cu].sum())
    a = SpTuples.from_coo(ru, cu, np.ones(nnz, np.float32), n, n)
    csr = CSR.from_tuples(a, assume_sorted=True)
    return a, csr, n, nnz, flops


def exp_escparts(variant: str, scale: int, R: int):
    """Decompose local ESC SpGEMM (A^2, rmat ef8) phase by phase:
      expand / sort / segsum / compact / full — each timed alone in one
      launch chain. Identifies which of the 26.6 s at scale 14 is sort,
      which is the segment scatter, which is compaction scatters.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    from combblas_tpu import PLUS_TIMES
    from combblas_tpu.ops.spgemm import expand
    from combblas_tpu.ops.tuples import SpTuples

    sr = PLUS_TIMES
    a, csr, n, nnz, flops = _build_local_esc(scale)
    fcap = flops  # exact
    ocap = flops  # generous; compact clamps

    exp_t = None
    if variant in ("sort", "segsum", "compact"):
        # materialize the expansion once (untimed) as the phase input
        exp_t = jax.jit(
            lambda a, c: expand(sr, a, c, fcap), static_argnums=()
        )(a, csr)
        jax.block_until_ready(exp_t.vals)

    if variant == "expand":

        @jax.jit
        def run(a, csr):
            def body(_, s):
                import dataclasses

                t = expand(
                    sr,
                    dataclasses.replace(a, vals=a.vals + s * 1e-30),
                    csr,
                    fcap,
                )
                return t.vals[0] + s * 1e-30

            return lax.fori_loop(0, R, body, jnp.float32(0))

        args = (a, csr)
    elif variant == "sort":

        @jax.jit
        def run(t):
            def body(_, s):
                import dataclasses

                st = dataclasses.replace(t, vals=t.vals + s * 1e-30)
                st = st.sort_rowmajor()
                return st.vals[0] + s * 1e-30

            return lax.fori_loop(0, R, body, jnp.float32(0))

        args = (exp_t,)
    elif variant == "segsum":
        # sorted expansion -> the segment fold + scatters of compact_counted
        # WITHOUT the sort (assume_sorted) — isolates the post-sort phases
        exp_t = jax.jit(lambda t: t.sort_rowmajor())(exp_t)
        jax.block_until_ready(exp_t.vals)

        @jax.jit
        def run(t):
            def body(_, s):
                import dataclasses

                st = dataclasses.replace(t, vals=t.vals + s * 1e-30)
                out, _ = st.compact_counted(
                    sr, capacity=ocap, assume_sorted=True
                )
                return out.vals[0] + s * 1e-30

            return lax.fori_loop(0, R, body, jnp.float32(0))

        args = (exp_t,)
    elif variant == "compact":

        @jax.jit
        def run(t):
            def body(_, s):
                import dataclasses

                st = dataclasses.replace(t, vals=t.vals + s * 1e-30)
                out, _ = st.compact_counted(sr, capacity=ocap)
                return out.vals[0] + s * 1e-30

            return lax.fori_loop(0, R, body, jnp.float32(0))

        args = (exp_t,)
    elif variant == "full":

        @jax.jit
        def run(a, csr):
            def body(_, s):
                import dataclasses

                from combblas_tpu.ops.spgemm import local_spgemm

                aa = dataclasses.replace(a, vals=a.vals + s * 1e-30)
                C = local_spgemm(
                    sr, aa, csr, flop_capacity=fcap, out_capacity=ocap
                )
                return C.vals[0] + s * 1e-30

            return lax.fori_loop(0, R, body, jnp.float32(0))

        args = (a, csr)
    else:
        raise SystemExit(f"unknown escparts variant {variant}")

    out = run(*args)
    jax.block_until_ready(out)
    time.sleep(3.0)
    dt = timed(lambda prev: run(*args), 1,
               lambda out: float(jax.device_get(out)))
    return {
        "experiment": f"escparts {variant} scale={scale} R={R}",
        "dt_s": round(dt, 4),
        "s_per_iter": round(dt / R, 3),
        "nnz": nnz,
        "flops": flops,
        "MFLOPs": round(flops * 2 * R / dt / 1e6, 2),
    }


def main():
    exp = sys.argv[1]
    if exp == "chain":
        out = exp_chain(int(sys.argv[2]), int(sys.argv[3]))
    elif exp == "kernel":
        out = exp_kernel(sys.argv[2], int(sys.argv[3]))
    elif exp == "membw":
        out = exp_membw(int(sys.argv[2]), int(sys.argv[3]))
    elif exp == "membw2":
        out = exp_membw2(int(sys.argv[2]), int(sys.argv[3]))
    elif exp == "args":
        out = exp_args(int(sys.argv[2]), int(sys.argv[3]))
    elif exp == "gatherw":
        out = exp_gatherw(int(sys.argv[2]), int(sys.argv[3]))
    elif exp == "pallas_gather":
        out = exp_pallas_gather(int(sys.argv[2]),
                                int(sys.argv[3]) if len(sys.argv) > 3 else 128)
    elif exp == "sort":
        out = exp_sort(int(sys.argv[2]), int(sys.argv[3]))
    elif exp == "argsort":
        out = exp_argsort(int(sys.argv[2]), int(sys.argv[3]))
    elif exp == "scatter":
        out = exp_scatter(
            sys.argv[2], float(sys.argv[3]), float(sys.argv[4]),
            int(sys.argv[5]),
        )
    elif exp == "escparts":
        out = exp_escparts(sys.argv[2], int(sys.argv[3]), int(sys.argv[4]))
    else:
        raise SystemExit(f"unknown experiment {exp}")
    out["scale"] = SCALE
    print(json.dumps(out))




def exp_args(mb: int, R: int):
    """Trivial kernel over an MB-sized resident argument, R launches:
    if per-launch time scales with MB, arguments are streamed per
    launch (the fixed-cost hypothesis for the BFS gap)."""
    import jax
    import jax.numpy as jnp

    n = mb * 1024 * 1024 // 4
    a = jax.device_put(jnp.ones((n,), jnp.float32))

    @jax.jit
    def run(a, s):
        return a[:8].sum() + s

    out = run(a, jnp.float32(0))
    jax.block_until_ready(out)
    time.sleep(3.0)
    dt = timed(lambda prev: run(a, prev if prev is not None else jnp.float32(0)),
               R, lambda out: float(jax.device_get(out)))
    return {
        "experiment": f"args {mb}MB R={R}",
        "dt_s": round(dt, 4),
        "ms_per_launch": round(dt / R * 1e3, 3),
        "implied_stream_MBps": round(mb * R / dt, 1),
    }


def exp_membw2(mb: int, R: int):
    """HBM bandwidth: array passed as ARGUMENT (not closure constant —
    closures get embedded in the compiled program)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    n = mb * 1024 * 1024 // 4
    a = jax.device_put(jnp.ones((n,), jnp.float32))

    @jax.jit
    def run(a, s):
        def body(_, s):
            return s + jnp.sum(a * (1.0 + s * 1e-30))
        return lax.fori_loop(0, R, body, s)

    out = run(a, jnp.float32(0))
    jax.block_until_ready(out)
    time.sleep(3.0)
    dt = timed(lambda prev: run(a, jnp.float32(0)), 1,
               lambda out: float(jax.device_get(out)))
    return {
        "experiment": f"membw2 {mb}MB R={R}",
        "dt_s": round(dt, 4),
        "ms_per_iter": round(dt / R * 1e3, 3),
        "achieved_GBps": round(mb / 1024 * R / dt, 1),
    }


def exp_gatherw(W: int, R: int):
    """Width-batched gather: g = x2[idx] where x2 is [lc+1, W] — the
    multi-source-BFS amortization question. If dt(W=8) ~= dt(W=1), the
    gather cost is per-INDEX, and batching 8 BFS roots into one frontier
    matrix makes each gathered index fetch 8 lanes of payload ~free."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    E, n, nnz = upload_ell()
    lc = E.local_cols
    buckets = [(bc[0, 0], br[0, 0]) for bc, _, br in E.buckets]

    @jax.jit
    def run(x2):
        def body(_, x2):
            acc = jnp.zeros((W,), jnp.int32)
            for bc, _br in buckets:
                g = x2[jnp.minimum(bc, lc)]  # [nb, kb, W]
                acc = acc + jnp.max(jnp.max(g, axis=1), axis=0)
            return x2.at[0].set(acc)

        return lax.fori_loop(0, R, body, x2)

    x0 = jnp.tile(jnp.arange(lc + 1, dtype=jnp.int32)[:, None], (1, W))
    out = run(x0)
    jax.block_until_ready(out)
    time.sleep(3.0)
    dt = timed(lambda prev: run(x0 if prev is None else prev), 1,
               lambda out: int(jax.device_get(out[0, 0])))
    slots = sum(bc.size for bc, _ in buckets)
    return {
        "experiment": f"gatherw W={W} R={R}",
        "iters": R,
        "dt_s": round(dt, 4),
        "ms_per_iter": round(dt / R * 1e3, 3),
        "gather_slots": int(slots),
        "Mindex_per_s": round(slots * R / dt / 1e6, 1),
        "payload_GBps": round(slots * W * 4 * R / dt / 1e9, 2),
    }


def exp_pallas_gather(R: int, W: int = 128):
    """Feasibility + speed of a Pallas TPU kernel doing vectorized dynamic
    gather from a VMEM-resident [lc+1, W] table (the hand-rolled multi-root
    ELL-SpMV core; Mosaic supports 2D gather via jnp.take axis=0)."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    E, n, nnz = upload_ell()
    lc = E.local_cols
    # use the biggest mid-size bucket's indices as the workload
    bc = max((b[0][0, 0] for b in E.buckets), key=lambda a: a.size)
    nb, kb = bc.shape
    idx = jnp.minimum(bc, lc).reshape(-1)  # [nb*kb]
    m = idx.shape[0]
    TILE = 65536
    m_pad = -(-m // TILE) * TILE
    idx = jnp.concatenate([idx, jnp.zeros((m_pad - m,), jnp.int32)])

    def kernel(x_ref, idx_ref, o_ref):
        # Mosaic 2D gather: per-lane gather along sublanes —
        # g[e, r] = x[idx[e], r] via take_along_axis with broadcast idx.
        idx2 = jnp.broadcast_to(idx_ref[:][:, None], (TILE, W))
        g = jnp.take_along_axis(x_ref[:], idx2, axis=0)  # [TILE, W]
        o_ref[:] = jnp.max(g.reshape(-1, 8, g.shape[1]), axis=0)

    @jax.jit
    def run(x):
        def body(_, carry):
            x = carry
            out = pl.pallas_call(
                kernel,
                grid=(m_pad // TILE,),
                in_specs=[
                    pl.BlockSpec(memory_space=pltpu.VMEM),
                    pl.BlockSpec((TILE,), lambda i: (i,)),
                ],
                out_specs=pl.BlockSpec((8, W), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct(
                    (m_pad // TILE * 8, W), jnp.int32
                ),
            )(x, idx)
            return x.at[0, 0].set(jnp.max(out))

        return lax.fori_loop(0, R, body, x)

    x0 = jnp.tile(jnp.arange(lc + 1, dtype=jnp.int32)[:, None], (1, W))
    out = run(x0)
    jax.block_until_ready(out)
    time.sleep(3.0)
    dt = timed(lambda prev: run(x0 if prev is None else prev), 1,
               lambda out: int(jax.device_get(out[0, 0])))
    return {
        "experiment": f"pallas_gather R={R} W={W}",
        "iters": R,
        "dt_s": round(dt, 4),
        "ms_per_iter": round(dt / R * 1e3, 3),
        "gather_slots": int(m),
        "Mindex_per_s": round(m * R / dt / 1e6, 1),
    }



def exp_sort(n_millions: int, R: int):
    """XLA sort throughput on this chip: sort of N uint32 keys (the ESC
    SpGEMM bottleneck candidate — compact() sorts the expanded tuples)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    n = n_millions * 1_000_000
    a = jax.device_put(jnp.arange(n, dtype=jnp.uint32)[::-1])

    @jax.jit
    def run(a):
        def body(_, carry):
            s = jnp.sort(carry)
            return s[::-1]  # keep it unsorted for the next iteration

        return lax.fori_loop(0, R, body, a)

    out = run(a)
    jax.block_until_ready(out)
    time.sleep(3.0)
    dt = timed(lambda prev: run(a), 1, lambda out: int(jax.device_get(out[0])))
    return {
        "experiment": f"sort {n_millions}M R={R}",
        "dt_s": round(dt, 4),
        "ms_per_sort": round(dt / R * 1e3, 2),
        "Mkeys_per_s": round(n * R / dt / 1e6, 1),
    }


def exp_argsort(n_millions: int, R: int):
    """argsort (sort with permutation payload) — what compact() actually
    does (sort_rowmajor carries values)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    n = n_millions * 1_000_000
    a = jax.device_put(jnp.arange(n, dtype=jnp.uint32)[::-1])

    @jax.jit
    def run(a):
        def body(_, carry):
            order = jnp.argsort(carry)
            return carry[order[::-1]]

        return lax.fori_loop(0, R, body, a)

    out = run(a)
    jax.block_until_ready(out)
    time.sleep(3.0)
    dt = timed(lambda prev: run(a), 1, lambda out: int(jax.device_get(out[0])))
    return {
        "experiment": f"argsort {n_millions}M R={R}",
        "dt_s": round(dt, 4),
        "ms_per_argsort": round(dt / R * 1e3, 2),
        "Mkeys_per_s": round(n * R / dt / 1e6, 1),
    }


if __name__ == "__main__":
    main()
