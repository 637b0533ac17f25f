"""Multi-device Top-K eigensolver (the paper's §III-A partition scheme).

Mapping of the paper's multi-GPU design onto a JAX device mesh:

  paper                                 | here
  --------------------------------------+----------------------------------
  row partitions balanced by nnz        | ``core/partition.py`` (same greedy
                                        | prefix scheme), shards stacked on a
                                        | leading axis consumed by shard_map
  every vector partitioned like M       | vectors live as (n_pad,) locals
  SpMV input v_i replicated per GPU     | ``lax.all_gather(..., tiled=True)``
  round-robin partition swap to refill  | the all-gather's ring schedule on
  the replicas (their Fig. 1 C)         | the ICI torus *is* that round-robin
  sync points alpha / beta (A / B)      | two ``lax.psum`` per iteration
  reorth sync (C)                       | one psum per reorth pass (k-vector)
  out-of-core unified memory            | ChunkedOperator (operators.py)

The entire Lanczos loop executes inside ONE ``shard_map`` region, so the
only cross-device traffic per iteration is: 1 all-gather (n floats) +
2 scalar psums + (optionally) 1 k-length psum — matching the paper's
communication analysis.

Per-shard SpMV runs through the :class:`~repro.kernels.engine.SpmvEngine`
layer: each shard's COO slice is converted host-side to ELL, blocked-ELL,
or the hybrid hub split (``sparse.formats.shard_to_*``) and the Lanczos hot
loop runs the engine's gather body for that layout.  ``spmv_format="auto"``
picks ELL vs BSR vs hybrid from per-shard statistics — hybrid keeps
power-law shards off ``segment_sum`` for the bulk of their non-zeros by
capping the ELL width and spilling hub overflow to a small tail; plain COO
remains only as an explicit opt-out (``spmv_format="coo"``).
"""

from __future__ import annotations

import time
import warnings
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..kernels.engine import SpmvEngine, make_engine, shard_stats
from ..sparse.formats import CSR, row_sums, shard_to_blocked_ell, shard_to_ell, shard_to_hybrid
from .eigensolver import EigResult
from .jacobi import jacobi_eigh_host, tridiag_to_dense
from ..testing import faults as _faults
from .lanczos import (
    LanczosResult,
    Ops,
    _lanczos_loop,
    check_tridiag_health,
    resolve_update_mode,
)
from .partition import PartitionedMatrix, nnz_balanced_splits, partition_matrix
from .precision import PrecisionPolicy, FDF, basis_matmul, compensated_sum, exact_matmuls

__all__ = [
    "DISTRIBUTED_FORMATS",
    "PreparedShards",
    "prepare_sharded",
    "ShardedSolveOutput",
    "solve_sharded",
    "topk_eigs_sharded",
    "sharded_lanczos",
]

# Formats the distributed hot loop may auto-select: no per-nnz segment_sum
# (the paper's design point; hybrid's tail segment_sum is bounded by the hub
# split, so it still counts).  "coo" stays available as an explicit request.
DISTRIBUTED_FORMATS = ("ell", "bsr", "hybrid")

# check_vma off: the replication rule for pallas_call is unimplemented.
_shard_map = jax.shard_map
_SHARD_MAP_KW = {"check_vma": False}


def _make_sharded_ops(
    mats: tuple,
    n_pad: int,
    policy: PrecisionPolicy,
    axis: str,
    engine: Optional[SpmvEngine] = None,
    fused: Optional[bool] = None,
) -> Ops:
    cdt = policy.compute
    abdt = policy.phase_dtype("alpha_beta")  # alpha/beta reduction phase
    rdt = policy.phase_dtype("reorth")  # re-orthogonalization phase
    sdt_spmv = policy.phase_dtype("spmv")  # SpMV accumulator phase
    fmt = engine.format if engine is not None else "coo"

    def matvec(x_local):
        # Replicate the SpMV input: the paper's round-robin partition swap.
        x_full = jax.lax.all_gather(x_local, axis, tiled=True)  # (G * n_pad,)
        if fmt == "ell":
            val, col = mats
            return engine.ell_matvec(val, col, x_full)[:n_pad].astype(cdt)
        if fmt == "bsr":
            val, bcol = mats
            return engine.bsr_matvec(val, bcol, x_full)[:n_pad].astype(cdt)
        if fmt == "hybrid":
            val, col, trow, tcol, tval = mats
            y = engine.hybrid_matvec(val, col, trow, tcol, tval, x_full, n_pad)
            return y.astype(cdt)
        row, col, val = mats
        prod = val.astype(sdt_spmv) * jnp.take(x_full, col).astype(sdt_spmv)
        return row_sums(prod, row, n_pad).astype(cdt)

    def dot(a, b):
        prods = a.astype(abdt) * b.astype(abdt)
        local = compensated_sum(prods, abdt) if policy.compensated else jnp.sum(prods)
        return jax.lax.psum(local, axis).astype(cdt)  # sync point A / B

    def gram(vs, u):
        local = basis_matmul(vs.astype(rdt), u.astype(rdt))
        return jax.lax.psum(local, axis).astype(cdt)  # sync point C

    def project_out(vs, u, mask):
        vs_c = vs.astype(rdt) * mask.astype(rdt)[:, None]  # ONE (m, n_pad) cast
        # u rounds through the storage dtype first — legacy gram-path policy
        # semantics (see make_local_ops.project_out).
        local = basis_matmul(vs_c, u.astype(policy.storage).astype(rdt))
        coeffs = jax.lax.psum(local, axis)  # sync point C
        return (u.astype(rdt) - basis_matmul(coeffs, vs_c)).astype(cdt)

    fused_update = None
    interpret = None if engine is None else engine.interpret
    if resolve_update_mode(policy, fused=fused, interpret=interpret) == "fused":
        from ..kernels import ops as kops

        kw = {} if interpret is None else {"interpret": interpret}

        def fused_update(w, v, v_prev, alpha, beta, need_norm=True):
            u, nrm_sq = kops.lanczos_update(w, v, v_prev, alpha, beta, accum_dtype=cdt, **kw)
            # Only pay the collective when the caller will use the norm
            # (under reorth the loop recomputes beta post-projection, and an
            # extra psum per iteration would break the paper's sync budget).
            if need_norm:
                nrm_sq = jax.lax.psum(nrm_sq, axis)  # sync point B
            return u, nrm_sq

    return Ops(
        matvec=matvec, dot=dot, gram=gram, project_out=project_out,
        fused_update=fused_update,
    )


def sharded_lanczos(
    pm: PartitionedMatrix,
    v1_padded: jax.Array,
    num_iters: int,
    policy: PrecisionPolicy,
    mesh: Mesh,
    reorth: str = "full",
    axis: str = "data",
    engine: Optional[SpmvEngine] = None,
    mats: Optional[tuple] = None,
    fused: Optional[bool] = None,
) -> LanczosResult:
    """Run the distributed Lanczos loop. ``v1_padded``: (G, n_pad) layout.

    ``mats`` are the shard-stacked SpMV arrays matching ``engine.format``
    (default: the COO triplets of ``pm`` — the legacy segment-sum path);
    ``fused`` pins the update mode (:func:`resolve_update_mode`).
    """
    policy = policy.effective()
    _faults.check_sweep_entry()
    if mats is None:
        mats = (pm.row, pm.col, pm.val)

    def local_fn(v1, *shard_mats):
        v1 = v1[0]  # drop shard axis
        local = tuple(m[0] for m in shard_mats)
        ops = _make_sharded_ops(local, pm.n_pad, policy, axis, engine=engine, fused=fused)
        res = _lanczos_loop(v1, ops, num_iters, policy, reorth)
        return res.alpha, res.beta, res.beta_last, res.basis[None]  # re-add shard axis

    fn = _shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(axis),) * (1 + len(mats)),
        out_specs=(P(), P(), P(), P(axis, None, None)),
        **_SHARD_MAP_KW,
    )
    alpha, beta, beta_last, basis_sh = jax.jit(fn)(v1_padded, *mats)
    # The wrapper re-traces per call (fresh jit object), so an armed Lanczos
    # fault is baked into this launch; count it host-side (see faults docs).
    _faults.consume_lanczos(_faults.trace_key())
    return LanczosResult(alpha=alpha, beta=beta, basis=basis_sh, beta_last=beta_last)


class PreparedShards(NamedTuple):
    """Plan-time product of the distributed engine: the nnz-balanced row
    partition, the shard-stacked SpMV arrays in the engine's chosen
    format, and the engine itself (padding + accum dtype).  Building one is
    the entire per-matrix setup cost of :func:`solve_sharded`; reusing it
    across solves (``api/session.py``) skips every host-side conversion."""

    pm: PartitionedMatrix
    mats: tuple  # shard-stacked SpMV arrays matching engine.format
    engine: SpmvEngine
    spmv_meta: dict  # engine.describe() + conversion stats
    convert_s: float  # one-time partition + conversion wall time


def prepare_sharded(
    csr: CSR,
    g: int,
    policy: PrecisionPolicy = FDF,
    spmv_format: str = "auto",
    engine: Optional[SpmvEngine] = None,
) -> PreparedShards:
    """Partition + convert a CSR for a ``g``-shard distributed solve.

    This is the *plan* half of :func:`solve_sharded`: everything here is a
    pure function of (matrix, shard count, storage/compute dtypes, format)
    and none of it depends on the query (k, num_iters, tol, start vector),
    so one ``PreparedShards`` amortizes across arbitrarily many solves.
    """
    policy = policy.effective()
    t0 = time.perf_counter()
    splits = nnz_balanced_splits(csr.indptr, g)
    if engine is None:
        allowed = DISTRIBUTED_FORMATS if spmv_format == "auto" else ("coo",) + DISTRIBUTED_FORMATS
        engine = make_engine(
            csr,
            spmv_format,
            stats=shard_stats(csr, splits, with_blocks=(spmv_format == "auto")),
            accum_dtype=policy.phase_dtype("spmv"),
            allowed=allowed,
            storage_dtype=policy.storage,
        )
    fmt = engine.format
    row_align = {
        "ell": engine.tiles.block_r,
        "hybrid": engine.tiles.block_r,
        "bsr": engine.tiles.block_size,
    }.get(fmt, 1)
    pm = partition_matrix(
        csr, g, dtype=policy.storage, row_align=row_align, with_coo=(fmt == "coo"),
        splits=splits,
    )
    spmv_meta = engine.describe()
    if fmt == "ell":
        ell_val, ell_col, conv_stats = shard_to_ell(
            csr,
            pm.splits(),
            pm.n_pad,
            dtype=policy.storage,
            row_tile=engine.tiles.block_r,
            slot_tile=128,
        )
        mats = (ell_val, ell_col)
        spmv_meta.update(conv_stats)
    elif fmt == "bsr":
        bsr_val, bsr_bcol, conv_stats = shard_to_blocked_ell(
            csr,
            pm.splits(),
            pm.n_pad,
            block_size=engine.tiles.block_size,
            dtype=policy.storage,
        )
        mats = (bsr_val, bsr_bcol)
        spmv_meta.update(conv_stats)
    elif fmt == "hybrid":
        mats, conv_stats = shard_to_hybrid(
            csr,
            pm.splits(),
            pm.n_pad,
            dtype=policy.storage,
            width_cap=max(s.hyb_width for s in engine.stats) if engine.stats else None,
            row_tile=engine.tiles.block_r,
        )
        spmv_meta.update(conv_stats)
    else:
        mats = (pm.row, pm.col, pm.val)
    return PreparedShards(
        pm=pm,
        mats=mats,
        engine=engine,
        spmv_meta=spmv_meta,
        convert_s=time.perf_counter() - t0,
    )


class ShardedSolveOutput(NamedTuple):
    """Raw engine output consumed by the ``eigsh`` frontend."""

    eigenvalues: jax.Array  # (k,) output dtype
    eigenvectors: jax.Array  # (n, k) output dtype
    residuals: np.ndarray  # (k,) float64 — Ritz residual bounds
    eigenvalues_f64: np.ndarray  # (k,) float64 — pre-output-cast, for tol checks
    tridiag: LanczosResult
    iterations: int
    partition: dict  # num_shards / n_pad / splits / axis / spmv
    timings: dict
    spmv_format: tuple = ()  # per-shard executed SpMV format


@exact_matmuls
def solve_sharded(
    csr: CSR,
    k: int,
    mesh: Mesh,
    policy: PrecisionPolicy = FDF,
    reorth: str = "full",
    num_iters: Optional[int] = None,
    seed: int = 0,
    axis: str = "data",
    v1: Optional[jax.Array] = None,
    spmv_format: str = "auto",
    engine: Optional[SpmvEngine] = None,
    prepared: Optional[PreparedShards] = None,
    probe: bool = True,
) -> ShardedSolveOutput:
    """End-to-end distributed Top-K eigensolver on a 1-axis mesh.

    ``spmv_format``: "auto" picks ELL vs blocked-ELL per shard statistics
    (kernel-backed hot loop, the paper's design); "ell" / "bsr" force a
    kernel layout; "coo" opts back into the ``segment_sum`` reference path.
    A prebuilt ``engine`` overrides ``spmv_format``; a ``prepared``
    :class:`PreparedShards` (see :func:`prepare_sharded`) skips the whole
    plan phase — partition, conversion, tile selection — entirely.
    """
    policy = policy.effective()
    g = mesh.shape[axis]
    m = num_iters or k

    t_conv0 = time.perf_counter()
    if prepared is None:
        prepared = prepare_sharded(csr, g, policy, spmv_format, engine=engine)
        t_convert = prepared.convert_s
    else:
        t_convert = 0.0  # plan reused: this call pays no conversion
    pm, mats = prepared.pm, prepared.mats
    engine, spmv_meta = prepared.engine, dict(prepared.spmv_meta)
    fmt = engine.format

    if v1 is None:
        rng = np.random.default_rng(seed)
        v1 = jnp.asarray(rng.standard_normal(csr.n), dtype=policy.compute)
    v1p = pm.pad_vector(jnp.asarray(v1, dtype=policy.compute))

    t0 = time.perf_counter()
    lres = sharded_lanczos(
        pm, v1p, m, policy, mesh, reorth=reorth, axis=axis, engine=engine, mats=mats
    )
    lres = jax.tree.map(lambda a: a.block_until_ready(), lres)  # timings = execution, not dispatch
    if probe:
        check_tridiag_health(lres, policy)
    t_lanczos = time.perf_counter() - t0
    t1 = time.perf_counter()
    alpha = np.asarray(lres.alpha, dtype=np.float64)
    beta = np.asarray(lres.beta, dtype=np.float64)
    evals, w = jacobi_eigh_host(np.asarray(tridiag_to_dense(jnp.asarray(alpha), jnp.asarray(beta))))
    t_jacobi = time.perf_counter() - t1

    # X = V^T W on the padded layout, then strip padding.
    t2 = time.perf_counter()
    basis = lres.basis  # (G, m, n_pad) shard-stacked
    rzdt = policy.phase_dtype("ritz")  # Ritz-extraction phase dtype
    w_k = jnp.asarray(w[:, :k], dtype=rzdt)
    x_pad = jnp.einsum("gmn,mk->gnk", basis.astype(rzdt), w_k)
    parts = []
    splits = pm.splits()
    for s in range(g):
        lo, hi = int(splits[s]), int(splits[s + 1])
        parts.append(x_pad[s, : hi - lo, :])
    x = jnp.concatenate(parts, axis=0).astype(policy.output)
    x.block_until_ready()
    t_project = time.perf_counter() - t2

    beta_m = float(np.asarray(lres.beta_last, dtype=np.float64))
    residuals = np.abs(beta_m * np.asarray(w, dtype=np.float64)[m - 1, :k])
    total = time.perf_counter() - t_conv0  # includes host-side format conversion
    return ShardedSolveOutput(
        eigenvalues=jnp.asarray(evals[:k], dtype=policy.output),
        eigenvectors=x,
        residuals=residuals,
        eigenvalues_f64=np.asarray(evals[:k], dtype=np.float64),
        tridiag=lres,
        iterations=m,
        partition={
            "num_shards": int(g),
            "n_pad": int(pm.n_pad),
            "splits": [int(s) for s in splits],
            "axis": axis,
            "spmv": spmv_meta,
        },
        timings={
            "convert_s": t_convert,
            "lanczos_s": t_lanczos,
            "jacobi_s": t_jacobi,
            "project_s": t_project,
            "total_s": total,
        },
        spmv_format=(fmt,) * int(g),
    )


def topk_eigs_sharded(
    csr: CSR,
    k: int,
    mesh: Mesh,
    policy: PrecisionPolicy = FDF,
    reorth: str = "full",
    num_iters: Optional[int] = None,
    seed: int = 0,
    axis: str = "data",
) -> EigResult:
    """Deprecated: use :func:`repro.api.eigsh` with ``backend="distributed"``."""
    warnings.warn(
        "topk_eigs_sharded is deprecated; use "
        "repro.api.eigsh(csr, k, backend='distributed', mesh=mesh, ...)",
        DeprecationWarning,
        stacklevel=2,
    )
    from ..api import eigsh

    res = eigsh(
        csr,
        k,
        policy=policy,
        backend="distributed",
        reorth=reorth,
        num_iters=num_iters,
        seed=seed,
        mesh=mesh,
        axis=axis,
    )
    return EigResult(
        eigenvalues=res.eigenvalues,
        eigenvectors=res.eigenvectors,
        tridiag=res.tridiag,
        wall_time_s=res.timings["total_s"],
    )
