"""Fixed-subspace Top-K sparse eigensolver engine (the paper's Fig. 1 pipeline).

``solve_fixed`` = Lanczos (device, phase 1) + Jacobi (host CPU by default,
exactly the paper's placement; pure-JAX optional) + basis combination
``X = V^T W`` + |lambda|-descending selection.

This module is an *engine*: the user-facing entrypoint is ``repro.api.eigsh``
(the unified frontend), which dispatches here for the single-device and
chunked out-of-core paths.  ``topk_eigs`` remains as a deprecated shim.
"""

from __future__ import annotations

import time
import warnings
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .jacobi import jacobi_eigh, jacobi_eigh_host, tridiag_to_dense
from .lanczos import LanczosResult, check_tridiag_health, lanczos_tridiag, ops_for_operator
from .operators import LinearOperator
from .precision import FDF, PrecisionPolicy, basis_matmul, exact_matmuls

__all__ = [
    "EigResult",
    "FixedSolveOutput",
    "ritz_decompose",
    "ritz_extract",
    "solve_fixed",
    "topk_eigs",
]


class EigResult(NamedTuple):
    """Legacy result type kept for the deprecated ``topk_eigs`` shims."""

    eigenvalues: jax.Array  # (k,) output dtype, |lambda| descending
    eigenvectors: jax.Array  # (n, k) output dtype, column-wise
    tridiag: LanczosResult  # raw Lanczos output (alpha, beta, basis)
    wall_time_s: float


class FixedSolveOutput(NamedTuple):
    """Raw engine output consumed by the ``eigsh`` frontend."""

    eigenvalues: jax.Array  # (k,) output dtype, |lambda| descending
    eigenvectors: jax.Array  # (n, k) output dtype
    residuals: np.ndarray  # (k,) float64 — Ritz residual bounds |beta_m W[m-1,i]|
    eigenvalues_f64: np.ndarray  # (k,) float64 — pre-output-cast, for tol checks
    tridiag: LanczosResult
    iterations: int  # Lanczos steps actually run (= m)
    timings: dict  # seconds: lanczos / jacobi / project / total


@exact_matmuls
def ritz_decompose(lres: LanczosResult, policy: PrecisionPolicy, jacobi: str = "host"):
    """Phase 2: eigen-decompose the Lanczos tridiagonal.

    Returns ``(evals, w, evals_f64, w_f64, beta_m)`` where ``evals`` / ``w``
    are device arrays in the compute dtype (|lambda|-descending), the f64
    copies are host-side for residual/tolerance arithmetic, and ``beta_m``
    is the final residual norm scaling the classical Ritz bound.  Split out
    of :func:`solve_fixed` so the session layer's shared-subspace sweep
    (``api/session.py``) can decompose one tridiagonal and serve many
    ``(k, tol)`` queries from it.
    """
    rzdt = policy.phase_dtype("ritz")  # Ritz/restart arithmetic phase dtype
    if jacobi == "host":
        t_host = tridiag_to_dense(
            np.asarray(lres.alpha, dtype=np.float64),
            np.asarray(lres.beta, dtype=np.float64),
        )
        evals_f64, w_host = jacobi_eigh_host(np.asarray(t_host))
        evals = jnp.asarray(evals_f64, dtype=rzdt)
        w = jnp.asarray(w_host, dtype=rzdt)
    else:
        # The device Jacobi runs in the tridiagonal's dtype: cast to the ritz
        # phase dtype first (no-op when it equals compute) so the phase_map
        # audit reports what actually executed.
        t_dev = tridiag_to_dense(lres.alpha, lres.beta).astype(rzdt)
        evals, w = jacobi_eigh(t_dev)
        evals_f64 = np.asarray(evals, dtype=np.float64)
    # Residual arithmetic sees W *as the solver uses it* — rounded through
    # the compute dtype — so reported residuals are bit-identical to the
    # pre-refactor solve_fixed for every policy (f32-compute included).
    w_f64 = np.asarray(w, dtype=np.float64)
    beta_m = (
        float(np.asarray(lres.beta_last, dtype=np.float64)) if lres.beta_last is not None else 0.0
    )
    return evals, w, np.asarray(evals_f64, dtype=np.float64), w_f64, beta_m


@exact_matmuls
def ritz_extract(
    lres: LanczosResult,
    evals,
    w,
    w_f64: np.ndarray,
    beta_m: float,
    k: int,
    policy: PrecisionPolicy,
):
    """Phase 3: Top-K selection + back-projection ``X = V^T W`` + residuals.

    Returns ``(evals_k, x, residuals)`` with ``evals_k`` / ``x`` in the
    policy's output dtype.  Columns are independent, so extracting at
    ``k_max`` and slicing serves every smaller-``k`` query of a batch.
    """
    m = int(w_f64.shape[0])
    evals_k = evals[:k]
    w_k = w[:, :k].astype(policy.phase_dtype("ritz"))
    # (k, m) @ (m, n), then transpose: the long axis stays minor (see
    # restarted.ritz_rows for what the other orientation costs a TPU).
    x = basis_matmul(w_k.T, lres.basis.astype(policy.phase_dtype("ritz")))
    x = x.astype(policy.output).T
    # Classical Ritz residual bound: ||A x_i - theta_i x_i|| = |beta_m W[m-1,i]|.
    residuals = np.abs(beta_m * w_f64[m - 1, :k])
    return evals_k.astype(policy.output), x, residuals


@exact_matmuls
def solve_fixed(
    op: LinearOperator,
    k: int,
    policy: PrecisionPolicy = FDF,
    reorth: str = "half",
    num_iters: Optional[int] = None,
    v1: Optional[jax.Array] = None,
    seed: int = 0,
    jacobi: str = "host",
    ops=None,
    probe: bool = True,
    checkpoint=None,
) -> FixedSolveOutput:
    """Compute the K eigenpairs of largest |lambda| of a symmetric operator.

    ``num_iters`` defaults to ``k`` — the paper's configuration (their K is
    both the subspace size and the output count).  Larger values give an
    extended Krylov subspace from which the Top-K Ritz pairs are extracted
    (beyond-paper accuracy knob).

    ``ops`` (an :class:`~repro.core.lanczos.Ops`) lets a caller reuse ONE
    arithmetic-kernel record across solves: the jitted Lanczos loop is keyed
    on the record's identity, so a stable record means repeated solves hit
    the XLA compile cache instead of retracing — the session layer's serving
    path passes its per-(plan, policy) record here.
    """
    policy = policy.effective()
    m = num_iters or k
    if m < k:
        raise ValueError("num_iters must be >= k")
    n = op.n
    if v1 is None:
        v1 = jax.random.normal(jax.random.PRNGKey(seed), (n,), dtype=policy.compute)

    t0 = time.perf_counter()
    # Operators that stream host data per step (ChunkedOperator) must run the
    # Lanczos loop eagerly: see LinearOperator.prefers_jit / lanczos module doc.
    use_jit = getattr(op, "prefers_jit", True)
    if ops is None:
        # Route by the operator's measured iteration plan (fused/unfused/
        # fully-fused SpMV+alpha) instead of the bare policy gate.
        ops = ops_for_operator(op, policy)
    lres = lanczos_tridiag(
        op.bound_matvec(policy), v1, m, policy, reorth=reorth, jit=use_jit, ops=ops,
        checkpoint=checkpoint if not use_jit else None,
    )
    lres = jax.tree.map(lambda x: x.block_until_ready(), lres)
    if probe:
        # Health probe on the already-materialized tridiagonal scalars: a
        # typed NumericalBreakdown beats NaN eigenvalues (see lanczos module).
        check_tridiag_health(lres, policy)
    t_lanczos = time.perf_counter() - t0

    # Phase 2 — Jacobi on the K x K tridiagonal matrix.
    t1 = time.perf_counter()
    evals, w, evals_f64, w_f64, beta_m = ritz_decompose(lres, policy, jacobi)
    t_jacobi = time.perf_counter() - t1

    # Top-K selection (already |lambda|-sorted) and back-projection X = V^T W.
    t2 = time.perf_counter()
    evals_k, x, residuals = ritz_extract(lres, evals, w, w_f64, beta_m, k, policy)
    x.block_until_ready()
    t_project = time.perf_counter() - t2

    total = time.perf_counter() - t0
    return FixedSolveOutput(
        eigenvalues=evals_k,
        eigenvectors=x,
        residuals=residuals,
        eigenvalues_f64=np.asarray(evals_f64[:k], dtype=np.float64),
        tridiag=lres,
        iterations=m,
        timings={
            "lanczos_s": t_lanczos,
            "jacobi_s": t_jacobi,
            "project_s": t_project,
            "total_s": total,
        },
    )


def topk_eigs(
    op: LinearOperator,
    k: int,
    policy: PrecisionPolicy = FDF,
    reorth: str = "half",
    num_iters: Optional[int] = None,
    v1: Optional[jax.Array] = None,
    seed: int = 0,
    jacobi: str = "host",
) -> EigResult:
    """Deprecated: use :func:`repro.api.eigsh` (the unified frontend)."""
    warnings.warn(
        "topk_eigs is deprecated; use repro.api.eigsh(A, k, backend='single', ...)",
        DeprecationWarning,
        stacklevel=2,
    )
    from ..api import eigsh

    res = eigsh(
        op,
        k,
        policy=policy,
        backend="single",
        reorth=reorth,
        num_iters=num_iters,
        v0=v1,
        seed=seed,
        jacobi=jacobi,
    )
    return EigResult(
        eigenvalues=res.eigenvalues,
        eigenvectors=res.eigenvectors,
        tridiag=res.tridiag,
        wall_time_s=res.timings["total_s"],
    )
