"""Thick-restart Lanczos (Wu & Simon 2000) — beyond-paper accuracy feature.

The paper runs exactly K Lanczos steps (their K is both subspace size and
output count), which caps attainable accuracy by Krylov truncation.  Their
CPU baseline, ARPACK, *restarts* instead: it compresses the subspace to the
best Ritz directions and continues, converging to machine-precision
residuals with bounded memory.  This module adds the same capability on top
of our mixed-precision substrate:

  * subspace of m vectors (m >= k + a few), restart keeps the top-k Ritz
    vectors "thick" + the residual direction;
  * the projected matrix after a restart is arrowhead-plus-tridiagonal,
    handled densely (m <= 64) by the same Jacobi phase-2 as the paper;
  * per-pair convergence test: |beta_m * W[m-1, i]| <= tol * |theta_i|
    (the classical Ritz residual bound — no extra SpMV needed);
  * all vector arithmetic honors the PrecisionPolicy (storage vs compute),
    so the paper's FFF/FDF/DDD study extends to restarted solves.

Host-orchestrated restarts around jitted vector kernels: the right split for
a latency-insensitive convergence loop (identical placement to the paper's
host-side Jacobi phase).

This module is an *engine*: the user-facing entrypoint is ``repro.api.eigsh``
with ``backend="restarted"`` (or any ``tol=``, which auto-selects it).
``topk_eigs_restarted`` remains as a deprecated shim.
"""

from __future__ import annotations

import time
import warnings
from typing import NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..testing import faults as _faults
from ..tracing import span
from .eigensolver import EigResult
from .jacobi import jacobi_eigh_host
from .lanczos import LanczosResult, NumericalBreakdown
from .operators import LinearOperator
from .precision import FDF, PrecisionPolicy, basis_matmul, exact_matmuls

__all__ = [
    "RestartedSolveOutput",
    "restart_kernels",
    "ritz_project",
    "ritz_rows",
    "solve_restarted",
    "topk_eigs_restarted",
]

# Blocking device-to-host reads in one Lanczos step: the ``float(...)`` of
# alpha and of beta (the ``host_reads`` stat of ``repro.lanczos.step``).
_HOST_READS_PER_STEP = 2


def restart_kernels(policy: PrecisionPolicy):
    """The restarted engine's jitted vector kernels ``(dot, orth)``.

    Module-level (rather than closures inside :func:`solve_restarted`) so the
    precision-flow verifier (``repro.analysis``) traces the *same* callables
    the engine executes — the declared phase map is checked against the real
    lowering, not a re-implementation.
    """
    policy = policy.effective()
    cdt = policy.compute
    abdt = policy.phase_dtype("alpha_beta")
    rdt = policy.phase_dtype("reorth")

    @jax.jit
    def dot(a, b):
        return jnp.sum(a.astype(abdt) * b.astype(abdt)).astype(cdt)

    @jax.jit
    def orth(u, basis, nvalid_mask):
        b = basis.astype(rdt)
        coeffs = basis_matmul(b, u.astype(rdt)) * nvalid_mask.astype(rdt)
        return (u.astype(rdt) - basis_matmul(coeffs, b)).astype(cdt)

    return dot, orth


def ritz_rows(basis: jax.Array, wk: jax.Array, policy: PrecisionPolicy, out_dtype=None):
    """Ritz vectors as rows, ``W_k^T @ V`` -> (k, n), in the policy's ritz
    phase dtype.  The long axis stays minor: a TPU lays an (n, 32) operand
    out in 128-lane tiles, and f64 emulation multiplies that 4x padding by
    its split into f32 pieces (an FDF restart on WK asked for 24 GB)."""
    rzdt = policy.phase_dtype("ritz")
    x = basis_matmul(wk.astype(rzdt).T, basis.astype(rzdt))
    return x.astype(out_dtype if out_dtype is not None else policy.output)


def ritz_project(basis: jax.Array, wk: jax.Array, policy: PrecisionPolicy, out_dtype=None):
    """Ritz back-projection ``V^T @ W_k`` -> (n, k) in the policy's ritz
    phase dtype.

    Shared by the final eigenvector assembly and the precision-flow
    verifier's ritz-phase trace (the restart compression uses
    :func:`ritz_rows`).
    """
    return ritz_rows(basis, wk, policy, out_dtype).T


class RestartedSolveOutput(NamedTuple):
    """Raw engine output consumed by the ``eigsh`` frontend."""

    eigenvalues: jax.Array  # (k,) output dtype
    eigenvectors: jax.Array  # (n, k) output dtype
    residuals: np.ndarray  # (k,) float64 — final Ritz residual bounds
    eigenvalues_f64: np.ndarray  # (k,) float64 — pre-output-cast, for tol checks
    tridiag: LanczosResult
    iterations: int  # total Lanczos steps across all restarts
    restarts: int  # restarts actually performed
    timings: dict


@exact_matmuls
def solve_restarted(
    op: LinearOperator,
    k: int,
    policy: PrecisionPolicy = FDF,
    m: int | None = None,
    max_restarts: int = 30,
    tol: float = 1e-8,
    seed: int = 0,
    v1: Optional[jax.Array] = None,
    probe: bool = True,
    checkpoint=None,
) -> RestartedSolveOutput:
    """Top-k eigenpairs by |lambda| with restarts until the Ritz residual
    bound satisfies ``tol`` (relative) for every pair.

    ``probe`` enables the in-loop health check: alpha/beta are already
    Python floats here, so non-finite values and beta underflow raise a
    typed :class:`NumericalBreakdown` at the offending step for free.

    ``checkpoint`` is a ``(store, token)`` pair (see
    :class:`~repro.serving.store.SolveCheckpoint`): the full restart state
    (basis block, projected matrix, arrow border, next start vector,
    counters) is snapshotted after every completed compression, and a rerun
    with the same token resumes from the last completed cycle bit-identically
    — each cycle's fill loop depends only on that state, never on the
    original ``v1``.
    """
    policy = policy.effective()
    cdt, sdt = policy.compute, policy.storage
    rzdt = policy.phase_dtype("ritz")  # Ritz/restart arithmetic phase
    n = op.n
    m = m or max(2 * k, k + 8)
    assert m > k + 1, "subspace must exceed k by at least 2"
    if max_restarts < 1:
        raise ValueError(f"max_restarts must be >= 1, got {max_restarts}")
    mv = op.bound_matvec(policy)
    _dot, _orth = restart_kernels(policy)

    t0 = time.perf_counter()
    if v1 is None:
        rng = np.random.default_rng(seed)
        v = jnp.asarray(rng.standard_normal(n), dtype=cdt)
    else:
        v = jnp.asarray(v1, dtype=cdt)
    v = v / jnp.sqrt(_dot(v, v))

    basis = jnp.zeros((m, n), sdt)
    t_hat = np.zeros((m, m))
    nkeep = 0  # locked Ritz vectors at the head of the basis
    s_border = np.zeros(0)  # arrow column entries for the kept block
    evals = w = None
    steps = 0
    restarts = 0
    resid = np.zeros(k)
    breakdown_tiny = float(jnp.finfo(cdt).tiny) * 1e3
    pol_name = getattr(policy, "name", None) or str(policy)

    start_cycle = 0
    if checkpoint is not None:
        store, token = checkpoint
        state = store.load(token)
        if (
            state is not None
            and state.get("engine") == "restarted"
            and int(state.get("n", -1)) == n
            and int(state.get("m", -1)) == m
            and int(state.get("k", -1)) == k
        ):
            basis = jnp.asarray(state["basis"], sdt)
            t_hat = np.asarray(state["t_hat"], np.float64)
            s_border = np.asarray(state["s_border"], np.float64)
            v = jnp.asarray(state["v"], cdt)
            nkeep = int(state["nkeep"])
            steps = int(state["steps"])
            restarts = int(state["restarts"])
            start_cycle = int(state["cycle"]) + 1

    for cycle in range(start_cycle, max_restarts):
        _faults.check_solve_crash(cycle)
        # --- fill rows nkeep..m-1 with (re-orthogonalized) Lanczos steps ---
        beta_prev = 0.0
        v_prev = jnp.zeros((n,), cdt)
        for i in range(nkeep, m):
            with span("repro.lanczos.step", i=i, cycle=cycle, host_reads=_HOST_READS_PER_STEP):
                basis = basis.at[i].set(v.astype(sdt))
                u = mv(v.astype(sdt)).astype(cdt)
                u = _faults.tap_spmv(u, i)
                alpha = float(_dot(v, u))
                if probe and not np.isfinite(alpha):
                    raise NumericalBreakdown("nonfinite", i, pol_name, f"alpha={alpha!r}")
                t_hat[i, i] = alpha
                u = u - alpha * v - beta_prev * v_prev
                if i == nkeep and nkeep > 0:
                    # arrowhead coupling to the kept Ritz block
                    u = u - basis_matmul(jnp.asarray(s_border, cdt), basis[:nkeep].astype(cdt))
                    t_hat[i, :nkeep] = s_border
                    t_hat[:nkeep, i] = s_border
                # full re-orthogonalization (stability: see EXPERIMENTS §Reorth)
                mask = (jnp.arange(m) <= i).astype(cdt)
                u = _orth(u, basis, mask)
                beta = float(jnp.sqrt(jnp.maximum(_dot(u, u), 0.0)))
                beta = float(_faults.tap_beta(beta, i))
                if probe:
                    if not np.isfinite(beta):
                        raise NumericalBreakdown("nonfinite", i, pol_name, f"beta={beta!r}")
                    if beta <= breakdown_tiny and i < m - 1:
                        raise NumericalBreakdown(
                            "beta_underflow", i, pol_name,
                            f"beta={beta:.3e} <= {breakdown_tiny:.3e}",
                        )
                if i < m - 1:
                    t_hat[i, i + 1] = beta
                    t_hat[i + 1, i] = beta
                beta_prev, v_prev = beta, v
                v = u / max(beta, 1e-300)
            steps += 1
        beta_m = beta_prev

        # --- Ritz pairs of the projected matrix ---
        with span("repro.restarted.jacobi", m=m):
            evals, w = jacobi_eigh_host(t_hat)  # |lambda|-desc
        resid = np.abs(beta_m * w[m - 1, :k])
        if np.all(resid <= tol * np.maximum(np.abs(evals[:k]), 1e-300)):
            break
        if cycle == max_restarts - 1:
            # Budget exhausted: stop here WITHOUT compressing, so the final
            # projection below uses `w` in the coordinates of the current
            # `basis` (compressing would leave them in different systems).
            break

        # --- thick restart: compress to top-k Ritz vectors + residual dir ---
        restarts += 1
        wk = jnp.asarray(w[:, :k], dtype=rzdt)
        with span("repro.restarted.ritz"):
            ritz = ritz_rows(basis, wk, policy, out_dtype=rzdt)  # (k, n)
        new_basis = jnp.zeros((m, n), sdt)
        new_basis = new_basis.at[:k].set(ritz.astype(sdt))
        basis = new_basis
        t_hat = np.zeros((m, m))
        t_hat[:k, :k] = np.diag(evals[:k])
        s_border = beta_m * w[m - 1, :k]
        nkeep = k
        # v (the next Lanczos vector) already holds the residual direction

        if checkpoint is not None:
            store, token = checkpoint
            store.save(
                token,
                {
                    "engine": "restarted",
                    "cycle": cycle,
                    "n": n,
                    "m": m,
                    "k": k,
                    "nkeep": nkeep,
                    "steps": steps,
                    "restarts": restarts,
                    "basis": basis,
                    "t_hat": t_hat,
                    "s_border": s_border,
                    "v": v,
                },
            )

    if checkpoint is not None:
        store, token = checkpoint
        store.clear(token)  # completed: the snapshot must not resurrect
    evals_k = jnp.asarray(evals[:k], dtype=policy.output)
    wk = jnp.asarray(w[:, :k], dtype=rzdt)
    with span("repro.restarted.ritz"):
        x = ritz_project(basis, wk, policy)
    lres = LanczosResult(
        alpha=jnp.asarray(np.diag(t_hat), cdt),
        beta=jnp.asarray(np.diag(t_hat, 1), cdt),
        basis=basis,
        beta_last=jnp.asarray(beta_m, cdt),
    )
    total = time.perf_counter() - t0
    return RestartedSolveOutput(
        eigenvalues=evals_k,
        eigenvectors=x,
        residuals=np.asarray(resid, dtype=np.float64),
        eigenvalues_f64=np.asarray(evals[:k], dtype=np.float64),
        tridiag=lres,
        iterations=steps,
        restarts=restarts,
        timings={"total_s": total},
    )


def topk_eigs_restarted(
    op: LinearOperator,
    k: int,
    policy: PrecisionPolicy = FDF,
    m: int | None = None,
    max_restarts: int = 30,
    tol: float = 1e-8,
    seed: int = 0,
) -> EigResult:
    """Deprecated: use :func:`repro.api.eigsh` with ``tol=``/``backend="restarted"``."""
    warnings.warn(
        "topk_eigs_restarted is deprecated; use "
        "repro.api.eigsh(A, k, backend='restarted', tol=..., subspace=m, ...)",
        DeprecationWarning,
        stacklevel=2,
    )
    from ..api import eigsh

    res = eigsh(
        op,
        k,
        policy=policy,
        backend="restarted",
        tol=tol,
        subspace=m,
        max_restarts=max_restarts,
        seed=seed,
    )
    return EigResult(
        eigenvalues=res.eigenvalues,
        eigenvectors=res.eigenvectors,
        tridiag=res.tridiag,
        wall_time_s=res.timings["total_s"],
    )
