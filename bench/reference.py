"""The plain reference that decides ``correct``, and the comparison itself.

Every request of the benchmark's traffic asks the eigensolver for ``steps``
Lanczos steps from a given start vector (one cycle of the restarted engine,
no restart), then the Ritz pairs of the ``k`` Ritz values of largest
magnitude.  Those pairs are a function of the matrix, the start vector and
the step count alone, so the reference recomputes them here in float64 with
SciPy and NumPy, independently of the program: Lanczos with full
reorthogonalisation (classical Gram-Schmidt, twice), the tridiagonal matrix's
eigenpairs by ``numpy.linalg.eigh``, and the Ritz vectors ``S^T V``.

The numbers compared, per request (``compare``):

``eig_gap``
    the widest gap between a served Ritz value and the reference's, over
    the largest reference Ritz value ``|theta_1|`` (the spectrum's scale);
``vec_gap``
    the widest angle (its sine) between a served Ritz vector and the
    reference's;
``res_gap``
    the widest gap between the norm of a served pair's residual
    ``||A x - theta x|| / ||x||`` and the reference pair's, over
    ``|theta_1|``, with the matrix in float64.

``eig_gap`` covers the tridiagonal matrix the recurrence built and the
Ritz step; ``vec_gap`` and ``res_gap`` cover the basis the eigenvectors are
formed from and the back-projection.
"""

from __future__ import annotations

import numpy as np
import scipy.sparse as sp

NUMBERS = ("eig_gap", "vec_gap", "res_gap")


def matrix(indptr, indices, data, n: int, dtype=np.float64):
    return sp.csr_matrix((np.asarray(data, dtype), indices, indptr), shape=(n, n))


def ritz_pairs(a, v0: np.ndarray, steps: int, k: int):
    """``(theta (k,), x (k, n))``: the Ritz pairs of the ``k`` Ritz values of
    largest magnitude after ``steps`` Lanczos steps of ``a`` from ``v0``,
    in the dtype of ``a``."""
    dt = a.dtype
    n = a.shape[0]
    basis = np.zeros((steps, n), dt)
    alpha = np.zeros(steps)
    beta = np.zeros(steps)
    v = np.asarray(v0, dt)
    v = v / np.linalg.norm(v)
    for j in range(steps):
        basis[j] = v
        w = a @ v
        alpha[j] = float(v @ w)
        w = w - alpha[j] * v
        if j:
            w = w - beta[j - 1] * basis[j - 1]
        for _ in range(2):
            w = w - basis[: j + 1].T @ (basis[: j + 1] @ w)
        beta[j] = float(np.linalg.norm(w))
        v = w / beta[j]
    t = np.diag(alpha) + np.diag(beta[:-1], 1) + np.diag(beta[:-1], -1)
    theta, s = np.linalg.eigh(t)
    top = np.argsort(-np.abs(theta), kind="stable")[:k]
    return theta[top], (s[:, top].T.astype(dt) @ basis)


def _residuals(a, theta, x) -> np.ndarray:
    """``||A x_i - theta_i x_i|| / ||x_i||`` per row of ``x``."""
    r = (a @ x.T).T - theta[:, None] * x
    return np.linalg.norm(r, axis=1) / np.linalg.norm(x, axis=1)


def compare(a, theta, x, ref_theta, ref_x) -> dict:
    """The numbers compared for one served answer ``(theta (k,), x (k, n))``
    against the reference's; ``a`` is the float64 matrix."""
    theta = np.asarray(theta, np.float64)
    x = np.asarray(x, np.float64)
    scale = abs(float(ref_theta[0]))
    cos = np.abs(np.einsum("ij,ij->i", x, ref_x)) / (
        np.linalg.norm(x, axis=1) * np.linalg.norm(ref_x, axis=1)
    )
    return {
        "eig_gap": float(np.max(np.abs(theta - ref_theta))) / scale,
        "vec_gap": float(np.max(np.sqrt(np.maximum(1.0 - cos * cos, 0.0)))),
        "res_gap": float(
            np.max(np.abs(_residuals(a, theta, x) - _residuals(a, ref_theta, ref_x)))
        )
        / scale,
    }
