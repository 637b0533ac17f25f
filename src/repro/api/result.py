"""The unified solver result type returned by every ``eigsh`` backend.

The paper's transparency argument (one solver, any scale) only survives into
an API if every execution path — single-device, shard_map-distributed,
thick-restarted, chunked out-of-core — reports its outcome in the same
schema.  ``EigenResult`` is that schema: eigenpairs plus the convergence,
precision, placement, and timing facts a caller needs to trust (or retry)
a solve.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.lanczos import LanczosResult

__all__ = ["EigenResult", "with_queue_time"]


def _jsonify(obj):
    """Recursively convert numpy/jax scalars and arrays to JSON-safe types."""
    if isinstance(obj, dict):
        return {str(k): _jsonify(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonify(v) for v in obj]
    if isinstance(obj, (np.bool_, bool)):
        return bool(obj)
    if isinstance(obj, (np.integer, int)):
        return int(obj)
    if isinstance(obj, (np.floating, float)):
        return float(obj)
    if isinstance(obj, (np.ndarray, jax.Array)):
        arr = np.asarray(obj)
        if arr.dtype == np.bool_:
            return arr.tolist()
        if np.issubdtype(arr.dtype, np.integer):
            return arr.astype(np.int64).tolist()  # exact: indices must stay ints
        return arr.astype(np.float64).tolist()
    return obj


@dataclasses.dataclass(frozen=True)
class EigenResult:
    """Result of :func:`repro.api.eigsh`, identical across all backends.

    Supports scipy-style unpacking: ``evals, evecs = eigsh(A, k)``.

    Attributes:
      eigenvalues: (k,) |lambda|-descending, in the policy's output dtype.
      eigenvectors: (n, k) column eigenvectors, same dtype.
      residuals: (k,) float64 Ritz residual bounds ``|beta_m * W[m-1, i]|``
        (an upper estimate of ``||A x_i - lambda_i x_i||``; free — no extra
        SpMV).
      converged: (k,) bool — ``residuals <= tol * |lambda_i|`` under the
        effective tolerance.
      iterations: Lanczos steps actually run (summed across restarts).
      restarts: thick restarts performed (0 for fixed-subspace backends).
      k / n: problem dimensions.
      backend: backend actually executed ("single" | "distributed" |
        "restarted" | "chunked").
      policy: name of the precision policy actually used (after any
        x64-unavailable downgrade, e.g. ``"FDF(x32!)"``).
      tol: the effective relative tolerance convergence was judged against.
      num_devices: devices the solve ran on.
      partition: placement facts, backend-dependent: the distributed backend
        records the row partition (num_shards / n_pad / splits / axis); the
        chunked backend records the chunk stream (num_chunks / stage_depth /
        ``"staging"`` counters: one-time host conversions, THIS call's
        device_put transfers, peak device-resident chunks).  Every backend
        (since the plan/execute split) carries a ``"spmv"`` dict with the
        executed SpMV format, the layout padding, what ran each phase
        (``"kernels"``), and the session-reuse audit (``"conversions"`` this
        call paid, ``"reused"``).
      timings: seconds per phase — always contains ``"total_s"``, plus the
        plan/execute split ``"prepare_s"`` (what this call spent building
        session state: coercion, conversion; 0.0 on session reuse)
        and ``"solve_s"`` (the execute phase); fixed-m backends add
        ``"lanczos_s"`` / ``"jacobi_s"`` / ``"project_s"``.  Batched
        ``eigsh_many`` results sharing one sweep also carry
        ``"amortized_over"`` (queries served by these timings).  Results
        returned through the serving scheduler additionally carry the
        queue/solve split: ``"queue_s"`` (submit-to-dispatch wait) and
        ``"e2e_s"`` (``queue_s + total_s``, what the submitter observed) —
        see :func:`with_queue_time`.
      spmv_format: SpMV layout the hot loop executed — "coo" | "ell" | "bsr"
        | "hybrid" (quantile-capped ELL + COO hub tail) | "sell" (row-length-
        bucketed ELL) for explicit sparse inputs ("dense" / "matfree" otherwise).  The distributed backend
        reports one entry per shard (a tuple; shard_map runs one program, so
        entries agree).  This is the outcome of the ``format="auto"``
        selection (see ``repro.kernels.engine``).
      tridiag: raw Lanczos output (alpha / beta / basis), for diagnostics.
      session_reuse: this solve executed against an already-prepared
        :class:`~repro.api.session.EigenSession` — no coercion, format
        or format conversion was paid (the counters in
        ``partition["spmv"]`` verify it).
      policy_escalations: ``policy="auto"`` attempt trail — one dict per
        ladder rung tried ({policy, max_residual, tol, converged}, cheapest
        first; the last entry is the policy this result executed).  None for
        explicit-policy solves.  The chosen per-phase dtype map rides in
        ``partition["spmv"]["precision"]["phase_map"]``.
      recovery_trail: ``recovery="auto"`` action trail — one dict per
        recovery action taken before this (successful) attempt:
        ``{action, error, kind, iteration, from, to, attempt}`` where
        ``action`` is "reseed" (lucky breakdown → new start vector),
        "escalate_policy" (overflow → one precision rung up),
        "unfuse" (kernel lowering/execution error → reference recurrence),
        or "fallback_chunked" (device OOM → out-of-core engine).  None when
        the solve succeeded first try or recovery was off.
    """

    eigenvalues: jax.Array
    eigenvectors: jax.Array
    residuals: np.ndarray
    converged: np.ndarray
    iterations: int
    restarts: int
    k: int
    n: int
    backend: str
    policy: str
    tol: float
    num_devices: int
    partition: Optional[dict]
    timings: Dict[str, float]
    spmv_format: Optional[object] = None  # str, or tuple of str per shard
    tridiag: Optional[LanczosResult] = None
    session_reuse: bool = False
    policy_escalations: Optional[list] = None
    recovery_trail: Optional[list] = None

    def __iter__(self):
        # scipy.sparse.linalg.eigsh compatibility: ``w, v = eigsh(A, k)``.
        yield self.eigenvalues
        yield self.eigenvectors

    @property
    def all_converged(self) -> bool:
        return bool(np.all(self.converged))

    @property
    def wall_time_s(self) -> float:
        return float(self.timings.get("total_s", 0.0))

    def to_dict(self) -> dict:
        """JSON-safe dict of the result: arrays become nested lists, with
        their dtypes recorded so :meth:`from_dict` can round-trip them.

        ``tridiag`` (the raw Lanczos basis — large and diagnostic-only) is
        dropped.  ``json.dumps(res.to_dict())`` is valid for every backend,
        which is what serving layers and ``benchmarks/run.py`` persist.
        """
        return {
            "schema": 1,
            "eigenvalues": np.asarray(self.eigenvalues, dtype=np.float64).tolist(),
            "eigenvectors": np.asarray(self.eigenvectors, dtype=np.float64).tolist(),
            "residuals": np.asarray(self.residuals, dtype=np.float64).tolist(),
            "converged": np.asarray(self.converged, dtype=bool).tolist(),
            "dtypes": {
                "eigenvalues": str(np.asarray(self.eigenvalues).dtype),
                "eigenvectors": str(np.asarray(self.eigenvectors).dtype),
            },
            "iterations": int(self.iterations),
            "restarts": int(self.restarts),
            "k": int(self.k),
            "n": int(self.n),
            "backend": self.backend,
            "policy": self.policy,
            "tol": float(self.tol),
            "num_devices": int(self.num_devices),
            "partition": _jsonify(self.partition) if self.partition is not None else None,
            "timings": {k: float(v) for k, v in self.timings.items()},
            "spmv_format": _jsonify(self.spmv_format),
            "session_reuse": bool(self.session_reuse),
            "policy_escalations": _jsonify(self.policy_escalations),
            "recovery_trail": _jsonify(self.recovery_trail),
        }

    @classmethod
    def from_dict(cls, d: dict) -> "EigenResult":
        """Rebuild a result from :meth:`to_dict` output (``tridiag`` is None)."""
        dtypes = d.get("dtypes", {})
        ev_dt = jnp.dtype(dtypes.get("eigenvalues", "float32"))
        x_dt = jnp.dtype(dtypes.get("eigenvectors", "float32"))
        fmt = d.get("spmv_format")
        return cls(
            eigenvalues=jnp.asarray(d["eigenvalues"], dtype=ev_dt),
            eigenvectors=jnp.asarray(d["eigenvectors"], dtype=x_dt),
            residuals=np.asarray(d["residuals"], dtype=np.float64),
            converged=np.asarray(d["converged"], dtype=bool),
            iterations=int(d["iterations"]),
            restarts=int(d["restarts"]),
            k=int(d["k"]),
            n=int(d["n"]),
            backend=d["backend"],
            policy=d["policy"],
            tol=float(d["tol"]),
            num_devices=int(d["num_devices"]),
            partition=d.get("partition"),
            timings=dict(d.get("timings", {})),
            spmv_format=tuple(fmt) if isinstance(fmt, list) else fmt,
            tridiag=None,
            session_reuse=bool(d.get("session_reuse", False)),
            policy_escalations=d.get("policy_escalations"),
            recovery_trail=d.get("recovery_trail"),
        )

    @property
    def queue_s(self) -> float:
        """Seconds this query waited in a serving queue before its solve was
        dispatched (0.0 when the result was not produced by a scheduler)."""
        return float(self.timings.get("queue_s", 0.0))

    def summary(self) -> str:
        """One-paragraph human-readable report."""
        lam = np.asarray(self.eigenvalues, dtype=np.float64)
        fmt = self.spmv_format
        if isinstance(fmt, (tuple, list)):
            fmt = fmt[0] if fmt else None
        lines = [
            f"eigsh: k={self.k} n={self.n:,} backend={self.backend} "
            f"policy={self.policy} devices={self.num_devices}"
            + (f" spmv={fmt}" if fmt else ""),
            f"  iterations={self.iterations} restarts={self.restarts} "
            f"tol={self.tol:.1e} converged={int(self.converged.sum())}/{self.k} "
            f"wall={self.wall_time_s:.3f}s",
            f"  |lambda| range [{np.abs(lam).min():.4e}, {np.abs(lam).max():.4e}] "
            f"max residual {self.residuals.max():.2e}",
        ]
        return "\n".join(lines)


def with_queue_time(res: EigenResult, queue_s: float) -> EigenResult:
    """Stamp the serving queue/solve timing split onto a result.

    Returns a copy whose ``timings`` carry ``"queue_s"`` (seconds between
    submission and dispatch — scheduler wait, not solver work) and
    ``"e2e_s"`` (``queue_s + total_s``: the latency the submitter actually
    observed).  ``"total_s"`` / ``"solve_s"`` / ``"prepare_s"`` keep their
    solver-side meaning, so amortization math on them is unaffected.
    """
    t = dict(res.timings)
    t["queue_s"] = float(queue_s)
    t["e2e_s"] = float(queue_s) + float(t.get("total_s", 0.0))
    return dataclasses.replace(res, timings=t)
