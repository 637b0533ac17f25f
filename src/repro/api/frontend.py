"""``eigsh`` — the unified SciPy-style frontend over every solver backend.

One call reproduces the paper's transparency claim: the caller hands over a
problem in whatever form it exists (dense array, CSR, scipy sparse, linear
operator, bare matvec) and the frontend coerces it, picks a precision policy,
dispatches to the right execution engine, and reports the outcome in a single
:class:`EigenResult` schema:

    from repro.api import eigsh
    res = eigsh(A, k=8, policy="FDF", tol=1e-7)
    res.eigenvalues, res.residuals, res.converged, res.backend

``num_iters`` and ``tol`` mean the same thing on every backend:

  * ``num_iters`` — total Lanczos steps the solve may spend (the Krylov
    subspace size for fixed-m backends; a step budget across restarts for
    the restarted backend).
  * ``tol`` — relative Ritz residual target ``|beta_m W[m-1,i]| <=
    tol * |lambda_i|``.  Every backend reports per-pair ``residuals`` and
    ``converged`` flags against it; the restarted backend additionally
    iterates until it holds (or the budget runs out).

Since the plan/execute split (``repro.api.session``), ``eigsh`` is a thin
wrapper: ``prepare(A, ...)`` builds an :class:`~repro.api.session.EigenSession`
owning every per-matrix setup product (coerced input, chosen placement,
converted operators, tuned tiles) and the call executes one query against
it.  A fingerprint-keyed cache of recent sessions makes naive repeated
calls on the same matrix hit the prepared path transparently — the second
byte-identical call performs zero format conversions and zero tuner probes
(verified by the counters in ``EigenResult.partition["spmv"]``, flagged by
``EigenResult.session_reuse``).  For many-query workloads, use
:func:`repro.api.prepare` / :func:`repro.api.eigsh_many` directly.
"""

from __future__ import annotations

import dataclasses
import itertools
import math
import warnings
from collections.abc import Mapping
from typing import Optional, Union

import jax.numpy as jnp

from ..core.precision import POLICIES, PrecisionPolicy
from ..kernels.engine import FORMATS
from ..tracing import span
from .result import EigenResult

__all__ = ["SolverConfig", "eigsh", "resolve_policy", "is_auto_policy"]

# Process-wide sequence number of ``eigsh`` calls: the ``request`` stat of
# the ``repro.eigsh`` span, which every span of the call nests under.
_REQUEST_IDS = itertools.count()


def is_auto_policy(policy) -> bool:
    """True for the ``policy="auto"`` sentinel: not a resolvable policy but a
    request for the accuracy-driven escalation ladder (see ``eigsh``)."""
    return isinstance(policy, str) and policy.strip().lower() == "auto"


def resolve_policy(policy: Union[str, Mapping, PrecisionPolicy]) -> PrecisionPolicy:
    """Resolve a precision-policy spec to a :class:`PrecisionPolicy`.

    Accepts a name from ``POLICIES`` (case-insensitive: "FDF", "bcf", ...),
    a ``PrecisionPolicy`` instance, or a phase-override mapping
    ``{"base": "FDF", "reorth": "f32", ...}`` (``base`` defaults to "FDF";
    the other keys are per-phase compute dtypes — an unknown phase key is a
    named error listing the valid phases, never a raw ``KeyError``).
    ``"auto"`` is a selection *mode*, not a policy: resolving it is an error
    pointing back at ``eigsh(policy="auto")``.
    """
    if isinstance(policy, PrecisionPolicy):
        return policy
    if isinstance(policy, str):
        if is_auto_policy(policy):
            raise ValueError(
                'policy="auto" is the accuracy-driven selection mode, not a '
                "resolvable policy — pass it to eigsh()/EigenSession.eigsh() "
                "(ideally with tol=) and the solver escalates through "
                "repro.core.precision.auto_ladder()"
            )
        try:
            return POLICIES[policy.strip().upper()]
        except KeyError:
            raise ValueError(
                f"unknown precision policy {policy!r}; known: {sorted(POLICIES)} "
                "(case-insensitive), \"auto\", or a {'base': name, <phase>: dtype} "
                "mapping"
            ) from None
    if isinstance(policy, Mapping):
        spec = dict(policy)
        base = resolve_policy(spec.pop("base", "FDF"))
        # with_phases validates the remaining keys against PHASES by name.
        return base.with_phases(**spec)
    raise TypeError(
        f"policy must be a str, PrecisionPolicy, or phase-override mapping, "
        f"got {type(policy).__name__}"
    )


@dataclasses.dataclass(frozen=True)
class SolverConfig:
    """All solver knobs of :func:`eigsh` as one reusable value.

    Useful for sweeping configurations (benchmarks) and for services that
    pin a tuned configuration: ``eigsh(A, k, config=cfg)``.  The subset of
    fields that affects what a session *builds* (``backend``, ``format``,
    ``chunk_nnz``, ``stage_depth``, ``axis``) keys the session cache; the
    rest are per-query defaults.
    """

    policy: Union[str, PrecisionPolicy] = "FDF"
    backend: str = "auto"
    # None = the paper's per-engine default: "half" on the single-device /
    # chunked paths (Alg. 1's parity scheme), "full" on the distributed path
    # (their multi-GPU configuration).
    reorth: Optional[str] = None
    tol: Optional[float] = None
    num_iters: Optional[int] = None
    subspace: Optional[int] = None  # restarted backend: m (defaults to max(2k, k+8))
    max_restarts: int = 30
    seed: int = 0
    # SpMV layout for explicit sparse inputs: "auto" selects COO / ELL /
    # blocked-ELL(BSR) / hybrid(ELL+COO hub split) from matrix statistics
    # (repro.kernels.engine); an explicit value forces it.  The decision
    # lands in EigenResult.spmv_format.
    format: str = "auto"
    chunk_nnz: int = 1 << 20  # chunked backend: device-resident nnz per chunk
    stage_depth: int = 1  # chunked backend: chunks prefetched ahead of compute
    # Chunked backend: how staged ELL chunks travel host -> device.  "f32"
    # ships plain storage-dtype buffers; "bf16"/"fp8" quantize values (with
    # per-row-block scales) and delta-encode columns, decompressed in-kernel
    # (kernels/spmv_ell_packed) for 2-4x effective staging bandwidth; "auto"
    # packs when the policy's storage dtype is already narrow.
    staging: str = "f32"
    jacobi: str = "host"  # phase-2 placement, "host" (paper) or "jax"
    axis: str = "data"  # mesh axis name for the distributed backend
    # Breakdown handling: "raise" (default — the in-loop health probe turns
    # NaN/Inf and beta underflow into a typed NumericalBreakdown), "auto"
    # (probe + escalate: reseed / precision rung up / unfuse / chunked
    # fallback, trail on EigenResult.recovery_trail), or "none" (legacy:
    # probes off, garbage flows through).  Per-query override via
    # eigsh(recovery=...).  Deliberately NOT a _LAYOUT_FIELDS member: it
    # never changes what a session builds.
    recovery: Optional[str] = None
    # Solve checkpointing (restarted + chunked engines): a directory enables
    # periodic snapshots via serving.store.SolveCheckpoint; interrupted
    # solves resume from the last completed restart cycle / step block.
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 8  # chunked host loop: steps between snapshots


def _resolve_reorth(reorth: Optional[str], backend: str) -> str:
    """None -> the paper's configuration for the engine that will run."""
    if reorth is not None:
        return reorth
    return "full" if backend == "distributed" else "half"


def _default_tol(policy: PrecisionPolicy) -> float:
    """Reporting tolerance when the caller didn't give one: sqrt(eps) of the
    compute dtype — the classical 'converged for this arithmetic' line."""
    try:
        return float(math.sqrt(float(jnp.finfo(policy.compute).eps)))
    except (TypeError, ValueError):
        return 1e-6


# Legacy ``impl=`` spellings -> the ``format=`` knob that replaced them.  The
# fixed per-impl operator plumbing below the frontend is gone; these now run
# through the SpmvEngine layer like everything else.
_IMPL_TO_FORMAT = {
    "coo": "coo",
    "ell": "ell",
    "ell_kernel": "ell",
    "bsr_kernel": "bsr",
}


def eigsh(
    A,
    k: int = 6,
    *,
    config: Optional[SolverConfig] = None,
    policy: Union[str, PrecisionPolicy] = "FDF",
    backend: str = "auto",
    reorth: Optional[str] = None,
    tol: Optional[float] = None,
    num_iters: Optional[int] = None,
    v0=None,
    seed: int = 0,
    n: Optional[int] = None,
    subspace: Optional[int] = None,
    max_restarts: int = 30,
    format: str = "auto",
    impl: Optional[str] = None,
    chunk_nnz: int = 1 << 20,
    stage_depth: int = 1,
    staging: str = "f32",
    jacobi: str = "host",
    mesh=None,
    axis: str = "data",
    recovery: Optional[str] = None,
    checkpoint_dir: Optional[str] = None,
    checkpoint_every: int = 8,
) -> EigenResult:
    """Top-K eigenpairs (largest |lambda|) of a symmetric operator.

    Args:
      A: dense array, ``repro.sparse.CSR``, scipy sparse matrix, a
        ``repro.sparse.DiskCSR`` mapping or the path of a ``save_diskcsr``
        directory (out-of-core: the matrix streams from disk and is never
        fully materialized), ``LinearOperator`` (ours or scipy's), or a bare
        matvec callable (then pass ``n=``).
      k: number of eigenpairs.
      config: a :class:`SolverConfig` carrying every solver knob below; when
        given, the individual keyword arguments are ignored (``v0`` / ``n`` /
        ``mesh`` are per-call and always honored).
      policy: precision policy name (see ``repro.core.POLICIES``,
        case-insensitive), a ``PrecisionPolicy`` instance, a phase-override
        mapping ``{"base": "FDF", "reorth": "f32", ...}`` (per-phase compute
        dtypes — see ``repro.core.precision.PHASES``), or ``"auto"``: an
        accuracy-driven selector that probes the escalation ladder
        BFF -> FFF -> FCF (-> FDF -> DDD under x64) cheapest-first and stops
        at the first policy whose measured residuals meet ``tol`` (each
        rung's own default tol when none is given).  The attempt trail is
        returned as ``EigenResult.policy_escalations`` and the chosen phase
        map in ``partition["spmv"]["precision"]``.
      backend: "auto" (dispatch on input size / device count / memory
        pressure — see ``repro.api.dispatch``) or one of "single",
        "distributed", "restarted", "chunked".
      reorth: re-orthogonalization mode ("none" | "half" | "full" | "full2");
        None picks the paper's configuration for the engine that runs
        ("half" single-device/chunked, "full" distributed).  The restarted
        backend always re-orthogonalizes fully (anything else is ignored
        with a warning).
      tol: relative Ritz residual target; selects the restarted backend under
        "auto" and defines the ``converged`` flags everywhere.  When the
        restarted backend runs without an explicit tol, it iterates toward
        the same default the flags are judged against
        (``sqrt(eps(compute))``).
      num_iters: total Lanczos step budget (defaults to ``k`` on fixed-m
        backends, ``subspace + restarts * (subspace - k)`` on restarted).
      v0: optional start vector (length n).
      n: problem size, required only for bare callables.
      subspace: restarted backend's subspace size m.
      max_restarts: restart cap (ignored when ``num_iters`` already caps it).
      format: SpMV layout for explicit sparse matrices — "auto" (default)
        picks COO vs ELL vs blocked-ELL/BSR vs hybrid (quantile-capped ELL
        plus a COO hub tail — how power-law matrices reach the kernel path)
        from cheap row-length and block-density statistics
        (``repro.kernels.engine``); "coo" / "ell" / "bsr" / "hybrid" force
        one.  The kernel formats execute through the Pallas SpMV kernels
        (interpret mode off-TPU); the executed choice is reported as
        ``EigenResult.spmv_format``.  The distributed backend auto-selects
        kernel formats only (pass format="coo" to opt back into
        ``segment_sum``); the chunked backend supports "coo" / "ell".
      impl: DEPRECATED — the legacy fixed SpMV knob now maps onto ``format=``
        ("ell"/"ell_kernel" -> "ell", "bsr_kernel" -> "bsr", "coo" -> "coo")
        with a ``DeprecationWarning``; the per-impl operator plumbing it
        selected is gone.  Pass ``format=`` directly.
      chunk_nnz: chunk size (nnz) for the out-of-core backend.
      stage_depth: out-of-core double buffering — how many chunks the
        chunked backend prefetches (``jax.device_put``) ahead of the chunk
        being computed on; device residency is bounded by ``stage_depth +
        1`` chunks.  0 disables the overlap.  Staging counters are reported
        in ``EigenResult.partition["staging"]``.
      staging: out-of-core staged-chunk encoding — "f32" (plain), "bf16" /
        "fp8" (quantized values + delta-encoded columns, decompressed
        in-kernel; multiplies effective staging bandwidth), or "auto" (pack
        iff the policy's storage dtype is already narrow).  Bytes staged,
        effective bandwidth, and compression ratio are reported in
        ``EigenResult.partition["spmv"]["staging"]``.
      jacobi: phase-2 Jacobi placement ("host" = the paper's, or "jax").
      mesh: optional ``jax.sharding.Mesh``; passing one under
        ``backend="auto"`` is an explicit request for the distributed
        backend (the default mesh is all visible devices on one axis named
        ``axis``).
      recovery: breakdown handling — None/"raise" (default): the health
        probe raises a typed ``NumericalBreakdown`` instead of returning
        NaN eigenpairs; "auto": catch and escalate (re-seed on lucky
        breakdown, one precision rung up on overflow, fused->unfused on
        kernel errors, single->chunked on device OOM) with the action
        trail on ``EigenResult.recovery_trail``; "none": legacy behavior,
        probes off.
      checkpoint_dir: directory for periodic solve snapshots (restarted +
        chunked engines); an interrupted run with the same matrix + solve
        parameters resumes from its last snapshot bit-identically.
      checkpoint_every: chunked host loop — Lanczos steps between snapshots.

    Returns:
      An :class:`EigenResult` with an identical schema on every backend.
      Repeated calls on a byte-identical matrix + layout config reuse the
      cached :class:`~repro.api.session.EigenSession` (``session_reuse`` is
      set, ``timings["prepare_s"]`` drops to 0); see the module docstring.
    """
    pol = config.policy if config is not None else policy
    pol_name = getattr(pol, "name", None) or str(pol)
    with span("repro.eigsh", request=next(_REQUEST_IDS), k=k, policy=pol_name):
        if impl is not None:
            warnings.warn(
                "impl= is deprecated and now maps onto format= (impl='ell'/"
                "'ell_kernel' -> format='ell', 'bsr_kernel' -> format='bsr', "
                "'coo' -> format='coo'); the legacy fixed SpMV paths are gone — "
                "pass format= directly",
                DeprecationWarning,
                stacklevel=2,
            )
            mapped = _IMPL_TO_FORMAT.get(impl)
            if mapped is None:
                raise ValueError(
                    f"unknown legacy impl {impl!r}; expected one of {sorted(_IMPL_TO_FORMAT)}"
                )
            if format == "auto":
                # impl defaults to None now, so an explicit impl="coo" is a real
                # request for the segment-sum reference path and must pin it.
                format = mapped
        cfg = config or SolverConfig(
            policy=policy,
            backend=backend,
            reorth=reorth,
            tol=tol,
            num_iters=num_iters,
            subspace=subspace,
            max_restarts=max_restarts,
            seed=seed,
            format=format,
            chunk_nnz=chunk_nnz,
            stage_depth=stage_depth,
            staging=staging,
            jacobi=jacobi,
            axis=axis,
            recovery=recovery,
            checkpoint_dir=checkpoint_dir,
            checkpoint_every=checkpoint_every,
        )
        if k < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        if cfg.format not in ("auto",) + FORMATS:
            raise ValueError(
                f"unknown SpMV format {cfg.format!r}; expected 'auto' or one of {FORMATS}"
            )

        from .session import get_session  # lazy: session imports this module

        session, _hit = get_session(A, cfg, mesh=mesh, n=n)
        # Per-query fields come from THIS call's config — a cached session may
        # have been prepared under different solver defaults.  Routed through
        # eigsh_many(defaults=cfg) so non-query knobs that must bind per call
        # (recovery, checkpoint_dir) resolve against THIS config too.
        from .session import EigQuery

        q = EigQuery(
            k=k,
            policy=cfg.policy,
            tol=cfg.tol,
            num_iters=cfg.num_iters,
            reorth=cfg.reorth,
            v0=v0,
            seed=cfg.seed,
            subspace=cfg.subspace,
            max_restarts=cfg.max_restarts,
            jacobi=cfg.jacobi,
            recovery=cfg.recovery,
        )
        return session.eigsh_many([q], defaults=cfg)[0]
