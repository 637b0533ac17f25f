"""Phase 1 of the paper's eigensolver: the Lanczos algorithm (Algorithm 1).

Builds a Krylov basis V = [v_1 .. v_m] of a symmetric operator and the
tridiagonal matrix T = tridiag(beta, alpha, beta) whose eigenpairs
approximate the Top-K eigenpairs of the operator.

Mixed precision follows the paper exactly (§III-A): the basis V and the
carried vectors are kept in ``policy.storage``; SpMV accumulation and the
alpha / beta / re-orthogonalization reductions run in ``policy.compute`` —
or, per phase, in the policy's ``spmv`` / ``alpha_beta`` / ``reorth``
overrides (``core/precision.PHASES``), with every phase result rounded back
to the carried ``compute`` dtype at the phase boundary.

Re-orthogonalization modes:
  * ``"none"`` — plain three-term recurrence;
  * ``"half"`` — the paper's scheme (Alg. 1 lines 12-21): the new vector is
    re-orthogonalized against every *other* stored Lanczos vector
    (alternating parity), matching their quoted O(n K^2 / 2) cost;
  * ``"full"`` — classical full re-orthogonalization against all stored
    vectors (beyond-paper reference point).

The loop body is generic over an ``Ops`` record so the same code runs
single-device (plain reductions) and multi-device (psum reductions inside
``shard_map`` — see ``core/distributed.py``).

Three memory-roofline optimizations ride on the record (beyond-paper):

  * ``fused_update`` — the three-term recurrence + squared norm execute as
    ONE pass over the n-length vectors through the Pallas kernel in
    ``kernels/lanczos_update.py`` (policy-gated: compensated policies keep
    the reference reductions; f64 compute falls back to ``kernels/ref.py``
    inside the wrapper).
  * ``fused_iteration`` — the whole SpMV + alpha + update (+ norm) step in
    two passes over the Krylov vectors (``kernels/lanczos_fused.py`` chained
    with the update kernel); ELL operators only.
  * ``project_out`` — the masked re-orthogonalization casts the stored basis
    to the compute dtype ONCE per pass (coefficients and subtraction reuse
    the same masked cast) instead of materializing two full (m, n) copies.

Which of these actually runs is a **measured decision**: the engine's
:class:`~repro.kernels.engine.IterationPlan` (whole-iteration autotuner, or
its static mode table) routes the update via :func:`resolve_update_mode`.
``REPRO_FUSED_LANCZOS=0`` force-disables all fusion; ``=1`` force-enables the
fused update; ``REPRO_ITER_UPDATE`` pins the exact mode at the plan layer.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..configs import env as envcfg
from ..testing import faults as _faults
from .precision import PrecisionPolicy, basis_matmul, compensated_sum, exact_matmuls

__all__ = [
    "LanczosResult",
    "NumericalBreakdown",
    "check_tridiag_health",
    "lanczos_tridiag",
    "lanczos_tridiag_multi",
    "make_local_ops",
    "ops_for_operator",
    "fused_update_enabled",
    "resolve_update_mode",
    "Ops",
]


class NumericalBreakdown(ArithmeticError):
    """The Lanczos recurrence produced values no downstream phase can use.

    ``kind`` is the breakdown taxonomy the recovery layer dispatches on:

    * ``"nonfinite"`` — NaN/Inf in alpha, beta, or the residual norm; the
      shape of low-precision overflow (bf16/fp8 rungs) or a poisoned SpMV.
      Recovery re-runs one precision rung up the ladder.
    * ``"beta_underflow"`` — beta collapsed to ~0 *before* the final step:
      the classical "lucky breakdown" (the start vector hit an invariant
      subspace too early).  Recovery re-seeds the start vector.

    ``iteration`` is the first offending step, ``policy`` the precision
    policy name the sweep ran under.
    """

    def __init__(self, kind: str, iteration: int, policy: Optional[str] = None, detail: str = ""):
        self.kind = kind
        self.iteration = iteration
        self.policy = policy
        self.recovery_trail: Optional[list] = None  # stamped when recovery gives up
        msg = f"Lanczos breakdown: {kind} at iteration {iteration}"
        if policy:
            msg += f" under policy {policy}"
        if detail:
            msg += f" ({detail})"
        super().__init__(msg)


def check_tridiag_health(result: "LanczosResult", policy: PrecisionPolicy) -> None:
    """Post-sweep health probe: raise :class:`NumericalBreakdown` instead of
    letting garbage flow into the Ritz phase.

    Cost is O(m) host work on the already-materialized tridiagonal scalars
    (the (m, n) basis is never touched), so it is ~free next to the sweep.
    ``beta_last`` is checked for non-finiteness only: a *small* final
    residual norm means the subspace converged, which is success, not
    breakdown.  Multi-start (vmapped) results are checked flattened.
    """
    import numpy as np

    pol = getattr(policy, "name", None) or str(policy)
    alpha = np.asarray(result.alpha, dtype=np.float64).reshape(-1)
    beta = np.asarray(result.beta, dtype=np.float64).reshape(-1)
    m = result.alpha.shape[-1]
    tiny = float(jnp.finfo(policy.effective().compute).tiny) * 1e3
    # A breakdown cascades (beta ~ 0 at step i makes alpha at step i+1
    # non-finite), so find the EARLIEST offending step across all checks —
    # that is the one whose kind the recovery layer must dispatch on.
    found = []  # (iteration, priority, kind, detail)
    bad = ~np.isfinite(alpha)
    if bad.any():
        j = int(np.argmax(bad))
        found.append((j % m, 0, "nonfinite", f"alpha[{j % m}]={alpha[j]!r}"))
    bad = ~np.isfinite(beta)
    if bad.any():
        j = int(np.argmax(bad))
        found.append((j % max(m - 1, 1), 0, "nonfinite", f"beta[{j % max(m - 1, 1)}]={beta[j]!r}"))
    if result.beta_last is not None:
        bl = np.asarray(result.beta_last, dtype=np.float64).reshape(-1)
        if not np.isfinite(bl).all():
            found.append((m - 1, 0, "nonfinite", "beta_last"))
    small = beta <= tiny
    if small.any():
        j = int(np.argmax(small))
        found.append(
            (j % max(m - 1, 1), 1, "beta_underflow", f"beta={beta[j]:.3e} <= {tiny:.3e}")
        )
    if found:
        i, _, kind, detail = min(found)
        raise NumericalBreakdown(kind, i, pol, detail)


class LanczosResult(NamedTuple):
    alpha: jax.Array  # (m,) compute dtype — diagonal of T
    beta: jax.Array  # (m-1,) compute dtype — off-diagonal of T
    basis: jax.Array  # (m, n) storage dtype — Lanczos vectors (V), row-major
    # norm of the residual after the final step: the scale of the classical
    # Ritz residual bound |beta_m * W[m-1, i]| used for convergence reporting
    beta_last: Optional[jax.Array] = None


@dataclasses.dataclass(frozen=True)
class Ops:
    """Arithmetic kernel set; distributed variants psum the reductions."""

    matvec: Callable[[jax.Array], jax.Array]  # storage-in, compute-out
    dot: Callable[[jax.Array, jax.Array], jax.Array]  # compute-dtype scalar
    gram: Callable[[jax.Array, jax.Array], jax.Array]  # (m,n)@(n,) -> (m,)
    # (basis, u, mask) -> u minus its projection onto the masked rows;
    # None falls back to the legacy gram-based two-cast path.
    project_out: Optional[Callable] = None
    # (w, v, v_prev, alpha, beta, need_norm) -> (w - alpha v - beta v_prev,
    # ||.||^2) in one memory pass; None keeps the separate recurrence + dot.
    # ``need_norm=False`` tells distributed variants the caller will discard
    # the norm (reorth recomputes beta), so they must not psum it.
    fused_update: Optional[Callable] = None
    # (v, v_prev, beta_prev, need_norm) -> (u, alpha, ||u||^2 or None): the
    # whole SpMV + alpha + three-term update step in two fused passes.  When
    # set it subsumes matvec/dot/fused_update in the loop body.
    fused_iteration: Optional[Callable] = None


def fused_update_enabled(policy: PrecisionPolicy) -> bool:
    """Policy gate for the fused Pallas update: compensated policies need
    the compensated reductions for beta, so they keep the reference path,
    and a per-phase ``alpha_beta`` override splits the fused norm's dtype
    away from the recurrence's, so it keeps the reference path too;
    ``REPRO_FUSED_LANCZOS=0`` is the kill switch."""
    if not envcfg.get_bool("REPRO_FUSED_LANCZOS"):
        return False
    if policy.compensated:
        return False
    return jnp.dtype(policy.phase_dtype("alpha_beta")) == jnp.dtype(policy.compute)


def resolve_update_mode(policy: PrecisionPolicy, plan=None, fused: Optional[bool] = None) -> str:
    """How the three-term update (and SpMV fusion) should run for this solve.

    Layered decision:
      1. an explicit ``fused=`` pin from the caller wins (legacy knob — e.g.
         the vmapped multi-start path pins ``False``);
      2. the policy gate (:func:`fused_update_enabled`, which also honors the
         ``REPRO_FUSED_LANCZOS=0`` kill switch) can force "unfused";
      3. ``REPRO_FUSED_LANCZOS=1`` *explicitly set* force-enables fusion (the
         pre-plan default behavior, kept for A/B runs);
      4. otherwise the engine's measured :class:`IterationPlan` decides, or —
         with no plan in scope — the static mode table for the execution mode
         (interpret -> unfused: the Pallas interpreter's per-grid-step
         overhead makes fused kernels lose there; compiled -> fused).
    """
    if fused is not None:
        return "fused" if (fused and fused_update_enabled(policy)) else "unfused"
    if not fused_update_enabled(policy):
        return "unfused"
    pin = (envcfg.get_str("REPRO_ITER_UPDATE") or "").strip().lower()
    if pin:
        # Same pin resolve_iteration_plan honors — re-checked here so it
        # also reaches warm sessions whose plan was built before the pin.
        from ..kernels.engine import ITER_UPDATE_MODES

        if pin not in ITER_UPDATE_MODES:
            raise ValueError(
                f"REPRO_ITER_UPDATE={pin!r}: expected one of {ITER_UPDATE_MODES}"
            )
        return pin
    env = (envcfg.raw("REPRO_FUSED_LANCZOS") or "").strip().lower()
    if env in ("1", "true", "on", "yes"):
        if plan is not None and plan.update != "unfused":
            return plan.update
        return "fused"
    if plan is not None:
        return plan.update
    from ..kernels.engine import table_update_mode
    from ..kernels.ops import default_interpret

    return table_update_mode(default_interpret())


def _local_reduce(x: jax.Array, policy: PrecisionPolicy, dtype=None) -> jax.Array:
    if policy.compensated:
        return compensated_sum(x.reshape(-1), dtype or policy.compute)
    return jnp.sum(x)


def _make_fused_iteration(operator, policy: PrecisionPolicy) -> Optional[Callable]:
    """Whole-iteration fused step for an ELL-backed operator, or None.

    Requires the operator to expose its :class:`DeviceELL` container and
    engine (``SparseOperator`` does), an engine that runs the SpMV kernels
    (interpret mode: compiled TPU runs the SpMV as XLA, see
    ``kernels.engine.spmv_runs_pallas``), and the spmv-phase accumulation
    dtype to match the carried compute dtype — the kernel's in-pass alpha
    replaces ``dot(v, w)``, so a phase split there would change what alpha
    means.
    """
    from ..kernels.engine import spmv_runs_pallas  # lazy: core sits below kernels

    eng = getattr(operator, "engine", None)
    mat = getattr(operator, "mat", None)
    if eng is None or mat is None or eng.format != "ell" or not spmv_runs_pallas(eng.interpret):
        return None
    from ..kernels import ops as kops
    from ..sparse.formats import DeviceELL

    if not isinstance(mat, DeviceELL):
        return None
    cdt, sdt = policy.compute, policy.storage
    acc = jnp.dtype(policy.phase_dtype("spmv"))
    if acc != jnp.dtype(cdt):
        return None
    from ..kernels.engine import _fit_tile

    # Same divisibility clamp the engine's ell_matvec applies to its tiles.
    block_r = _fit_tile(eng.tiles.block_r, mat.val.shape[0])
    block_w = _fit_tile(eng.tiles.block_w, mat.val.shape[1])

    def fused_iteration(v, v_prev, beta, need_norm=True):
        # Pass 1: w = A v with alpha = <v, w> folded into the width sweep.
        w, alpha = kops.spmv_ell_alpha(
            mat,
            v.astype(sdt),
            v,
            accum_dtype=acc,
            block_r=block_r,
            block_w=block_w,
            interpret=eng.interpret,
        )
        alpha = alpha.astype(cdt)
        # Pass 2: three-term update + squared norm in one pass.
        u, nrm = kops.lanczos_update(w.astype(cdt), v, v_prev, alpha, beta, accum_dtype=cdt)
        return u, alpha, nrm

    return fused_iteration


def make_local_ops(
    matvec: Callable,
    policy: PrecisionPolicy,
    fused: Optional[bool] = None,
    plan=None,
    operator=None,
) -> Ops:
    """Single-device ops: plain reductions in the per-phase compute dtypes
    (``alpha_beta`` for dot, ``reorth`` for gram/project_out); every result
    is cast back to the carried ``compute`` dtype, so a policy with no phase
    overrides is bit-identical to the pre-phase uniform arithmetic.

    ``plan`` (an :class:`~repro.kernels.engine.IterationPlan`) and the legacy
    ``fused`` pin route the update through :func:`resolve_update_mode`;
    ``operator`` enables the fully-fused SpMV+alpha pass when the plan asks
    for it and the operator exposes an ELL layout.
    """
    cdt = policy.compute
    abdt = policy.phase_dtype("alpha_beta")
    rdt = policy.phase_dtype("reorth")

    def dot(a, b):
        return _local_reduce(a.astype(abdt) * b.astype(abdt), policy, abdt).astype(cdt)

    def gram(vs, u):
        return basis_matmul(vs.astype(rdt), u.astype(rdt)).astype(cdt)

    def project_out(basis, u, mask):
        basis_c = basis.astype(rdt) * mask.astype(rdt)[:, None]  # ONE (m, n) cast
        # u rounds through the storage dtype before the coefficient dot —
        # the same policy semantics the legacy gram path applied (the
        # fig4 precision ablation measures exactly this rounding).
        coeffs = basis_matmul(basis_c, u.astype(policy.storage).astype(rdt))
        return (u.astype(rdt) - basis_matmul(coeffs, basis_c)).astype(cdt)

    mode = resolve_update_mode(policy, plan=plan, fused=fused)
    fused_iteration = None
    if mode == "fused_spmv":
        fused_iteration = _make_fused_iteration(operator, policy)
        if fused_iteration is None:
            mode = "fused"  # operator can't supply the fused pass: next rung
    fused_update = None
    if mode in ("fused", "fused_spmv") and fused_iteration is None:
        from ..kernels import ops as kops  # lazy: core sits below kernels

        # The operator's engine decides interpret vs compiled, like its SpMV.
        eng = getattr(operator, "engine", None)
        kw = {} if eng is None else {"interpret": eng.interpret}

        def fused_update(w, v, v_prev, alpha, beta, need_norm=True):
            return kops.lanczos_update(w, v, v_prev, alpha, beta, accum_dtype=cdt, **kw)

    return Ops(
        matvec=matvec, dot=dot, gram=gram, project_out=project_out,
        fused_update=fused_update, fused_iteration=fused_iteration,
    )


def ops_for_operator(operator, policy: PrecisionPolicy, fused: Optional[bool] = None) -> Ops:
    """Ops for a :class:`LinearOperator`, routed by its engine's measured
    :class:`IterationPlan` (operators without an engine fall back to the
    static mode table)."""
    eng = getattr(operator, "engine", None)
    plan = getattr(eng, "iteration_plan", None)
    return make_local_ops(
        operator.bound_matvec(policy), policy, fused=fused, plan=plan, operator=operator
    )


def _reorth_mask(m: int, i: jax.Array, mode: str, dtype) -> jax.Array:
    """Mask over stored vector indices j (0-based) used for re-orth at step i."""
    j = jnp.arange(m)
    stored = j <= i  # vectors written so far (includes current v_i)
    if mode == "none":
        return jnp.zeros((m,), dtype)
    if mode == "half":
        # Paper's parity scheme (Alg. 1 lines 13-18): re-orthogonalize
        # against the odd-indexed (1-based) half of the basis.  Cost: the
        # paper's quoted O(n K^2 / 2).
        return (stored & (j % 2 == 0)).astype(dtype)
    if mode == "half_alt":
        # Variant: alternate the parity with the step index (both halves
        # cleaned on consecutive steps).  Empirically less stable -- see
        # EXPERIMENTS.md SReorth.
        return (stored & (j % 2 == i % 2)).astype(dtype)
    if mode in ("full", "full2"):
        return stored.astype(dtype)
    raise ValueError(f"unknown reorth mode {mode!r}")


@partial(jax.jit, static_argnames=("ops", "num_iters", "policy", "reorth", "fault_key"))
def _lanczos_jit(v1, ops: Ops, num_iters: int, policy: PrecisionPolicy, reorth: str, fault_key=None):
    # fault_key is unused in the computation: it exists so an armed fault
    # (read at trace time inside the loop body) retraces under its own cache
    # key and the poisoned executable never shadows the clean one.
    return _lanczos_loop(v1, ops, num_iters, policy, reorth)


def _lanczos_loop(
    v1,
    ops: Ops,
    num_iters: int,
    policy: PrecisionPolicy,
    reorth: str,
    host_loop: bool = False,
    checkpoint=None,
):
    m = num_iters
    n = v1.shape[0]
    cdt, sdt = policy.compute, policy.storage
    tiny = jnp.asarray(jnp.finfo(cdt).tiny * 1e3, cdt)

    v1 = v1.astype(cdt)
    v1 = v1 / jnp.sqrt(ops.dot(v1, v1))

    basis0 = jnp.zeros((m, n), sdt)
    alphas0 = jnp.zeros((m,), cdt)
    betas0 = jnp.zeros((m,), cdt)

    def body(i, carry):
        basis, alphas, betas, v_prev, w, beta_prev = carry
        # --- normalize the incoming vector (paper lines 5-7) ---
        v = jnp.where(i == 0, v1, w / jnp.maximum(beta_prev, tiny))
        basis = jax.lax.dynamic_update_slice(basis, v.astype(sdt)[None, :], (i, 0))
        nrm_sq = None
        if ops.fused_iteration is not None:
            # --- lines 9-11 in two fused passes: SpMV + alpha in one kernel,
            # update + norm in the other (each Krylov vector read once) ---
            u, alpha, fused_nrm = ops.fused_iteration(
                v, v_prev, beta_prev, need_norm=(reorth == "none")
            )
            u = _faults.tap_spmv(u, i)
            alphas = alphas.at[i].set(alpha)
            if reorth == "none":
                nrm_sq = fused_nrm
        else:
            # --- projection (line 9): SpMV in compute precision ---
            u = ops.matvec(v.astype(sdt)).astype(cdt)
            u = _faults.tap_spmv(u, i)
            # --- alpha (line 10): sync point A ---
            alpha = ops.dot(v, u)
            alphas = alphas.at[i].set(alpha)
            # --- three-term recurrence (line 11): one fused memory pass when
            # the plan asks for it (the kernel also yields ||u||^2 for free) ---
            if ops.fused_update is not None:
                u, fused_nrm = ops.fused_update(
                    u, v, v_prev, alpha, beta_prev, need_norm=(reorth == "none")
                )
                if reorth == "none":
                    nrm_sq = fused_nrm
            else:
                u = u - alpha * v - beta_prev * v_prev
        # --- re-orthogonalization (lines 12-21): sync point C ---
        if reorth != "none":
            mask = _reorth_mask(m, i, reorth, cdt)
            passes = 2 if reorth == "full2" else 1  # CGS2: "twice is enough"
            for _ in range(passes):
                if ops.project_out is not None:
                    u = ops.project_out(basis, u, mask)
                else:
                    coeffs = ops.gram(basis, u.astype(sdt)) * mask  # (m,)
                    u = u - basis_matmul(coeffs, basis.astype(cdt))
        # --- beta (line 6, next iteration): sync point B ---
        if nrm_sq is not None:
            beta = jnp.sqrt(jnp.maximum(nrm_sq.astype(cdt), 0.0))
        else:
            beta = jnp.sqrt(jnp.maximum(ops.dot(u, u), 0.0))
        beta = _faults.tap_beta(beta, i)
        betas = betas.at[i].set(beta)
        return (basis, alphas, betas, v, u, beta)

    init = (basis0, alphas0, betas0, jnp.zeros((n,), cdt), jnp.zeros((n,), cdt), jnp.zeros((), cdt))
    if host_loop:
        # Eager Python loop: required by operators whose matvec must execute
        # host-side per step (ChunkedOperator streams chunks through the
        # device; tracing it would bake every chunk into one executable and
        # defeat the bounded-residency staging).
        carry = init
        start = 0
        ckpt_op = None
        if checkpoint is not None:
            store, token, every, *rest = checkpoint
            # Optional 4th element: a ChunkedOperator whose streamed matvec
            # checkpoints its *chunk cursor* mid-step — a crash between chunk
            # stagings inside one step no longer loses the whole step.
            ckpt_op = rest[0] if rest else None
            if ckpt_op is not None and not hasattr(ckpt_op, "set_resume"):
                ckpt_op = None
            state = store.load(token)
            if (
                state is not None
                and state.get("engine") == "lanczos"
                and int(state.get("n", -1)) == n
                and int(state.get("m", -1)) == m
            ):
                carry = (
                    jnp.asarray(state["basis"], sdt),
                    jnp.asarray(state["alphas"], cdt),
                    jnp.asarray(state["betas"], cdt),
                    jnp.asarray(state["v_prev"], cdt),
                    jnp.asarray(state["w"], cdt),
                    jnp.asarray(state["beta_prev"], cdt),
                )
                if state.get("chunk") is not None and ckpt_op is not None:
                    # Mid-step snapshot: the carry above is the STEP-START
                    # carry of step i; re-enter step i with the matvec armed
                    # to skip already-accumulated chunks.  Chunk order is
                    # fixed, so the resumed sweep is bit-identical.
                    start = int(state["i"])
                    ckpt_op.set_resume(
                        int(state["chunk"]) + 1, jnp.asarray(state["partial"])
                    )
                else:
                    start = int(state["i"]) + 1
        ck_chunk_every = 0
        if ckpt_op is not None and getattr(ckpt_op, "num_chunks", 1) > 1:
            from ..configs import env as _envcfg

            ck_chunk_every = _envcfg.get_int("REPRO_CHUNK_CKPT_EVERY")
        for i in range(start, m):
            if ck_chunk_every > 0:
                basis_s, alphas_s, betas_s, v_prev_s, w_s, beta_prev_s = carry

                def _chunk_hook(c, partial, _i=i):
                    if (c + 1) % ck_chunk_every or c + 1 >= ckpt_op.num_chunks:
                        return  # end-of-step save covers the final chunk
                    store.save(
                        token,
                        {
                            "engine": "lanczos",
                            "i": _i,
                            "n": n,
                            "m": m,
                            "chunk": c,
                            "partial": partial,
                            "basis": basis_s,
                            "alphas": alphas_s,
                            "betas": betas_s,
                            "v_prev": v_prev_s,
                            "w": w_s,
                            "beta_prev": beta_prev_s,
                        },
                    )

                ckpt_op.set_step_hook(_chunk_hook)
            try:
                carry = body(i, carry)
            finally:
                if ck_chunk_every > 0:
                    ckpt_op.set_step_hook(None)
            if checkpoint is not None and (i + 1) % every == 0 and i + 1 < m:
                basis_c, alphas_c, betas_c, v_prev_c, w_c, beta_prev_c = carry
                store.save(
                    token,
                    {
                        "engine": "lanczos",
                        "i": i,
                        "n": n,
                        "m": m,
                        "basis": basis_c,
                        "alphas": alphas_c,
                        "betas": betas_c,
                        "v_prev": v_prev_c,
                        "w": w_c,
                        "beta_prev": beta_prev_c,
                    },
                )
        if checkpoint is not None:
            store.clear(token)
        basis, alphas, betas = carry[:3]
    else:
        basis, alphas, betas, _, _, _ = jax.lax.fori_loop(0, m, body, init)
    return LanczosResult(
        alpha=alphas, beta=betas[: m - 1], basis=basis, beta_last=betas[m - 1]
    )


@exact_matmuls
def lanczos_tridiag(
    matvec: Callable,
    v1: jax.Array,
    num_iters: int,
    policy: PrecisionPolicy,
    reorth: str = "half",
    ops: Optional[Ops] = None,
    jit: bool = True,
    checkpoint=None,
) -> LanczosResult:
    """Run ``num_iters`` Lanczos steps. See module docstring.

    ``jit=False`` runs an eager host loop (no ``fori_loop``), letting the
    matvec perform host-side work per iteration — the out-of-core engine's
    mode (see :class:`~repro.core.operators.ChunkedOperator`).  Only that
    host loop honors ``checkpoint`` — a ``(store, token, every)`` triple
    (see :class:`~repro.serving.store.SolveCheckpoint`) snapshotting the
    loop carry every ``every`` completed steps and resuming from the last
    snapshot bit-identically.
    """
    policy = policy.effective()
    _faults.check_sweep_entry()
    ops = ops or make_local_ops(matvec, policy)
    if jit:
        fault_key = _faults.trace_key()
        res = _lanczos_jit(v1, ops, num_iters, policy, reorth, fault_key=fault_key)
        _faults.consume_lanczos(fault_key)
        return res
    return _lanczos_loop(v1, ops, num_iters, policy, reorth, host_loop=True, checkpoint=checkpoint)


@partial(jax.jit, static_argnames=("ops", "num_iters", "policy", "reorth", "fault_key"))
def _lanczos_vmap(v1s, ops: Ops, num_iters: int, policy: PrecisionPolicy, reorth: str, fault_key=None):
    return jax.vmap(lambda v: _lanczos_loop(v, ops, num_iters, policy, reorth))(v1s)


@exact_matmuls
def lanczos_tridiag_multi(
    matvec: Callable,
    v1s: jax.Array,
    num_iters: int,
    policy: PrecisionPolicy,
    reorth: str = "half",
    ops: Optional[Ops] = None,
) -> LanczosResult:
    """Vmapped multi-start Lanczos: ``v1s`` is (s, n) start vectors; every
    field of the result gains a leading start axis ((s, m) alpha, (s, m, n)
    basis, ...).  One compiled sweep builds all s bases — the batched-serving
    path for many-query workloads that differ only in their start vector
    (``api/session.py``).  The fused Pallas update is not used here (the
    batching rule of the interpreter path is unvalidated); callers gate
    vmappability of the *matvec* (dense / COO segment-sum are safe).
    """
    policy = policy.effective()
    _faults.check_sweep_entry()
    ops = ops or make_local_ops(matvec, policy, fused=False)
    fault_key = _faults.trace_key()
    res = _lanczos_vmap(v1s, ops, num_iters, policy, reorth, fault_key=fault_key)
    _faults.consume_lanczos(fault_key)
    return res
