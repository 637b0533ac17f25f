"""Precision-flow verifier: the declared phase map vs the lowered truth.

For each engine (single, restarted, distributed, chunked) and iteration mode
(fused / unfused) this pass traces the *actual solver callables* — the ops
record built by ``core.lanczos.make_local_ops`` / ``core.distributed._make_
sharded_ops``, the restarted engine's ``restart_kernels``, the real Lanczos
loop, the real ritz projection — to jaxprs on abstract inputs (nothing
executes) and checks:

  * **P003** per compute phase: every float arithmetic op in the phase's
    trace runs in the declared phase dtype or the storage dtype — a foreign
    dtype is a phase leak;
  * **P001** over the whole solve: every widening conversion lands in a
    dtype the policy declares somewhere (storage/compute/output/phases) — a
    silent upcast would falsify the mixed-precision speed claim;
  * **P002** over the whole solve: a value cast *down* and then back *up*
    loses bits for no declared reason unless the narrow dtype is the
    policy's storage or a declared phase dtype (the intentional
    round-through-storage of reorthogonalization);
  * **P004**: the measured per-dtype op counts agree with the
    ``phase_op_counts`` model under its ``executed=True`` convention
    (:func:`core.precision.assert_phase_count_parity`) — the tripwire that
    keeps the hand-maintained model honest.

The measured counts are also what ``REPRO_PRECISION_MEASURE=1`` surfaces as
``partition["spmv"]["precision"]["ops_by_dtype_measured"]``.

The whole pass runs under ``jax.enable_x64`` so f64 rungs trace
as real f64 regardless of the process default, without flipping global
state for the rest of the process.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..core.lanczos import lanczos_tridiag, ops_for_operator
from ..core.operators import make_operator
from ..core.precision import (
    PHASES,
    POLICIES,
    PrecisionPolicy,
    assert_phase_count_parity,
    phase_op_counts,
)
from ..core.restarted import restart_kernels, ritz_project
from .findings import Finding, Findings
from .jaxpr_tools import abstract, conversions, count_ops_by_dtype, make_jaxpr_of

__all__ = [
    "ENGINES",
    "RUNGS",
    "policy_dtypes",
    "find_upcasts",
    "find_double_rounding",
    "find_phase_leaks",
    "trace_phases",
    "measure_ops_by_dtype",
    "measure_session_ops",
    "check_policy",
    "run",
]

ENGINES = ("single", "restarted", "distributed", "chunked")
# The five paper/TPU rungs the CI gate sweeps (compensated rungs are covered
# by tests; HFF aliases BFF structurally).
RUNGS = ("BFF", "FFF", "FCF", "FDF", "DDD")

_FLOAT_SIZES = {"bfloat16": 2, "float16": 2, "float32": 4, "float64": 8}


def _size(name: str) -> int:
    return _FLOAT_SIZES.get(name, np.dtype(name).itemsize)


def policy_dtypes(policy: PrecisionPolicy) -> set:
    """Every dtype name the policy declares anywhere."""
    p = policy
    names = {jnp.dtype(p.storage).name, jnp.dtype(p.compute).name, jnp.dtype(p.output).name}
    names.update(jnp.dtype(p.phase_dtype(ph)).name for ph in PHASES)
    return names


def find_upcasts(jaxpr, policy: PrecisionPolicy, context: str = "") -> Findings:
    """P001: widening conversions into undeclared dtypes."""
    declared = policy_dtypes(policy)
    out: List[Finding] = []
    seen = set()
    for conv in conversions(jaxpr):
        if _size(conv.dst) > _size(conv.src) and conv.dst not in declared:
            key = (conv.src, conv.dst)
            if key in seen:
                continue
            seen.add(key)
            out.append(
                Finding(
                    "P001",
                    f"upcast {conv.src} -> {conv.dst}, but {conv.dst} is not"
                    f" declared anywhere in policy {policy.name}",
                    context=context,
                )
            )
    return out


def find_double_rounding(jaxpr, policy: PrecisionPolicy, context: str = "") -> Findings:
    """P002: down-then-up cast chains through an undeclared narrow dtype."""
    declared = policy_dtypes(policy)
    out: List[Finding] = []
    seen = set()
    for conv in conversions(jaxpr):
        if conv.prev_src is None:
            continue
        a, b, c = conv.prev_src, conv.src, conv.dst
        if _size(b) < _size(a) and _size(c) > _size(b) and b not in declared:
            key = (a, b, c)
            if key in seen:
                continue
            seen.add(key)
            out.append(
                Finding(
                    "P002",
                    f"value rounded {a} -> {b} -> {c}; the intermediate {b} is"
                    f" not the storage or any declared phase dtype of {policy.name}",
                    context=context,
                )
            )
    return out


def find_phase_leaks(
    jaxpr, policy: PrecisionPolicy, phase: str, context: str = "", min_share: float = 0.01
) -> Findings:
    """P003: arithmetic in a dtype foreign to the declared phase.

    Allowed in a phase's trace: the declared phase dtype and the storage
    dtype (inputs are held in storage; elementwise pre-accumulation work may
    legally run there).  Anything else carrying more than ``min_share`` of
    the phase's ops is a leak.
    """
    allowed = {
        jnp.dtype(policy.phase_dtype(phase)).name,
        jnp.dtype(policy.storage).name,
    }
    counts = count_ops_by_dtype(jaxpr)
    total = sum(counts.values())
    out: List[Finding] = []
    if not total:
        return out
    for dt, cnt in sorted(counts.items()):
        if dt not in allowed and cnt / total >= min_share:
            out.append(
                Finding(
                    "P003",
                    f"phase '{phase}' declared {jnp.dtype(policy.phase_dtype(phase)).name}"
                    f" but executes {cnt} ops ({cnt / total:.0%}) in {dt}",
                    context=context,
                )
            )
    return out


# ------------------------------------------------------------ trace builders


def _fixture(policy: PrecisionPolicy, n: int, seed: int = 3):
    """Synthetic near-uniform problem + ELL engine + operator for tracing.

    'road' degree structure keeps the ELL padding overhead small so the
    executed-ops parity bound stays tight.
    """
    from ..kernels.engine import make_engine
    from ..sparse import generate

    csr = generate("road", n, 4.0, seed=seed, values="normalized")
    pol = policy.effective()
    eng = make_engine(
        csr, "ell", accum_dtype=pol.phase_dtype("spmv"), interpret=True
    )
    op = make_operator(csr, dtype=pol.storage, engine=eng)
    return csr, eng, op


def _executed_nnz(op, fallback_nnz: int) -> int:
    """SpMV elements per matvec as the kernel executes them (ELL: every
    padded cell), falling back to logical nnz."""
    mat = getattr(op, "mat", None)
    val = getattr(mat, "val", None)
    if val is not None:
        return int(np.prod(val.shape))
    return int(fallback_nnz)


def _trace_ritz(policy: PrecisionPolicy, *, n: int, m: int, k: int, jacobi: str):
    """Jaxprs of the ritz phase: back-projection (+ device Jacobi)."""
    pol = policy.effective()
    sdt, cdt = pol.storage, pol.compute
    rzdt = pol.phase_dtype("ritz")
    traces = [
        make_jaxpr_of(
            lambda basis, w: ritz_project(basis, w, pol),
            abstract((m, n), sdt),
            abstract((m, k), rzdt),
        )
    ]
    if jacobi == "device":
        from ..core.jacobi import jacobi_eigh, tridiag_to_dense

        traces.append(
            make_jaxpr_of(
                lambda a, b: jacobi_eigh(tridiag_to_dense(a, b).astype(rzdt)),
                abstract((m,), cdt),
                abstract((m - 1,), cdt),
            )
        )
    return traces


def _single_traces(policy, *, n, m, reorth, op, chunked: bool = False, fused=None):
    """(phase jaxprs, full-loop jaxpr) for the in-core single-device loop
    (also the chunked engine's loop, eager/unrolled); ``fused`` pins the
    update mode."""
    pol = policy.effective()
    sdt, cdt = pol.storage, pol.compute
    ops = ops_for_operator(op, pol, fused=fused)
    mv = op.bound_matvec(pol)
    phases = {
        "spmv": make_jaxpr_of(lambda v: ops.matvec(v), abstract((n,), sdt)),
        "alpha_beta": make_jaxpr_of(
            ops.dot, abstract((n,), cdt), abstract((n,), cdt)
        ),
        "reorth": make_jaxpr_of(
            ops.project_out,
            abstract((m, n), sdt),
            abstract((n,), cdt),
            abstract((m,), cdt),
        ),
    }
    full = make_jaxpr_of(
        lambda v: lanczos_tridiag(mv, v, m, pol, reorth=reorth, ops=ops, jit=not chunked),
        abstract((n,), cdt),
    )
    return phases, full


def _restarted_traces(policy, *, n, m, reorth, op):
    """Phase + per-step traces from the restarted engine's shared kernels."""
    pol = policy.effective()
    sdt, cdt = pol.storage, pol.compute
    dot, orth = restart_kernels(pol)
    mv = op.bound_matvec(pol)

    def step(v, v_prev, beta, basis, mask):
        u = mv(v.astype(sdt)).astype(cdt)
        alpha = dot(v, u)
        u = u - alpha * v - beta * v_prev
        u = orth(u, basis, mask)
        beta2 = jnp.sqrt(jnp.maximum(dot(u, u), jnp.zeros((), u.dtype)))
        return u / beta2, beta2

    phases = {
        "spmv": make_jaxpr_of(lambda v: mv(v.astype(sdt)), abstract((n,), cdt)),
        "alpha_beta": make_jaxpr_of(dot, abstract((n,), cdt), abstract((n,), cdt)),
        "reorth": make_jaxpr_of(
            orth, abstract((n,), cdt), abstract((m, n), sdt), abstract((m,), cdt)
        ),
    }
    step_jaxpr = make_jaxpr_of(
        step,
        abstract((n,), cdt),
        abstract((n,), cdt),
        abstract((), cdt),
        abstract((m, n), sdt),
        abstract((m,), cdt),
    )
    return phases, step_jaxpr


def _distributed_traces(policy, *, n, m, reorth, csr, fmt="ell", fused=None):
    """Phase + full traces through the real shard_map program (1-device mesh)."""
    from jax.sharding import Mesh

    from ..core.distributed import _make_sharded_ops, prepare_sharded, sharded_lanczos

    pol = policy.effective()
    sdt, cdt = pol.storage, pol.compute
    ps = prepare_sharded(csr, 1, pol, spmv_format=fmt)
    n_pad = ps.pm.n_pad
    axis = "data"
    local = tuple(mat[0] for mat in ps.mats)
    ops = _make_sharded_ops(local, n_pad, pol, axis, engine=ps.engine, fused=fused)
    env = [(axis, 1)]
    phases = {
        "spmv": jax.make_jaxpr(lambda v: ops.matvec(v), axis_env=env)(
            abstract((n_pad,), sdt)
        ),
        "alpha_beta": jax.make_jaxpr(ops.dot, axis_env=env)(
            abstract((n_pad,), cdt), abstract((n_pad,), cdt)
        ),
        "reorth": jax.make_jaxpr(ops.project_out, axis_env=env)(
            abstract((m, n_pad), sdt),
            abstract((n_pad,), cdt),
            abstract((m,), cdt),
        ),
    }
    mesh = Mesh(np.array(jax.devices()[:1]), (axis,))
    full = make_jaxpr_of(
        lambda v: sharded_lanczos(
            ps.pm, v, m, pol, mesh, reorth=reorth, axis=axis,
            engine=ps.engine, mats=ps.mats, fused=fused,
        ),
        abstract((1, n_pad), cdt),
    )
    nnz_exec = int(np.prod(ps.mats[0].shape)) if fmt in ("ell", "bsr") else csr.nnz
    return phases, full, n_pad, nnz_exec


def _merge(*count_dicts: Dict[str, int]) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for d in count_dicts:
        for k, v in d.items():
            out[k] = out.get(k, 0) + v
    return out


def _scaled(counts: Dict[str, int], factor: int) -> Dict[str, int]:
    return {k: v * factor for k, v in counts.items()}


def _build_traces(
    policy: PrecisionPolicy,
    engine: str,
    *,
    fused: bool,
    n: int,
    m: int,
    k: int,
    reorth: str,
    jacobi: str,
):
    """All jaxprs + parity-model inputs for one (policy, engine, mode)."""
    pol = policy.effective()
    csr, _, op = _fixture(pol, n)
    n = csr.n  # 'road' rounds n up to a grid square
    if engine == "distributed":
        phases, full, n_model, nnz_exec = _distributed_traces(
            pol, n=n, m=m, reorth=reorth, csr=csr, fused=fused
        )
        step_scale = 1
    elif engine == "restarted":
        phases, full = _restarted_traces(pol, n=n, m=m, reorth=reorth, op=op)
        n_model = n
        nnz_exec = _executed_nnz(op, csr.nnz)
        step_scale = m  # host loop: one traced step x m fill iterations
    elif engine == "chunked":
        from ..core.operators import ChunkedOperator

        # Two chunks: exercises the streaming loop; chunks are padded to
        # chunk_nnz, so executed nnz is the padded total.
        chunk_nnz = max(1, (csr.nnz + 1) // 2)
        op = ChunkedOperator(csr, chunk_nnz=chunk_nnz, dtype=pol.storage)
        phases, full = _single_traces(
            pol, n=n, m=m, reorth=reorth, op=op, chunked=True, fused=fused
        )
        n_model = n
        nnz_exec = op.num_chunks * chunk_nnz
        step_scale = 1
    elif engine == "single":
        phases, full = _single_traces(pol, n=n, m=m, reorth=reorth, op=op, fused=fused)
        n_model = n
        nnz_exec = _executed_nnz(op, csr.nnz)
        step_scale = 1
    else:
        raise ValueError(f"unknown engine {engine!r}; expected one of {ENGINES}")
    ritz_traces = _trace_ritz(pol, n=n_model, m=m, k=k, jacobi=jacobi)
    phases["ritz"] = ritz_traces[0]
    return phases, full, ritz_traces, step_scale, n_model, nnz_exec


def trace_phases(policy, engine="single", *, fused=False, n=64, m=8, k=4,
                 reorth="full", jacobi="host"):
    """Public: {phase: jaxpr} for one engine config (for tests/inspection)."""
    with jax.enable_x64(True):
        phases, _, _, _, _, _ = _build_traces(
            POLICIES.get(policy, policy) if isinstance(policy, str) else policy,
            engine, fused=fused, n=n, m=m, k=k, reorth=reorth, jacobi=jacobi,
        )
        return phases


def measure_ops_by_dtype(
    policy: PrecisionPolicy,
    engine: str = "single",
    *,
    fused: bool = False,
    n: int = 64,
    m: int = 8,
    k: int = 4,
    reorth: str = "full",
    jacobi: str = "host",
) -> Dict[str, int]:
    """Jaxpr-measured element ops per dtype for one traced solve."""
    with jax.enable_x64(True):
        _, full, ritz_traces, step_scale, _, _ = _build_traces(
            policy, engine, fused=fused, n=n, m=m, k=k, reorth=reorth, jacobi=jacobi
        )
        counts = _scaled(count_ops_by_dtype(full), step_scale)
        for rt in ritz_traces:
            counts = _merge(counts, count_ops_by_dtype(rt))
        return counts


def check_policy(
    policy: PrecisionPolicy,
    engine: str = "single",
    *,
    fused: bool = False,
    n: int = 64,
    m: int = 8,
    k: int = 4,
    reorth: str = "full",
    jacobi: str = "host",
    parity_ratio: float = 8.0,
) -> Tuple[Findings, Dict[str, int]]:
    """Run all four precision rules for one (policy, engine, mode).

    Returns ``(findings, measured_ops_by_dtype)``.
    """
    pol = policy.effective() if not isinstance(policy, str) else POLICIES[policy]
    findings: List[Finding] = []
    with jax.enable_x64(True):
        pol = (POLICIES[policy] if isinstance(policy, str) else policy).effective()
        ctx = f"{pol.name}/{engine}/{'fused' if fused else 'unfused'}"
        phases, full, ritz_traces, step_scale, n_model, nnz_exec = _build_traces(
            pol, engine, fused=fused, n=n, m=m, k=k, reorth=reorth, jacobi=jacobi
        )
        # P003 per phase
        for ph, jx in phases.items():
            findings.extend(find_phase_leaks(jx, pol, ph, context=f"{ctx}/{ph}"))
        # P001/P002 over the full solve + ritz
        for jx in [full, *ritz_traces]:
            findings.extend(find_upcasts(jx, pol, context=ctx))
            findings.extend(find_double_rounding(jx, pol, context=ctx))
        # P004 parity with the model
        measured = _scaled(count_ops_by_dtype(full), step_scale)
        for rt in ritz_traces:
            measured = _merge(measured, count_ops_by_dtype(rt))
        model = phase_op_counts(
            pol, n=n_model, nnz=nnz_exec, m=m, k=k,
            reorth=reorth, jacobi=jacobi, executed=True,
        )
        try:
            assert_phase_count_parity(
                model, measured, ratio=parity_ratio, context=ctx
            )
        except AssertionError as exc:
            findings.append(Finding("P004", str(exc), context=ctx))
    return findings, measured


def run(
    rungs: Iterable[str] = RUNGS,
    engines: Iterable[str] = ENGINES,
    modes: Iterable[bool] = (False, True),
    **kw,
) -> Findings:
    """The CI sweep: every rung x engine x fused/unfused."""
    findings: List[Finding] = []
    for name in rungs:
        pol = POLICIES[name]
        for eng in engines:
            for fused in modes:
                fs, _ = check_policy(pol, eng, fused=fused, **kw)
                findings.extend(fs)
    return findings


# ------------------------------------------------------ session integration

_SESSION_MEASURE_CACHE: Dict[tuple, Dict[str, int]] = {}
_SESSION_MEASURE_CACHE_MAX = 32


def measure_session_ops(
    policy: PrecisionPolicy,
    operator,
    *,
    backend: str,
    m: int,
    k: int,
    reorth: str,
    jacobi: str = "host",
) -> Dict[str, int]:
    """``ops_by_dtype_measured`` for a live session solve (behind
    ``REPRO_PRECISION_MEASURE``).

    Traces the session's *own* operator (its device arrays close over as
    constants — tracing allocates nothing and executes nothing).  The
    restarted backend uses its per-step trace x ``m``; every other backend
    uses the jitted loop trace, whose phase dtypes are shared by
    construction across the single/distributed/chunked engines.
    """
    pol = policy.effective()
    key = (id(operator), pol.name, backend, m, k, reorth, jacobi)
    hit = _SESSION_MEASURE_CACHE.get(key)
    if hit is not None:
        return hit
    n = operator.n
    if backend == "restarted":
        phases, step = _restarted_traces(pol, n=n, m=m, reorth="full", op=operator)
        counts = _scaled(count_ops_by_dtype(step), m)
    else:
        _, full = _single_traces(pol, n=n, m=m, reorth=reorth, op=operator)
        counts = count_ops_by_dtype(full)
    for rt in _trace_ritz(pol, n=n, m=m, k=k, jacobi=jacobi):
        counts = _merge(counts, count_ops_by_dtype(rt))
    if len(_SESSION_MEASURE_CACHE) >= _SESSION_MEASURE_CACHE_MAX:
        _SESSION_MEASURE_CACHE.pop(next(iter(_SESSION_MEASURE_CACHE)))
    _SESSION_MEASURE_CACHE[key] = counts
    return counts
