"""Mixed-precision policies for the Top-K sparse eigensolver.

The paper (Sgherzi et al., 2022, §III-A / §IV-D) parameterizes the solver by a
*dtype triple* — the precision in which vectors are **stored**, the precision
in which the numerically critical reductions (the ``alpha`` dot products, the
``beta`` L2 norms and the re-orthogonalization coefficients) are **computed**,
and the precision of the **output** eigencomponents.  Their headline result is
that FDF (store f32 / compute f64 / output f32) is 50% faster than DDD and 12x
more accurate than FFF.

TPU adaptation (see DESIGN.md §3): TPUs have no fast f64, so the TPU-native
ladder is shifted one rung down — bf16/f16 storage with f32 compute — and the
"extra accumulator width" role of f64 is played by *compensated* (Neumaier)
f32 summation, exposed here as ``compensated=True`` policies.  The f64 paths
remain available on CPU (JAX x64) and are used to reproduce the paper's
Fig. 4 exactly.

Per-phase compute dtypes (beyond-paper): the solver's "intermediate
operations" are not one phase but four — the SpMV accumulator, the
alpha/beta reductions, the re-orthogonalization projections, and the
Ritz/restart arithmetic — and they tolerate narrow formats very differently
(Hunhold et al. 2025: reorthogonalization and the tridiagonal solve are the
accuracy-critical ones).  ``PrecisionPolicy`` therefore carries optional
per-phase overrides of the ``compute`` dtype (:data:`PHASES`,
:meth:`PrecisionPolicy.with_phases`); ``None`` means "inherit ``compute``",
so a policy with no overrides behaves — bit-identically — like the uniform
triple.  ``phase_op_counts`` provides the model-based per-dtype operation
audit surfaced in ``EigenResult.partition["spmv"]["precision"]``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

__all__ = [
    "PHASES",
    "PrecisionPolicy",
    "FFF",
    "FDF",
    "DDD",
    "BFF",
    "HFF",
    "FCF",
    "BCF",
    "POLICIES",
    "x64_enabled",
    "auto_ladder",
    "phase_op_counts",
    "assert_phase_count_parity",
    "exact_matmuls",
    "basis_matmul",
]

# The four compute phases of one Lanczos-based solve, in hot-loop order:
#   spmv       — the SpMV accumulator (y = A @ x partial sums);
#   alpha_beta — the alpha dot products and beta L2 norms (sync points A/B);
#   reorth     — the re-orthogonalization coefficient dots + subtraction (C);
#   ritz       — Ritz extraction / thick-restart arithmetic (X = V^T W).
PHASES = ("spmv", "alpha_beta", "reorth", "ritz")

# Short dtype spellings accepted by ``with_phases`` / phase-override dicts.
_DTYPE_ALIASES = {
    "f16": "float16",
    "f32": "float32",
    "f64": "float64",
    "bf16": "bfloat16",
}


def _parse_dtype(dt):
    """Accept a dtype object or a (shorthand) name; normalize via jnp.dtype."""
    if isinstance(dt, str):
        dt = _DTYPE_ALIASES.get(dt.lower(), dt.lower())
    try:
        return jnp.dtype(dt)
    except TypeError as e:
        raise ValueError(f"unparseable phase dtype {dt!r}") from e


def x64_enabled() -> bool:
    return bool(jax.config.read("jax_enable_x64"))


@dataclasses.dataclass(frozen=True)
class PrecisionPolicy:
    """(storage, compute, output) dtype triple, the paper's precision knob.

    Attributes:
      name: short id, e.g. ``"FDF"``.
      storage: dtype in which the Lanczos basis V and carried vectors are kept.
      compute: dtype in which SpMV accumulation and the alpha/beta/reorth
        reductions are performed (the paper's "intermediate operations").
      output: dtype of the returned eigenvalues/eigenvectors.
      compensated: if True, scalar reductions additionally use Neumaier
        compensated summation in the ``compute`` dtype (TPU-native analogue
        of the paper's f64 accumulation; beyond-paper feature).
      spmv / alpha_beta / reorth / ritz: optional per-phase overrides of
        ``compute`` (see :data:`PHASES`); ``None`` inherits ``compute``, so
        a policy without overrides is exactly the paper's uniform triple.
        Build them with :meth:`with_phases`.
    """

    name: str
    storage: Any
    compute: Any
    output: Any
    compensated: bool = False
    # Per-phase overrides of ``compute`` (None = inherit).  See PHASES.
    spmv: Any = None
    alpha_beta: Any = None
    reorth: Any = None
    ritz: Any = None

    def phase_dtype(self, phase: str):
        """Compute dtype of one solver phase (the override, or ``compute``)."""
        if phase not in PHASES:
            raise ValueError(f"unknown precision phase {phase!r}; valid phases: {PHASES}")
        override = getattr(self, phase)
        return self.compute if override is None else override

    def phase_map(self) -> Dict[str, str]:
        """{phase: dtype name} of every compute phase — the provenance record
        surfaced in ``EigenResult.partition["spmv"]["precision"]``."""
        return {ph: jnp.dtype(self.phase_dtype(ph)).name for ph in PHASES}

    def is_uniform(self) -> bool:
        """True when every phase runs in the plain ``compute`` dtype."""
        cdt = jnp.dtype(self.compute)
        return all(
            getattr(self, ph) is None or jnp.dtype(getattr(self, ph)) == cdt
            for ph in PHASES
        )

    def with_phases(self, **overrides) -> "PrecisionPolicy":
        """New policy with per-phase compute dtypes, e.g.
        ``FDF.with_phases(reorth="f32")`` (alpha/beta stay f64).  Unknown
        phase names are a named error listing the valid phases; dtypes may be
        objects or (shorthand) names.  ``None`` clears an override."""
        bad = sorted(set(overrides) - set(PHASES))
        if bad:
            raise ValueError(
                f"unknown precision phase(s) {bad}; valid phases: {PHASES}"
            )
        parsed = {
            ph: (None if dt is None else _parse_dtype(dt)) for ph, dt in overrides.items()
        }
        new = dataclasses.replace(self, **parsed)
        tags = ",".join(
            f"{ph}={jnp.dtype(getattr(new, ph)).name}"
            for ph in PHASES
            if getattr(new, ph) is not None
        )
        base = self.name.split("[")[0]
        return dataclasses.replace(new, name=f"{base}[{tags}]" if tags else base)

    def effective(self) -> "PrecisionPolicy":
        """Downgrade f64 members to f32 when x64 is disabled (with a note)."""
        if x64_enabled():
            return self

        f64 = jnp.dtype(jnp.float64)

        def _eff(dt):
            return jnp.float32 if jnp.dtype(dt) == f64 else dt

        members = [self.storage, self.compute, self.output] + [
            getattr(self, ph) for ph in PHASES if getattr(self, ph) is not None
        ]
        if any(jnp.dtype(dt) == f64 for dt in members):
            return dataclasses.replace(
                self,
                name=self.name + "(x32!)",
                storage=_eff(self.storage),
                compute=_eff(self.compute),
                output=_eff(self.output),
                **{
                    ph: _eff(getattr(self, ph))
                    for ph in PHASES
                    if getattr(self, ph) is not None
                },
            )
        return self

    def short(self) -> str:
        return self.name


# Paper's three configurations (their §IV-D, Fig. 4).
FFF = PrecisionPolicy("FFF", jnp.float32, jnp.float32, jnp.float32)
FDF = PrecisionPolicy("FDF", jnp.float32, jnp.float64, jnp.float32)
DDD = PrecisionPolicy("DDD", jnp.float64, jnp.float64, jnp.float64)

# TPU-native ladder (DESIGN.md §3): bf16/f16 storage, f32 compute; the
# compensated variants recover the wide-accumulator role of f64.
BFF = PrecisionPolicy("BFF", jnp.bfloat16, jnp.float32, jnp.float32)
HFF = PrecisionPolicy("HFF", jnp.float16, jnp.float32, jnp.float32)
FCF = PrecisionPolicy("FCF", jnp.float32, jnp.float32, jnp.float32, compensated=True)
BCF = PrecisionPolicy("BCF", jnp.bfloat16, jnp.float32, jnp.float32, compensated=True)

POLICIES = {p.name: p for p in (FFF, FDF, DDD, BFF, HFF, FCF, BCF)}


def exact_matmuls(fn):
    """Run ``fn`` with matmuls at the precision of their dtype.

    A policy names the dtype each phase computes in.  On a TPU an f32 matmul
    at JAX's default precision is one bf16 pass (~3 significant digits), so
    every solve traces its basis products, projections and Ritz extractions
    at ``"highest"``; the CPU computes f32 exactly either way.
    """

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)

    return wrapper


@jax.jit
def basis_matmul(a: jax.Array, b: jax.Array) -> jax.Array:
    """``a @ b`` for the Krylov-basis products (projections, Ritz vectors).

    A TPU has no f64 matrix unit, and XLA emulates an f64 dot through f32
    pieces with large temporaries (13 GB for one 32 x 3.57M basis product).
    Written as multiply + reduce, the f64 product fuses and needs none —
    under ``jit`` only: run eagerly, the broadcast product would be
    materialised (8.2 GB for the FDF Ritz rows on WK).  Other dtypes keep
    the dot.
    """
    if jnp.result_type(a.dtype, b.dtype) != jnp.dtype(jnp.float64):
        return a @ b
    a2 = a if a.ndim == 2 else a[None, :]
    b2 = b if b.ndim == 2 else b[:, None]
    out = jnp.sum(a2[:, :, None] * b2[None, :, :], axis=1)
    if b.ndim == 1:
        out = out[:, 0]
    return out[0] if a.ndim == 1 else out


def compensated_sum(x: jax.Array, dtype) -> jax.Array:
    """Neumaier (improved Kahan) compensated summation of a 1-D array.

    Sequential over ``x`` in chunks: each chunk is summed natively (one rounding
    per chunk) and chunk totals are combined with Neumaier compensation.  This
    bounds the error like a ~2x-wider accumulator at a small bandwidth cost —
    the TPU stand-in for the paper's f64 accumulation.
    """
    x = x.astype(dtype)
    n = x.shape[0]
    chunk = 256
    pad = (-n) % chunk
    xp = jnp.pad(x, (0, pad))
    parts = xp.reshape(-1, chunk).sum(axis=1)  # one native sum per chunk

    def body(carry, p):
        s, c = carry
        t = s + p
        # Neumaier: pick compensation direction by magnitude.
        comp = jnp.where(
            jnp.abs(s) >= jnp.abs(p), (s - t) + p, (p - t) + s
        )
        return (t, c + comp), None

    (s, c), _ = jax.lax.scan(body, (jnp.zeros((), dtype), jnp.zeros((), dtype)), parts)
    return s + c


def reduce_sum(x: jax.Array, policy: PrecisionPolicy) -> jax.Array:
    """Policy-directed sum reduction (the paper's alpha/beta accumulators)."""
    if policy.compensated:
        return compensated_sum(x.reshape(-1), policy.compute)
    return jnp.sum(x.astype(policy.compute))


def dot(a: jax.Array, b: jax.Array, policy: PrecisionPolicy) -> jax.Array:
    """Mixed-precision dot product: storage-dtype inputs, compute-dtype accum."""
    prod = a.astype(policy.compute) * b.astype(policy.compute)
    return reduce_sum(prod, policy)


def norm2(a: jax.Array, policy: PrecisionPolicy) -> jax.Array:
    return jnp.sqrt(dot(a, a, policy))


# --------------------------- accuracy-driven auto ----------------------------

# Escalation ladder for ``policy="auto"``: cheapest first.  Each rung is a
# real policy from POLICIES; the selector probes rungs in order and stops at
# the first whose measured residuals meet the requested tol.  The f64 rungs
# only exist where x64 does (they would silently alias FFF otherwise).
_AUTO_LADDER_X64 = ("BFF", "FFF", "FCF", "FDF", "DDD")
_AUTO_LADDER_X32 = ("BFF", "FFF", "FCF")


def auto_ladder() -> tuple:
    """Policy names ``policy="auto"`` escalates through, cheapest first,
    capped by :func:`x64_enabled` (no point escalating to a rung that the
    x32 downgrade folds back onto an earlier one)."""
    return _AUTO_LADDER_X64 if x64_enabled() else _AUTO_LADDER_X32


# Fraction of the stored basis each re-orthogonalization mode touches per
# pass (the paper's parity scheme halves it; CGS2 runs two full passes).
_REORTH_PASS_FRAC = {"none": 0.0, "half": 0.5, "half_alt": 0.5, "full": 1.0, "full2": 2.0}
# Fraction of the basis each mode's *kernel* actually sweeps.  The parity
# modes are implemented as one masked full-width matmul per iteration (the
# mask zeroes the coefficients, not the work), so their executed fraction is
# 1.0 — the 0.5 above models data *touched*, the quantity the paper's parity
# argument is about.  The jaxpr-measured audit counts executed ops, so the
# parity assertion compares against this table.
_REORTH_EXEC_FRAC = {"none": 0.0, "half": 1.0, "half_alt": 1.0, "full": 1.0, "full2": 2.0}

# Element ops of one cyclic-Jacobi sweep on an m x m matrix: m(m-1)/2
# rotations, each applying 6 axpy-like updates of length m (two rows, two
# cols, two eigenvector cols at 3 ops/element) => ~9 m^3 per sweep.
_JACOBI_SWEEP_OPS = 9.0


def phase_op_counts(
    policy: PrecisionPolicy,
    *,
    n: int,
    nnz: int,
    m: int,
    k: int,
    reorth: str = "half",
    jacobi: str = "host",
    jacobi_sweeps: float = 6.0,
    executed: bool = False,
) -> Dict[str, int]:
    """Model-based count of element operations per compute dtype for one
    solve — the audit behind the per-phase precision claim ("this split
    reduced f64 work"), surfaced in ``partition["spmv"]["precision"]``.

    Counts are the leading terms of the solver's arithmetic, attributed to
    the phase that executes them: ``m * nnz`` SpMV accumulations, ``2 m n``
    alpha/beta reduction elements, ``2 f m^2 n`` re-orthogonalization
    elements (``f`` = the mode's basis fraction per pass; coefficient dot +
    subtraction), and ``n m k`` back-projection elements.  An *estimate* of
    work by dtype, not a hardware counter.

    ``jacobi="device"`` additionally attributes the on-device Jacobi
    eigensolve of the m x m projected matrix to the ritz phase
    (``~9 m^3`` per sweep x ``jacobi_sweeps``); the host placement runs in
    NumPy and contributes no device ops.  Before the jaxpr audit existed the
    model silently attributed zero ops to device Jacobi — the divergence the
    precision-flow verifier was built to catch.

    ``executed=True`` switches the reorth term from the algorithmic
    touched-data fractions to the fractions the masked kernels actually
    execute (see ``_REORTH_EXEC_FRAC``) and counts one Jacobi sweep (a jaxpr
    records a ``while`` body once) — the convention under which the counts
    are comparable to the verifier's ``ops_by_dtype_measured``.
    """
    p = policy.effective()
    counts: Dict[str, int] = {}

    def add(phase: str, ops: float) -> None:
        name = jnp.dtype(p.phase_dtype(phase)).name
        counts[name] = counts.get(name, 0) + int(ops)

    table = _REORTH_EXEC_FRAC if executed else _REORTH_PASS_FRAC
    frac = table.get(reorth, 1.0)
    add("spmv", m * nnz)
    add("alpha_beta", 2 * m * n)
    add("reorth", 2.0 * frac * m * m * n)
    add("ritz", n * m * k)
    if jacobi == "device":
        sweeps = 1.0 if executed else jacobi_sweeps
        add("ritz", _JACOBI_SWEEP_OPS * sweeps * m**3)
    return counts


def assert_phase_count_parity(
    model: Dict[str, int],
    measured: Dict[str, int],
    *,
    ratio: float = 8.0,
    min_share: float = 0.02,
    context: str = "",
) -> None:
    """Tripwire pinning the model to the jaxpr-measured reality.

    The model and the trace count with different granularity (a matmul is
    ``MNK`` macs in the model, multiply + reduce eqns in the trace), so this
    does not demand equality; it demands the same *story*: every dtype
    carrying a non-trivial share (``min_share``) of the work appears on both
    sides, and per-dtype totals agree within a factor of ``ratio``.  A wrong
    phase-dtype attribution (the device-Jacobi bug this was added for) moves
    whole ``m^3``/``m^2 n`` terms between dtypes and trips either check long
    before any constant-factor slack matters.
    """
    problems = []
    total_meas = sum(measured.values()) or 1
    total_model = sum(model.values()) or 1
    for dt, cnt in sorted(measured.items()):
        if cnt / total_meas >= min_share and model.get(dt, 0) == 0:
            problems.append(
                f"measured dtype {dt} ({cnt} ops, {cnt / total_meas:.0%} of trace)"
                " is absent from the model"
            )
    for dt, cnt in sorted(model.items()):
        if cnt / total_model >= min_share and measured.get(dt, 0) == 0:
            problems.append(
                f"model dtype {dt} ({cnt} ops, {cnt / total_model:.0%} of model)"
                " never appears in the trace"
            )
    for dt in sorted(set(model) & set(measured)):
        if model[dt] == 0 or measured[dt] == 0:
            continue
        r = measured[dt] / model[dt]
        if not (1.0 / ratio <= r <= ratio):
            problems.append(
                f"{dt}: measured/model ratio {r:.3g} outside"
                f" [{1.0 / ratio:.3g}, {ratio:.3g}]"
                f" (measured={measured[dt]}, model={model[dt]})"
            )
    if problems:
        where = f" [{context}]" if context else ""
        raise AssertionError(
            f"phase_op_counts parity failure{where}:\n  " + "\n  ".join(problems)
        )
