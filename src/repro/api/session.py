"""Plan/execute split for the solver frontend: prepared ``EigenSession``s.

``eigsh(A, k)`` reproduces the paper's transparency claim, but every call
re-pays the full plan phase — input coercion, format census, ELL/BSR/hybrid
conversion, tile tuning, shard remapping, chunk pinning — even when the
matrix is identical.  The serving pattern the ROADMAP targets (one graph,
millions of queries) is the opposite shape: one expensive plan, many cheap
executes.  This module makes the split explicit:

    sess = prepare(A, format="auto")            # pay the plan once
    r1 = sess.eigsh(8, policy="FDF")            # execute: no conversions
    r2 = sess.eigsh(4, tol=1e-7)                # execute: no conversions
    rs = sess.eigsh_many([{"k": 4}, {"k": 8}])  # batched: one shared sweep

A session owns the coerced input, the resolved placement, the converted
device/shard/chunk operators and their tuned tiles — everything that is a
function of the *matrix* and the layout-affecting config, and nothing that
is a function of the *query* (k, policy, tol, num_iters, start vector).
Operators are cached per precision policy (storage/compute dtype pair), so
a session serves mixed-policy query streams without rebuilding.

``eigsh`` stays the one-call entrypoint: it is now a thin wrapper over a
small fingerprint-keyed session cache (content digest of the CSR arrays +
the layout-affecting config fields), so naive repeated calls transparently
hit the prepared path.  Reuse is *verified*, not assumed: results report
the conversion and tuner-probe counts their call actually paid
(``partition["spmv"]``) and a ``session_reuse`` provenance flag.

``eigsh_many`` amortizes one matrix across many ``(k, policy, tol)``
queries: queries are grouped by (backend, policy, reorth, jacobi), each
group runs ONE Lanczos sweep at the group's largest subspace and every
query slices its Ritz pairs from it (columns are independent, so a k=4
answer inside a k=16 sweep is exactly the k=4 answer of that subspace —
never worse than the query's own sweep).  Queries that differ only in
their start vector run as a vmapped multi-start batch when the operator's
matvec is batchable (dense / COO segment-sum).
"""

from __future__ import annotations

import dataclasses
import hashlib
import math
import os
import threading
import time
import warnings
from collections import OrderedDict
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import env as envcfg
from ..core.distributed import PreparedShards, prepare_sharded, solve_sharded
from ..core.eigensolver import ritz_decompose, ritz_extract, solve_fixed
from ..core.lanczos import (
    LanczosResult,
    NumericalBreakdown,
    lanczos_tridiag_multi,
    resolve_update_mode,
)
from ..core.operators import (
    ChunkedOperator,
    DenseOperator,
    LinearOperator,
    SparseOperator,
    make_operator,
)
from ..core.precision import PrecisionPolicy, auto_ladder, exact_matmuls, phase_op_counts
from ..core.restarted import solve_restarted
from ..kernels.engine import (
    FORMATS,
    SpmvEngine,
    choose_format,
    make_engine,
    matrix_stats,
    phase_executors,
    tuner_probe_count,
)
from ..kernels.ops import default_interpret
from ..sparse.diskcsr import DiskCSR, is_diskcsr
from ..sparse.formats import CSR, conversion_count
from ..tracing import span
from .coerce import CoercedInput, coerce_input, traced_fingerprint
from .dispatch import device_working_set, select_backend
from .frontend import (
    SolverConfig,
    _default_tol,
    _resolve_reorth,
    is_auto_policy,
    resolve_policy,
)
from .result import EigenResult

__all__ = [
    "EigQuery",
    "EigenSession",
    "prepare",
    "eigsh_many",
    "policy_key",
    "config_fingerprint",
    "get_session",
    "session_cache_clear",
    "session_cache_info",
]

# Persisted-session schema version (EigenSession.export_state /
# import_plans).  Bump when the exported plan layout changes shape.
_EXPORT_SCHEMA = 1

_UNSET = object()  # distinguishes "inherit the session default" from None

# SolverConfig fields that change what a session *builds* (placement, device
# layouts, tiles).  Per-query fields (k, tol, num_iters, reorth, seed,
# subspace, max_restarts, jacobi, policy) are deliberately excluded: the
# session resolves them per query, and policies get per-dtype operator
# caches inside the session.
_LAYOUT_FIELDS = ("backend", "format", "chunk_nnz", "stage_depth", "axis", "staging")

# Largest on-disk payload the auto ladder's f64 residual verification will
# materialize: a bigger DiskCSR stays on disk and the ladder falls back to
# the Ritz bound (verification must never defeat the out-of-core budget).
_DISK_VERIFY_MAX_BYTES = 1 << 28


def policy_key(policy: Union[str, PrecisionPolicy]) -> str:
    """Stable identity key of a policy: the dtype triple — plus any
    per-phase compute overrides — never the spelling.  ``"FDF"`` and the
    ``FDF`` instance key identically (the frontend's session cache relies on
    this); a phase-split policy whose overrides all equal ``compute`` keys
    identically to the uniform policy.  Built *plans* are shared more
    aggressively than this key — see :func:`_plan_key`."""
    p = resolve_policy(policy).effective()
    parts = [
        jnp.dtype(p.storage).name,
        jnp.dtype(p.compute).name,
        jnp.dtype(p.output).name,
        f"c{int(p.compensated)}",
    ]
    if not p.is_uniform():
        parts.append(
            "ph[" + ",".join(f"{ph}:{dt}" for ph, dt in p.phase_map().items()) + "]"
        )
    return "-".join(parts)


def _plan_key(pol: PrecisionPolicy) -> str:
    """Key of what a built plan actually depends on: the storage dtype (the
    device container) and the SpMV-phase accumulator (the engine).  Narrower
    than :func:`policy_key` on purpose — a reorth/alpha_beta/ritz split
    changes per-query arithmetic (its ``Ops`` record, keyed per policy in
    ``_Prepared.ops_for``), never the converted operator, so e.g. FDF and
    FDF[reorth=f32] share one plan instead of double-converting."""
    return "-".join(
        (jnp.dtype(pol.storage).name, jnp.dtype(pol.phase_dtype("spmv")).name)
    )


# Policy the plan phase assumes when ``policy="auto"`` is requested: the
# ladder's f32-storage rung, so coercion never rounds the input below what
# any rung needs; each rung's own operators build lazily per policy_key.
_AUTO_PLAN_POLICY = "FFF"


def _plan_policy(policy) -> PrecisionPolicy:
    """The policy a session plans/coerces with (resolves "auto" to the
    ladder-neutral f32 rung; see :func:`auto_ladder`)."""
    return resolve_policy(_AUTO_PLAN_POLICY if is_auto_policy(policy) else policy)


def config_fingerprint(cfg: SolverConfig, fields: Optional[Sequence[str]] = None) -> str:
    """Stable digest of a :class:`SolverConfig` (or the ``fields`` subset).

    ``policy`` is normalized through :func:`resolve_policy` and hashed by
    name + dtype triple, so a config carrying a ``PrecisionPolicy`` instance
    fingerprints identically to one carrying the policy's name — passing
    ``policy=FDF`` must hit the same cache entry as ``policy="FDF"``.
    """
    if fields is not None:
        names = tuple(fields)
    else:
        names = tuple(f.name for f in dataclasses.fields(cfg))
    parts = []
    for name in sorted(names):
        v = getattr(cfg, name)
        if name == "policy":
            if is_auto_policy(v):
                v = ("auto", "auto")  # the ladder, not any one rung
            else:
                p = resolve_policy(v)
                v = (p.name, policy_key(p))
        parts.append(f"{name}={v!r}")
    return hashlib.blake2b("|".join(parts).encode(), digest_size=12).hexdigest()


@dataclasses.dataclass(frozen=True, eq=False)
class EigQuery:
    """One solve request against a prepared session.

    Every field except ``k`` defaults to the session's configuration
    (``_UNSET`` = inherit); explicit values — including ``None`` where that
    is meaningful, e.g. ``tol=None`` for fixed-iteration mode — override it.
    Plain dicts (``{"k": 8, "tol": 1e-6}``) and bare ints coerce.
    ``policy`` accepts everything :func:`repro.api.resolve_policy` does plus
    ``"auto"`` (the accuracy-driven escalation ladder; such queries solve
    individually, never grouped).
    """

    k: int
    policy: Any = None
    tol: Any = _UNSET
    num_iters: Any = _UNSET
    reorth: Any = _UNSET
    seed: Any = _UNSET
    v0: Any = None
    subspace: Any = _UNSET
    max_restarts: Any = _UNSET
    jacobi: Any = _UNSET
    recovery: Any = _UNSET


# recovery="auto" escalation bounds: total attempts (the first solve plus up
# to five recovery actions) and how many fresh start vectors a lucky
# breakdown may burn before it is treated as structural and re-raised.
_MAX_RECOVERY_ATTEMPTS = 6
_MAX_RESEEDS = 2


def _classify_failure(exc) -> Optional[str]:
    """Map an in-solve exception to a ``recovery="auto"`` action, or None
    when no documented recovery applies (the error re-raises unchanged).

    Classification is deliberately conservative: only errors whose shape
    identifies a *transient or escapable* failure mode map to an action —
    user errors (``ValueError``/``TypeError`` from validation) never retry.
    """
    from ..core.lanczos import NumericalBreakdown as _NB

    if isinstance(exc, _NB):
        # A lucky breakdown (the Krylov space closed early) wants a new
        # start vector; non-finite recurrence scalars want more headroom.
        return "reseed" if exc.kind == "beta_underflow" else "escalate_policy"
    msg = str(exc)
    if (
        isinstance(exc, MemoryError)
        or "RESOURCE_EXHAUSTED" in msg
        or "out of memory" in msg.lower()
    ):
        return "fallback_chunked"
    mod = type(exc).__module__ or ""
    looks_kernel = (
        "lowering" in msg.lower() or "Mosaic" in msg or "pallas" in msg.lower()
    )
    from ..testing.faults import InjectedKernelError

    if isinstance(exc, InjectedKernelError):
        return "unfuse"
    if looks_kernel and (
        mod.startswith("jax")
        or mod.startswith("jaxlib")
        or isinstance(exc, (RuntimeError, NotImplementedError))
    ):
        return "unfuse"
    return None


def _policy_rank(pol: PrecisionPolicy) -> tuple:
    """Orderable cost/headroom rank of a policy: compute width first (what
    breakdown escalation buys), then compensation, then storage width —
    matching :func:`auto_ladder`'s cheapest-first ordering."""
    p = pol.effective()
    return (
        jnp.dtype(p.compute).itemsize,
        int(bool(p.compensated)),
        jnp.dtype(p.storage).itemsize,
    )


def _next_rung(pol: PrecisionPolicy) -> Optional[PrecisionPolicy]:
    """The cheapest :func:`auto_ladder` rung strictly above ``pol`` in
    compute headroom, or None when ``pol`` already tops the ladder."""
    cur = _policy_rank(pol)
    for rung in auto_ladder():
        cand = resolve_policy(rung).effective()
        if _policy_rank(cand) > cur:
            return cand
    return None


def _as_query(q) -> EigQuery:
    if isinstance(q, EigQuery):
        return q
    if isinstance(q, dict):
        return EigQuery(**q)
    if isinstance(q, (int, np.integer)):
        return EigQuery(k=int(q))
    raise TypeError(
        f"eigsh_many query must be an EigQuery, a dict of its fields, or an "
        f"int k; got {type(q).__name__}"
    )


def _norm_group_key(q: "_NormQuery") -> tuple:
    """Group-compatibility key of a normalized query: queries sharing it are
    answered by ONE Lanczos sweep (``eigsh_many`` groups by exactly this; the
    serving scheduler coalesces queued queries by it).  ``recovery`` joins
    the key: a recovering sweep may escalate policy / unfuse / reseed, so a
    ``recovery="none"`` query must never ride along with it."""
    return (q.backend, q.pkey, q.pol.name, q.reorth, q.jacobi, q.recovery)


class _NormQuery(NamedTuple):
    """A query with every field resolved against the session defaults."""

    idx: int
    k: int
    pol: PrecisionPolicy  # effective()
    pkey: str
    backend: str
    reorth: str
    tol_req: Optional[float]
    tol_eff: float
    num_iters: Optional[int]
    m: int  # fixed-m subspace this query needs
    subspace: Optional[int]
    max_restarts: int
    seed: int
    v0: Any
    jacobi: str
    start_key: str
    recovery: str  # "none" | "raise" | "auto"
    ckpt_dir: Optional[str]  # solve-checkpoint directory (None = off)
    ckpt_every: int  # chunked host loop: steps between snapshots


@dataclasses.dataclass
class _Prepared:
    """One built execution plan: a device operator (single/chunked) or a
    shard set (distributed), plus what building it cost."""

    kind: str  # "single" | "chunked" | "distributed"
    operator: Optional[LinearOperator]
    shards: Optional[PreparedShards]
    spmv_format: Any
    engine: Optional[SpmvEngine]
    build_s: float = 0.0
    conversions: int = 0
    tuner_probes: int = 0
    # Arithmetic-kernel records (core.lanczos.Ops) memoized per policy: the
    # jitted Lanczos loop is keyed on the record's identity, so reusing one
    # record across queries turns every repeat solve into an XLA compile
    # cache hit — without this, "zero-conversion" executes still re-trace.
    ops_cache: Dict[tuple, Any] = dataclasses.field(default_factory=dict)

    def ops_for(self, pol: PrecisionPolicy, fused: Optional[bool] = None):
        from ..core.lanczos import ops_for_operator

        eng = getattr(self.operator, "engine", None)
        plan = getattr(eng, "iteration_plan", None)
        # The resolved update mode joins the memo key so env-pin changes
        # (REPRO_FUSED_LANCZOS / REPRO_ITER_UPDATE) between executes on one
        # warm session can never serve a stale record.
        mode = resolve_update_mode(pol, plan=plan, fused=fused)
        key = (pol, fused, mode)
        ops = self.ops_cache.get(key)
        if ops is None:
            ops = ops_for_operator(self.operator, pol, fused=fused)
            self.ops_cache[key] = ops
        return ops


def _op_format(op) -> str:
    """SpMV layout label of a caller-provided operator."""
    fmt = getattr(op, "spmv_format", None)
    if fmt is not None:
        return fmt
    if isinstance(op, DenseOperator):
        return "dense"
    return "matfree"


class EigenSession:
    """Prepared solve state for one matrix; see the module docstring.

    Build one with :func:`prepare` (direct construction is supported but
    skips the frontend's session cache).  Concurrent use is safe but
    serialized: a session runs one query batch at a time (an internal lock
    — the shared operators and counters are single-stream); distinct
    sessions run in parallel.

    Attributes:
      cfg: the layout/default configuration the session was prepared with.
      n: problem dimension.
      csr: the owned host CSR (None for matrix-free/dense inputs).
      fingerprint: content+config digest keying the frontend cache (None
        when the input has no fingerprintable bytes, or when the session was
        built directly — digests are computed only for the cache's benefit).
      prepare_s: wall seconds the eager plan phase took.
      stats: {"queries", "sweeps", "cache_hits"} counters.
    """

    # Checked by repro.analysis C001: the prepared-plan cache is mutated
    # only under the build lock (queries hold _query_lock, which is a
    # different lock — reads of _prepared race only with idempotent
    # inserts, and insertion goes through _build_lock).
    _GUARDED_BY = {"_prepared": "_build_lock"}

    def __init__(
        self,
        A,
        config: Optional[SolverConfig] = None,
        *,
        mesh=None,
        n: Optional[int] = None,
        _coerced: Optional[CoercedInput] = None,
    ):
        cfg = config or SolverConfig()
        if cfg.format not in ("auto",) + FORMATS:
            raise ValueError(
                f"unknown SpMV format {cfg.format!r}; expected 'auto' or one of {FORMATS}"
            )
        self.cfg = cfg
        self.mesh = mesh
        self._default_mesh = None
        t0 = time.perf_counter()
        conv0, probes0 = conversion_count(), tuner_probe_count()
        pol0 = _plan_policy(cfg.policy).effective()
        ci = _coerced or coerce_input(A, n=n, storage_dtype=pol0.storage)
        self.op, self.csr, self.n = ci.operator, ci.csr, ci.n
        # Dense inputs keep the ORIGINAL array so a later query with a
        # different storage dtype re-coerces from the source, not from an
        # already-rounded copy.
        self._dense = A if isinstance(A, (np.ndarray, jax.Array)) else None
        self.device_count = mesh.size if mesh is not None else len(jax.devices())
        self.matrix_fingerprint = ci.fingerprint
        self.fingerprint = _session_key(ci.fingerprint, cfg, mesh) if ci.fingerprint else None
        self._prepared: Dict[Tuple[str, str], _Prepared] = {}
        self._verify_a = None  # lazy f64 matrix for the auto ladder's verification
        self._layout_stats = None  # lazy SpmvStats of the CSR (device residency)
        self._build_lock = threading.Lock()
        self._query_lock = threading.RLock()  # queries serialize per session
        self.stats = {"queries": 0, "sweeps": 0, "cache_hits": 0, "recoveries": 0}
        self.prepare_s = time.perf_counter() - t0
        self.prepare_conversions = conversion_count() - conv0
        self.prepare_tuner_probes = tuner_probe_count() - probes0
        # Coercion cost not yet attributed to any result: the first query
        # that builds a plan claims it into its timings["prepare_s"] (a
        # warmup() claims it into session.prepare_s instead).
        self._unclaimed_init_s = self.prepare_s

    def warmup(self) -> "EigenSession":
        """Eagerly build the plan for the configured placement and default
        policy, so :func:`prepare` — not the first query — pays the
        conversion/tuning cost.  (Construction alone builds lazily: the
        frontend's one-call path lets the first query build, so that call's
        counters honestly report what it paid.)"""
        pol0 = _plan_policy(self.cfg.policy).effective()
        backend0 = self._resolve_backend(self.cfg.tol, pol0, self.cfg.num_iters or 0)
        prep, built = self._ensure(backend0, pol0)
        if built:
            self.prepare_s += prep.build_s
            self.prepare_conversions += prep.conversions
            self.prepare_tuner_probes += prep.tuner_probes
        self._unclaimed_init_s = 0.0  # prepare() paid it; queries report 0
        return self

    def _claim_init_s(self) -> float:
        s, self._unclaimed_init_s = self._unclaimed_init_s, 0.0
        return s

    def _own_data(self) -> None:
        """Snapshot the host-side problem data (CSR arrays / dense source) so
        the session stops aliasing the caller's buffers.  Called when a
        session enters the frontend cache: its fingerprint pins the bytes it
        was built from, and a later in-place mutation by the caller must not
        leak into lazily-built per-policy plans — that would serve a stale
        plan for byte-identical input, the exact thing the digest forbids."""
        from ..sparse.formats import CSR as _CSR

        if isinstance(self.csr, DiskCSR):
            # Disk-backed sessions keep the mapping, never a RAM snapshot —
            # materializing would defeat the out-of-core budget, and the
            # sampled fingerprint already keys the on-disk content.
            self._verify_a = None
            return
        if self.csr is not None:
            self.csr = _CSR(
                indptr=np.array(self.csr.indptr, copy=True),
                indices=np.array(self.csr.indices, copy=True),
                data=np.array(self.csr.data, copy=True),
                shape=self.csr.shape,
            )
        if self._dense is not None:
            self._dense = np.array(self._dense, copy=True)
        # Rebuild the verification copy from the snapshotted data on demand
        # (it may alias the caller's pre-snapshot buffers).
        self._verify_a = None

    def approx_bytes(self) -> int:
        """Rough memory footprint of what caching this session pins: the host
        problem data plus ~one converted (device) copy per built plan —
        lazily-built per-policy plans grow it, and the cache re-enforces its
        byte budget after each build.  An estimate, not an audit."""
        if isinstance(self.csr, DiskCSR):
            # Disk pages are the kernel's to cache and reclaim; the session
            # pins only O(n) planning metadata per built plan.
            return int(self.csr.indptr.nbytes) * (2 + len(self._prepared))
        if self.csr is not None:
            base = self.csr.indptr.nbytes + self.csr.indices.nbytes + self.csr.data.nbytes
        elif self._dense is not None:
            base = int(getattr(self._dense, "nbytes", 0))
        else:
            base = 0
        return base * (2 + len(self._prepared))

    # ------------------------------------------------------------ planning

    def _resolve_backend(
        self, tol: Optional[float], pol: PrecisionPolicy, steps: int
    ) -> str:
        return select_backend(
            self.cfg.backend,
            has_matrix=self.csr is not None,
            nnz=self.csr.nnz if self.csr is not None else 0,
            tol=tol,
            device_count=self.device_count,
            mesh_given=self.mesh is not None,
            disk_bytes=(
                self.csr.nbytes_on_disk() if isinstance(self.csr, DiskCSR) else None
            ),
            working_set=lambda: self._working_set(pol, steps),
        )

    def _matrix_stats(self):
        """Layout statistics of the CSR, computed once and shared with the
        single-device engine build (the block census only where auto
        selection may pick BSR, as in ``make_engine``)."""
        if self._layout_stats is None:
            self._layout_stats = matrix_stats(
                self.csr, with_blocks=self.cfg.format == "auto"
            )
        return self._layout_stats

    def _working_set(self, pol: PrecisionPolicy, steps: int) -> int:
        """Device bytes of an in-core solve of ``steps`` Lanczos steps: the
        layout the single-device engine would build, and the basis."""
        stats = self._matrix_stats()
        fmt = choose_format(stats) if self.cfg.format == "auto" else self.cfg.format
        return device_working_set(stats, fmt, pol.storage, steps)

    def _mesh_for_solve(self):
        from jax.sharding import Mesh

        if self.mesh is not None:
            return self.mesh
        if self._default_mesh is None:
            devs = np.array(jax.devices())
            self._default_mesh = Mesh(devs.reshape(len(devs)), (self.cfg.axis,))
        return self._default_mesh

    def _ensure(self, backend: str, pol: PrecisionPolicy) -> Tuple[_Prepared, bool]:
        """Prepared plan for (placement, policy dtypes): build once, reuse.
        Serialized: concurrent queries must not double-build one plan."""
        kind = backend if backend in ("distributed", "chunked") else "single"
        key = (kind, _plan_key(pol))
        with self._build_lock:
            hit = self._prepared.get(key)
            if hit is not None:
                return hit, False
            t0 = time.perf_counter()
            conv0, probes0 = conversion_count(), tuner_probe_count()
            if kind == "distributed":
                prep = self._build_distributed(pol)
            elif kind == "chunked":
                prep = self._build_chunked(pol)
            else:
                prep = self._build_single(pol)
            prep.build_s = time.perf_counter() - t0
            prep.conversions = conversion_count() - conv0
            prep.tuner_probes = tuner_probe_count() - probes0
            self._prepared[key] = prep
        # A lazy build grew this session's footprint: let the cache re-check
        # its byte budget (no-op for sessions that were never cached).
        _cache_enforce_budget()
        return prep, True

    def _build_single(self, pol: PrecisionPolicy) -> _Prepared:
        if self.op is not None:
            op = self.op
            if isinstance(op, DenseOperator) and self._dense is not None:
                want = jnp.dtype(pol.storage)
                if jnp.dtype(op.a.dtype) != want:
                    op = DenseOperator(jnp.asarray(self._dense, dtype=want))
            return _Prepared("single", op, None, _op_format(op), None)
        engine = make_engine(
            self.csr,
            self.cfg.format,
            stats=self._layout_stats,
            accum_dtype=pol.phase_dtype("spmv"),
            storage_dtype=pol.storage,
        )
        op = make_operator(self.csr, dtype=pol.storage, engine=engine)
        return _Prepared("single", op, None, engine.format, engine)

    def _build_chunked(self, pol: PrecisionPolicy) -> _Prepared:
        cfg, csr = self.cfg, self.csr
        fmt = cfg.format if cfg.format != "auto" else "ell"
        # Build the ELL engine first even under "auto": its tiles determine
        # the per-chunk row padding, which the selection below must charge.
        engine = make_engine(
            csr,
            fmt,
            accum_dtype=pol.phase_dtype("spmv"),
            allowed=("coo", "ell"),  # per-chunk BSR/hybrid staging not implemented
            storage_dtype=pol.storage,
        )
        if cfg.format == "auto":
            # The chunked engine stages ELL per chunk at each chunk's OWN
            # 128-aligned max row width, so its ELL eligibility must be
            # judged on that realized layout — the whole-matrix selector's
            # global-max-row overhead would veto exactly the hub matrices
            # the per-chunk split handles (one hub inflates one chunk, not
            # all), while narrow matrices still lose to the 128-lane pad.
            # Memory being the backend's constraint, the padded footprint
            # must also not dwarf the COO triplets it replaces.
            from ..core.operators import chunk_row_bounds, chunk_rows_pad
            from ..kernels.engine import ell_overhead_bound

            row_nnz = csr.row_nnz()
            padded_slots = 0
            for r0, r1 in chunk_row_bounds(csr.indptr, csr.n, cfg.chunk_nnz):
                w = int(row_nnz[r0:r1].max()) if r1 > r0 else 1
                rows_pad = chunk_rows_pad(r1 - r0, engine.tiles.block_r, pol.storage)
                padded_slots += rows_pad * (-(-max(1, w) // 128) * 128)
            nnz = max(1, csr.nnz)
            ell_bytes = padded_slots * (jnp.dtype(pol.storage).itemsize + 4)
            overhead_ok = padded_slots / nnz <= ell_overhead_bound()
            if not (overhead_ok and ell_bytes <= 4 * nnz * 12):
                engine = make_engine(
                    csr,
                    "coo",
                    stats=engine.stats,
                    accum_dtype=pol.phase_dtype("spmv"),
                    storage_dtype=pol.storage,
                )
        # REPRO_CHUNK_STAGING pins the staged-chunk encoding for A/B runs,
        # overriding the config (ChunkedOperator validates the value).
        staging = envcfg.raw("REPRO_CHUNK_STAGING") or getattr(cfg, "staging", "f32")
        op = ChunkedOperator(
            csr,
            chunk_nnz=cfg.chunk_nnz,
            dtype=pol.storage,
            engine=engine,
            stage_depth=cfg.stage_depth,
            staging=staging,
            mesh=self.mesh,
            axis=cfg.axis,
        )
        return _Prepared("chunked", op, None, engine.format, engine)

    def _build_distributed(self, pol: PrecisionPolicy) -> _Prepared:
        mesh = self._mesh_for_solve()
        g = mesh.shape[self.cfg.axis]
        shards = prepare_sharded(self.csr, g, pol, self.cfg.format)
        return _Prepared("distributed", None, shards, shards.engine.format, shards.engine)

    # ----------------------------------------------------------- execution

    def eigsh(
        self,
        k: int,
        *,
        policy=None,
        tol=_UNSET,
        num_iters=_UNSET,
        reorth=_UNSET,
        v0=None,
        seed=_UNSET,
        subspace=_UNSET,
        max_restarts=_UNSET,
        jacobi=_UNSET,
        recovery=_UNSET,
    ) -> EigenResult:
        """Solve one query against the prepared plan.  Unset keywords inherit
        the session configuration; see :func:`repro.api.eigsh` for semantics."""
        q = EigQuery(
            k=k,
            policy=policy,
            tol=tol,
            num_iters=num_iters,
            reorth=reorth,
            seed=seed,
            v0=v0,
            subspace=subspace,
            max_restarts=max_restarts,
            jacobi=jacobi,
            recovery=recovery,
        )
        return self.eigsh_many([q])[0]

    @exact_matmuls
    def eigsh_many(self, queries, defaults: Optional[SolverConfig] = None) -> List[EigenResult]:
        """Batched execute: many ``(k, policy, tol, ...)`` queries, one matrix.

        Queries are grouped by (backend, policy, reorth, jacobi); each group
        (per start vector) runs one shared Lanczos sweep at the group's
        largest subspace and every member slices its Ritz pairs out of it.
        Groups differing only in start vector batch through the vmapped
        multi-start sweep when the operator supports it.  Results come back
        in input order, one :class:`EigenResult` per query.

        Merged groups run under the group's *most permissive* cost settings
        (largest ``num_iters``/``subspace``/``max_restarts``; a query with no
        budget lifts the cap for its restarted group) and its tightest
        ``tol`` — per-query step budgets are advisory under batching: the
        shared sweep can only make an individual answer more accurate, and
        its cost is paid once for the whole group.  Submit a query alone (or
        via :func:`repro.api.eigsh`) when its budget must bind exactly.
        """
        if not queries:
            return []
        cfg = defaults or self.cfg
        # Serialized: concurrent queries on ONE session would race the shared
        # operator counters and stats (distinct sessions still run parallel).
        with self._query_lock:
            raw = [_as_query(q) for q in queries]
            self.stats["queries"] += len(raw)
            results: List[Optional[EigenResult]] = [None] * len(raw)
            normal: List[_NormQuery] = []
            for i, rq in enumerate(raw):
                requested = rq.policy if rq.policy is not None else cfg.policy
                if is_auto_policy(requested):
                    # policy="auto" escalates through its own solve ladder;
                    # it never groups with fixed-policy queries.
                    results[i] = self._solve_auto(rq, cfg)
                else:
                    normal.append(self._normalize(rq, i, cfg))
            groups: Dict[tuple, List[_NormQuery]] = {}
            for q in normal:
                groups.setdefault(_norm_group_key(q), []).append(q)
            for group in groups.values():
                for idx, res in self._solve_group(group):
                    results[idx] = res
        return results  # type: ignore[return-value]

    def ensure_fingerprint(self) -> Optional[str]:
        """Content digest of this session's matrix, computing it on demand.

        Directly-constructed sessions skip the digest (it only exists for
        the frontend cache's benefit), but persistence needs one — the store
        keys entries by it and ``import_plans`` validates against it.  Still
        None for matrix-free inputs (no bytes to hash)."""
        if self.matrix_fingerprint is None:
            src = self.csr if self.csr is not None else self._dense
            if src is not None:
                self.matrix_fingerprint = traced_fingerprint(src)
        return self.matrix_fingerprint

    def group_key(self, query, defaults: Optional[SolverConfig] = None) -> Optional[tuple]:
        """Public group-compatibility predicate: the key :meth:`eigsh_many`
        groups by.  Two queries whose keys are equal (on the same session)
        are served by ONE shared Lanczos sweep; the serving scheduler
        (``repro.serving``) coalesces queued queries by exactly this key, so
        its batches can never mix what the session would not merge.

        Returns ``None`` for ``policy="auto"`` queries — the escalation
        ladder solves individually and never groups.  Raises the same
        ``ValueError`` as submitting the query would (``k`` out of range,
        infeasible ``num_iters``), so callers can validate at admission time.
        """
        cfg = defaults or self.cfg
        rq = _as_query(query)
        requested = rq.policy if rq.policy is not None else cfg.policy
        if is_auto_policy(requested):
            return None
        return _norm_group_key(self._normalize(rq, 0, cfg))

    # --------------------------------------------------- persistence hooks

    def export_state(self) -> dict:
        """Serializable snapshot of this session's built plans (the warm
        state a restarted server needs): per-plan device-container arrays +
        the engine configuration (format, accumulator dtype, tuned tiles).
        The header carries the repro version, the matrix fingerprint, and the
        layout-config fingerprint so :meth:`import_plans` can reject stale
        artifacts.  Arrays come back as npz-safe NumPy (bf16 values are
        stored widened to f32 with their dtype recorded).

        Only "single"-placement plans over explicit device containers (COO /
        ELL / BSR / hybrid) or dense operators export; chunked plans are
        host-resident anyway (nothing device-converted to save) and
        distributed plans are mesh-bound — both rebuild lazily on import.
        """
        from .. import __version__

        with self._build_lock:
            items = list(self._prepared.items())
        plans = []
        for (kind, plan_key), prep in items:
            if kind != "single" or prep.operator is None:
                continue
            exported = _export_operator(prep.operator)
            if exported is None:
                continue
            container, arrays = exported
            dtypes = {name: str(a.dtype) for name, a in arrays.items()}
            # bf16 has no native NumPy container format: widen to f32 for the
            # npz (lossless — f32 is a superset); import narrows back via the
            # recorded dtype.
            arrays = {
                name: (a.astype(np.float32) if str(a.dtype) == "bfloat16" else a)
                for name, a in arrays.items()
            }
            engine_cfg = None
            if prep.engine is not None:
                e = prep.engine
                engine_cfg = {
                    "format": e.format,
                    "accum_dtype": str(jnp.dtype(e.accum_dtype)),
                    "tiles": {
                        "block_r": int(e.tiles.block_r),
                        "block_w": int(e.tiles.block_w),
                        "block_size": int(e.tiles.block_size),
                    },
                    "interpret": bool(e.interpret),
                    "requested": e.requested,
                    "tiles_from": e.tiles_from,
                    "iteration_plan": (
                        e.iteration_plan.as_dict() if e.iteration_plan is not None else None
                    ),
                }
            fmt = prep.spmv_format
            plans.append(
                {
                    "plan_key": plan_key,
                    "container": container,
                    "spmv_format": fmt if isinstance(fmt, str) else str(fmt),
                    "engine": engine_cfg,
                    "dtypes": dtypes,
                    "arrays": arrays,
                }
            )
        state = {
            "schema": _EXPORT_SCHEMA,
            "repro_version": __version__,
            "matrix_fingerprint": self.ensure_fingerprint(),
            "layout_fingerprint": config_fingerprint(self.cfg, _LAYOUT_FIELDS),
            "layout": {f: repr(getattr(self.cfg, f)) for f in _LAYOUT_FIELDS},
            "n": int(self.n),
            "plans": plans,
        }
        if isinstance(self.csr, DiskCSR):
            # Disk-backed sessions persist a POINTER to the matrix, never its
            # payload: the store can revive the session by reopening the
            # mapping and re-checking the sampled fingerprint.
            state["matrix_ref"] = {
                "kind": "diskcsr",
                "path": self.csr.path,
                "fingerprint": self.ensure_fingerprint(),
            }
        return state

    def import_plans(self, state: dict) -> int:
        """Install plans exported by :meth:`export_state` into this session;
        returns how many were imported.  Containers are rebuilt with the
        plain device constructors — NO format conversion runs (the
        ``conversion_count()`` audit stays untouched) and the persisted tiles
        ride in, so no tuner probes either: the next query is a pure execute.

        Stale artifacts are *rejected, not trusted*: a mismatched schema,
        repro version, matrix fingerprint, layout fingerprint, or dimension
        warns and returns 0 — the session simply cold-rebuilds lazily, the
        same behaviour as having no persisted state at all.
        """
        from .. import __version__

        header_checks = (
            ("schema", state.get("schema"), _EXPORT_SCHEMA),
            ("repro_version", state.get("repro_version"), __version__),
            ("matrix_fingerprint", state.get("matrix_fingerprint"), self.ensure_fingerprint()),
            (
                "layout_fingerprint",
                state.get("layout_fingerprint"),
                config_fingerprint(self.cfg, _LAYOUT_FIELDS),
            ),
            ("n", state.get("n"), int(self.n)),
        )
        for field, got, want in header_checks:
            if got != want:
                warnings.warn(
                    f"stale persisted session rejected ({field}: saved {got!r} != "
                    f"current {want!r}); falling back to a cold rebuild",
                    stacklevel=2,
                )
                return 0
        imported = 0
        for plan in state.get("plans", ()):
            try:
                prep = _import_plan(plan, int(self.n))
            except Exception as exc:  # corrupt payload: warn, keep serving
                warnings.warn(
                    f"corrupt persisted plan {plan.get('plan_key')!r} skipped "
                    f"({type(exc).__name__}: {exc}); it will cold-rebuild on demand",
                    stacklevel=2,
                )
                continue
            key = ("single", str(plan["plan_key"]))
            with self._build_lock:
                if key not in self._prepared:
                    self._prepared[key] = prep
                    imported += 1
        return imported

    # ---------------------------------------------------------- internals

    def _normalize(self, q: EigQuery, idx: int, cfg: SolverConfig) -> _NormQuery:
        def pick(v, dflt):
            return dflt if v is _UNSET else v

        k = int(q.k)
        if k < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        if k > self.n:
            raise ValueError(f"k={k} exceeds the operator dimension n={self.n}")
        pol = resolve_policy(q.policy if q.policy is not None else cfg.policy).effective()
        tol_req = pick(q.tol, cfg.tol)
        num_iters = pick(q.num_iters, cfg.num_iters)
        backend = self._resolve_backend(tol_req, pol, num_iters if num_iters is not None else k)
        reorth_raw = pick(q.reorth, cfg.reorth)
        if backend == "restarted":
            if reorth_raw not in (None, "full"):
                warnings.warn(
                    f"reorth={reorth_raw!r} is ignored by the restarted backend: "
                    "thick restart requires full re-orthogonalization to keep "
                    "the locked Ritz block orthogonal",
                    stacklevel=4,
                )
            reorth = "full"
            if num_iters is not None and num_iters < k + 2:
                raise ValueError(
                    f"num_iters={num_iters} cannot fund a restarted solve for "
                    f"k={k} (the subspace needs at least k + 2 = {k + 2} steps); "
                    "raise num_iters or use backend='single'"
                )
        else:
            reorth = _resolve_reorth(reorth_raw, backend)
            if num_iters is not None and num_iters < k:
                # Validated per query: a merged group's shared (larger)
                # subspace must not mask an individually infeasible request.
                raise ValueError(f"num_iters must be >= k (got {num_iters} < {k})")
        max_restarts = int(pick(q.max_restarts, cfg.max_restarts))
        if backend == "restarted" and max_restarts < 1:
            raise ValueError(f"max_restarts must be >= 1, got {max_restarts}")
        recovery = pick(q.recovery, getattr(cfg, "recovery", None)) or "raise"
        if recovery not in ("none", "raise", "auto"):
            raise ValueError(
                f"recovery must be 'none', 'raise', or 'auto'; got {recovery!r}"
            )
        seed = int(pick(q.seed, cfg.seed))
        if q.v0 is not None:
            h = hashlib.blake2b(np.asarray(q.v0).tobytes(), digest_size=8)
            start_key = f"v0:{h.hexdigest()}"
        else:
            start_key = f"seed:{seed}"
        m = int(num_iters) if num_iters is not None else k
        return _NormQuery(
            idx=idx,
            k=k,
            pol=pol,
            pkey=policy_key(pol),
            backend=backend,
            reorth=reorth,
            tol_req=tol_req,
            tol_eff=tol_req if tol_req is not None else _default_tol(pol),
            num_iters=num_iters,
            m=m,
            subspace=pick(q.subspace, cfg.subspace),
            max_restarts=max_restarts,
            seed=seed,
            v0=q.v0,
            jacobi=pick(q.jacobi, cfg.jacobi),
            start_key=start_key,
            recovery=recovery,
            ckpt_dir=getattr(cfg, "checkpoint_dir", None),
            ckpt_every=int(getattr(cfg, "checkpoint_every", 8) or 8),
        )

    def _solve_auto(self, rq: EigQuery, cfg: SolverConfig) -> EigenResult:
        """Accuracy-driven policy selection: probe the escalation ladder
        (:func:`repro.core.precision.auto_ladder`, cheapest rung first),
        re-solving until the *measured* residuals meet the query's effective
        tolerance.  For explicit-matrix inputs each rung is judged on
        verified f64 reconstruction residuals ``||A x - lambda x||`` (the
        Ritz residual bound converges with the Krylov process regardless of
        storage precision, so it cannot expose a too-narrow rung — the
        paper's Fig. 4 measures exactly this reconstruction error); matrix-
        free inputs fall back to the engines' Ritz bound and converged
        flags.  Each rung reuses this session's per-policy operator cache,
        so escalation pays solves, not plans.  The attempt trail — policy
        tried, max relative residual, what it was judged on, tol, accepted —
        is recorded on the returned result as ``policy_escalations``."""
        attempts: List[dict] = []
        res: Optional[EigenResult] = None
        for rung in auto_ladder():
            nq = self._normalize(dataclasses.replace(rq, policy=rung), 0, cfg)
            ((_, res),) = self._solve_group([nq])
            verified = self._verified_rel_residuals(res)
            if verified is None:
                max_rel = float(
                    np.max(
                        res.residuals
                        / np.maximum(np.abs(np.asarray(res.eigenvalues, np.float64)), 1e-300)
                    )
                )
                accepted = bool(res.all_converged)
                kind = "ritz_bound"
            else:
                max_rel = float(np.max(verified))
                accepted = bool(np.all(verified <= nq.tol_eff))
                kind = "verified"
            attempts.append(
                {
                    "policy": res.policy,
                    "max_residual": max_rel,
                    "residual_kind": kind,
                    "tol": float(nq.tol_eff),
                    "converged": accepted,
                }
            )
            if accepted:
                break
        return dataclasses.replace(res, policy_escalations=attempts)

    def _verified_rel_residuals(self, res: EigenResult) -> Optional[np.ndarray]:
        """(k,) relative reconstruction residuals ``||A x_i - lambda_i x_i||
        / max(|lambda_i|, tiny)`` in f64 against the session's host-side
        matrix — the accuracy measurement driving ``policy="auto"``.  None
        for matrix-free inputs (nothing f64-exact to verify against)."""
        a = self._verify_matrix()
        if a is None:
            return None
        x = np.asarray(res.eigenvectors, dtype=np.float64)
        lam = np.asarray(res.eigenvalues, dtype=np.float64)
        r = a @ x - x * lam
        # Columns are unit-norm up to policy rounding; no normalization by
        # ||x|| — the same convention as the Ritz bound the flags use.
        return np.linalg.norm(r, axis=0) / np.maximum(np.abs(lam), 1e-300)

    def _verify_matrix(self):
        """f64 host copy of the matrix used by the auto ladder's residual
        verification; built once per session (every rung of every auto query
        reuses it — escalation pays solves, not O(nnz) rebuilds) and dropped
        when the cache snapshots the host data (``_own_data``)."""
        if self._verify_a is None:
            if isinstance(self.csr, DiskCSR) and (
                self.csr.nbytes_on_disk() > _DISK_VERIFY_MAX_BYTES
            ):
                # Too big to materialize: verification must not defeat the
                # out-of-core budget — the ladder falls back to Ritz bounds.
                return None
            if self.csr is not None:
                import scipy.sparse as sp

                self._verify_a = sp.csr_matrix(
                    (
                        np.asarray(self.csr.data, dtype=np.float64),
                        np.asarray(self.csr.indices),
                        np.asarray(self.csr.indptr),
                    ),
                    shape=self.csr.shape,
                )
            elif self._dense is not None:
                self._verify_a = np.asarray(self._dense, dtype=np.float64)
        return self._verify_a

    def _nnz_estimate(self) -> int:
        """Matrix work per matvec for the precision audit: nnz for explicit
        sparse inputs, n^2 for dense, n for matrix-free (a black-box matvec
        is charged as one pass over the vector)."""
        if self.csr is not None:
            return int(self.csr.nnz)
        if self._dense is not None:
            return int(self.n) * int(self.n)
        return int(self.n)

    def _solve_group(self, group: List[_NormQuery]):
        if group[0].recovery == "auto":
            return self._solve_group_recovering(group)
        return self._solve_group_inner(group)

    def _solve_group_inner(self, group: List[_NormQuery], fused_pin: Optional[bool] = None):
        backend, pol = group[0].backend, group[0].pol
        prep, built = self._ensure(backend, pol)
        if not built:
            self.stats["cache_hits"] += 1
        starts: "OrderedDict[str, List[_NormQuery]]" = OrderedDict()
        for q in group:
            starts.setdefault(q.start_key, []).append(q)
        if backend == "restarted":
            return self._run_restarted(starts, prep, built)
        if backend == "distributed":
            return self._run_distributed(starts, prep, built)
        return self._run_fixed(starts, prep, built, backend, fused_pin=fused_pin)

    def _solve_group_recovering(self, group: List[_NormQuery]):
        """``recovery="auto"``: run the group, catching in-solve failures and
        escalating along the documented axes — re-seed the start vector on a
        lucky breakdown (beta underflow: the Krylov space closed early, a
        different start almost surely escapes), one precision rung up on
        overflow/NaN (:func:`auto_ladder` order), fused->unfused on kernel
        lowering/execution errors, single->chunked on device OOM.  Every
        action is appended to a trail that rides out on the results as
        ``recovery_trail``; an unrecoverable (or exhausted) failure re-raises
        the original error with the trail attached when it is a
        :class:`NumericalBreakdown`."""
        trail: List[dict] = []
        qs = list(group)
        fused_pin: Optional[bool] = None
        reseeds = 0
        last_exc: Optional[BaseException] = None
        for attempt in range(_MAX_RECOVERY_ATTEMPTS):
            try:
                out = self._solve_group_inner(qs, fused_pin=fused_pin)
            except Exception as exc:
                last_exc = exc
                action = _classify_failure(exc)
                if action is None:
                    raise self._attach_trail(exc, trail)
                entry = {
                    "action": action,
                    "error": f"{type(exc).__name__}: {exc}",
                    "attempt": attempt,
                }
                if isinstance(exc, NumericalBreakdown):
                    entry["kind"] = exc.kind
                    entry["iteration"] = exc.iteration
                if action == "reseed":
                    if reseeds >= _MAX_RESEEDS:
                        raise self._attach_trail(exc, trail)
                    reseeds += 1
                    seed2 = qs[0].seed + 1000 + attempt
                    entry["from"] = qs[0].start_key
                    entry["to"] = f"seed:{seed2}"
                    qs = [
                        q._replace(seed=seed2, v0=None, start_key=f"seed:{seed2}")
                        for q in qs
                    ]
                elif action == "escalate_policy":
                    nxt = _next_rung(qs[0].pol)
                    if nxt is None:  # already at the ladder top
                        raise self._attach_trail(exc, trail)
                    entry["from"] = qs[0].pol.name
                    entry["to"] = nxt.name
                    qs = [q._replace(pol=nxt, pkey=policy_key(nxt)) for q in qs]
                elif action == "unfuse":
                    if fused_pin is False or qs[0].backend == "distributed":
                        raise self._attach_trail(exc, trail)
                    entry["from"] = "fused"
                    entry["to"] = "unfused"
                    fused_pin = False
                elif action == "fallback_chunked":
                    if qs[0].backend == "chunked" or self.csr is None:
                        raise self._attach_trail(exc, trail)
                    entry["from"] = qs[0].backend
                    entry["to"] = "chunked"
                    qs = [q._replace(backend="chunked") for q in qs]
                trail.append(entry)
                self.stats["recoveries"] = self.stats.get("recoveries", 0) + 1
                continue
            if trail:
                out = [
                    (idx, dataclasses.replace(res, recovery_trail=list(trail)))
                    for idx, res in out
                ]
            return out
        raise self._attach_trail(last_exc, trail)

    @staticmethod
    def _attach_trail(exc, trail):
        if isinstance(exc, NumericalBreakdown) and trail:
            exc.recovery_trail = list(trail)
        return exc

    def _finish(
        self,
        q: _NormQuery,
        prep: _Prepared,
        built: bool,
        *,
        eigenvalues,
        eigenvectors,
        residuals,
        evals_f64,
        iterations,
        restarts,
        timings,
        partition,
        spmv_format,
        tridiag,
        group_size,
    ) -> Tuple[int, EigenResult]:
        with span("repro.session.finish"):
            # Judge convergence on the engines' full-precision eigenvalues so the
            # flags agree with the restarted engine's own stopping decision (the
            # output-dtype cast could flip a boundary pair).
            lam = np.abs(np.asarray(evals_f64, dtype=np.float64))
            converged = np.asarray(residuals) <= q.tol_eff * np.maximum(lam, 1e-300)
            t = dict(timings)
            solve_s = float(t.get("total_s", 0.0))
            t["solve_s"] = solve_s
            # A building call also claims the session's so-far-unattributed init
            # (coercion/fingerprint) cost, so first-call totals cover real wall
            # time; pure executes report 0.
            t["prepare_s"] = (prep.build_s + self._claim_init_s()) if built else 0.0
            t["total_s"] = t["prepare_s"] + solve_s
            if group_size > 1:
                t["amortized_over"] = float(group_size)
            part = dict(partition) if partition else {}
            spmv = dict(part.get("spmv", {}))
            if not spmv:
                if prep.engine is not None:
                    spmv = prep.engine.describe()
                else:
                    fmt0 = spmv_format[0] if isinstance(spmv_format, tuple) else spmv_format
                    spmv = {"format": fmt0}
            # The reuse contract, verified: what THIS call actually paid.
            spmv["conversions"] = prep.conversions if built else 0
            spmv["tuner_probes"] = prep.tuner_probes if built else 0
            spmv["reused"] = not built
            # Iteration-plan provenance: what the tuner (or mode table) chose,
            # plus the update mode this query's policy actually allows — the
            # policy gate can demote a fused plan (compensated / phase splits).
            iter_plan = getattr(prep.engine, "iteration_plan", None)
            effective = resolve_update_mode(q.pol, plan=iter_plan)
            if spmv.get("iteration_plan") or iter_plan is not None:
                rec = dict(spmv.get("iteration_plan") or iter_plan.as_dict())
                rec["effective"] = effective
                spmv["iteration_plan"] = rec
            # Which phases ran as Mosaic kernels and which as XLA (the restarted
            # engine's update is its own jitted jnp, never the fused kernel).
            eng = prep.engine
            spmv["kernels"] = phase_executors(
                eng.format if eng is not None else "coo",
                eng.interpret if eng is not None else default_interpret(),
                "unfused" if q.backend == "restarted" else effective,
                q.pol.compute,
            )
            # Per-phase precision audit: the phase map this solve executed and a
            # model-based count of element ops per dtype (how the "this split
            # reduced f64 work" claim is verified — see precision.phase_op_counts).
            spmv["precision"] = {
                "policy": q.pol.name,
                "phase_map": q.pol.phase_map(),
                "compensated": bool(q.pol.compensated),
                "uniform": q.pol.is_uniform(),
                "ops_by_dtype": phase_op_counts(
                    q.pol,
                    n=self.n,
                    nnz=self._nnz_estimate(),
                    m=int(iterations),
                    k=q.k,
                    reorth=q.reorth,
                ),
            }
            # Jaxpr-measured counterpart (repro.analysis P004 ground truth):
            # traces the session's own operator — no execution, no data copies —
            # so it is opt-in; a trace failure degrades to an error note, never
            # a failed solve.
            if envcfg.get_bool("REPRO_PRECISION_MEASURE"):
                if prep.operator is None:
                    spmv["precision"]["ops_by_dtype_measured"] = {
                        "error": "no single-device operator to trace (distributed plan)"
                    }
                else:
                    try:
                        from ..analysis.precision_flow import measure_session_ops

                        spmv["precision"]["ops_by_dtype_measured"] = measure_session_ops(
                            q.pol,
                            prep.operator,
                            backend=q.backend,
                            m=max(int(iterations), 1),
                            k=q.k,
                            reorth=q.reorth,
                            jacobi=q.jacobi,
                        )
                    except Exception as exc:  # pragma: no cover - defensive
                        spmv["precision"]["ops_by_dtype_measured"] = {"error": str(exc)}
            part["spmv"] = spmv
            res = EigenResult(
                eigenvalues=eigenvalues,
                eigenvectors=eigenvectors,
                residuals=np.asarray(residuals, dtype=np.float64),
                converged=converged,
                iterations=int(iterations),
                restarts=int(restarts),
                k=q.k,
                n=self.n,
                backend=q.backend,
                policy=q.pol.name,
                tol=q.tol_eff,
                num_devices=self.device_count if q.backend == "distributed" else 1,
                partition=part,
                timings=t,
                spmv_format=spmv_format,
                tridiag=tridiag,
                session_reuse=not built,
            )
            return q.idx, res

    def _chunked_partition(self, prep: _Prepared, staging_before: dict) -> dict:
        op = prep.operator
        staging = op.staging_stats()
        # transfers / bytes / stage seconds are per-call costs (the
        # operator's counters are cumulative across a reused session's
        # queries); conversions stays the one-time build count and
        # max_resident the residency bound — both are invariants of the
        # plan, not per-call costs.  Bandwidth and compression are derived
        # from the per-call deltas.
        for key in ("transfers", "bytes_staged", "bytes_plain", "stage_s"):
            staging[key] = staging[key] - staging_before.get(key, 0)
        staging["effective_bandwidth_gbps"] = (
            staging["bytes_plain"] / staging["stage_s"] / 1e9
            if staging["stage_s"] > 0
            else 0.0
        )
        staging["compression_ratio"] = (
            staging["bytes_plain"] / staging["bytes_staged"]
            if staging["bytes_staged"]
            else 1.0
        )
        spmv = op.engine.describe() if op.engine is not None else {"format": "coo"}
        spmv["staging"] = staging  # ISSUE contract: partition["spmv"]["staging"]
        return {
            "num_chunks": op.num_chunks,
            "stage_depth": op.stage_depth,
            "disk_backed": bool(getattr(op, "disk_backed", False)),
            "staging": staging,  # legacy location, kept for existing readers
            "spmv": spmv,
        }

    def _solve_checkpoint(self, q: _NormQuery, pol, backend: str, k: int, m: int):
        """(store, token) for this sweep's snapshots, or None when solve
        checkpointing is off.  The token hashes the matrix fingerprint plus
        every parameter that shapes the trajectory — budget knobs
        (max_restarts, the chunked loop's snapshot period) stay out so an
        interrupted run relaunched with a different budget still resumes."""
        if q.ckpt_dir is None:
            return None
        from ..serving.store import SolveCheckpoint

        store = SolveCheckpoint(q.ckpt_dir)
        token = SolveCheckpoint.token(
            self.ensure_fingerprint(),
            backend=backend,
            policy=pol.name,
            k=k,
            m=m,
            start=q.start_key,
            tol=q.tol_eff,
            reorth=q.reorth,
        )
        return store, token

    def _run_fixed(
        self,
        starts,
        prep: _Prepared,
        built: bool,
        backend: str,
        fused_pin: Optional[bool] = None,
    ):
        out = []
        pol = next(iter(starts.values()))[0].pol
        all_qs = [q for qs in starts.values() for q in qs]
        reorth, jacobi = all_qs[0].reorth, all_qs[0].jacobi
        if len(starts) > 1 and self._vmappable(prep):
            out.extend(self._run_fixed_multistart(starts, prep, built))
            return out
        for qs in starts.values():
            k_max = max(q.k for q in qs)
            m = max(q.m for q in qs)
            staging0 = dict(prep.operator.staging) if backend == "chunked" else {}
            ckpt = None
            if backend == "chunked":  # only the host loop can snapshot
                pair = self._solve_checkpoint(qs[0], pol, backend, k_max, m)
                if pair is not None:
                    # 4th element: the operator itself, so the host loop can
                    # checkpoint/restore the chunk cursor *inside* a step.
                    ckpt = (*pair, qs[0].ckpt_every, prep.operator)
            sweep = solve_fixed(
                prep.operator,
                k_max,
                policy=pol,
                reorth=reorth,
                num_iters=m,
                v1=qs[0].v0,
                seed=qs[0].seed,
                jacobi=jacobi,
                ops=prep.ops_for(pol, fused=fused_pin),
                probe=qs[0].recovery != "none",
                checkpoint=ckpt,
            )
            self.stats["sweeps"] += 1
            partition = (
                self._chunked_partition(prep, staging0) if backend == "chunked" else {}
            )
            for q in qs:
                out.append(
                    self._finish(
                        q,
                        prep,
                        built,
                        eigenvalues=sweep.eigenvalues[: q.k],
                        eigenvectors=sweep.eigenvectors[:, : q.k],
                        residuals=sweep.residuals[: q.k],
                        evals_f64=sweep.eigenvalues_f64[: q.k],
                        iterations=sweep.iterations,
                        restarts=0,
                        timings=sweep.timings,
                        partition=partition,
                        spmv_format=prep.spmv_format,
                        tridiag=sweep.tridiag,
                        group_size=len(qs),
                    )
                )
        return out

    def _vmappable(self, prep: _Prepared) -> bool:
        """Is this operator's matvec safe under ``jax.vmap``?  Dense matmul
        and the COO ``segment_sum`` path batch cleanly; the Pallas kernel
        layouts are excluded (their interpret-mode batching rule is
        unvalidated), as is the host-loop chunked operator."""
        op = prep.operator
        if isinstance(op, DenseOperator):
            return True
        if isinstance(op, SparseOperator):
            if op.engine is not None:
                return op.engine.format == "coo"
            return op.impl == "coo"
        return False

    def _run_fixed_multistart(self, starts, prep: _Prepared, built: bool):
        """One vmapped Lanczos sweep over all start vectors of a group."""
        out = []
        all_qs = [q for qs in starts.values() for q in qs]
        pol, reorth, jacobi = all_qs[0].pol, all_qs[0].reorth, all_qs[0].jacobi
        m = max(q.m for q in all_qs)
        v1s = []
        for qs in starts.values():
            q0 = qs[0]
            if q0.v0 is not None:
                v1s.append(jnp.asarray(q0.v0, dtype=pol.compute))
            else:
                v1s.append(
                    jax.random.normal(jax.random.PRNGKey(q0.seed), (self.n,), dtype=pol.compute)
                )
        t0 = time.perf_counter()
        batch = lanczos_tridiag_multi(
            prep.operator.bound_matvec(pol),
            jnp.stack(v1s),
            m,
            pol,
            reorth=reorth,
            ops=prep.ops_for(pol, fused=False),
        )
        batch = jax.tree.map(lambda x: x.block_until_ready(), batch)
        t_lanczos = time.perf_counter() - t0
        self.stats["sweeps"] += 1
        for s, qs in enumerate(starts.values()):
            lres = LanczosResult(
                alpha=batch.alpha[s],
                beta=batch.beta[s],
                basis=batch.basis[s],
                beta_last=batch.beta_last[s],
            )
            t1 = time.perf_counter()
            evals, w, evals_f64, w_f64, beta_m = ritz_decompose(lres, pol, jacobi)
            k_max = max(q.k for q in qs)
            evals_k, x, resid = ritz_extract(lres, evals, w, w_f64, beta_m, k_max, pol)
            t_finish = time.perf_counter() - t1
            timings = {
                "lanczos_s": t_lanczos,  # shared across all starts of the batch
                "jacobi_s": t_finish,
                "total_s": t_lanczos + t_finish,
            }
            for q in qs:
                out.append(
                    self._finish(
                        q,
                        prep,
                        built,
                        eigenvalues=evals_k[: q.k],
                        eigenvectors=x[:, : q.k],
                        residuals=resid[: q.k],
                        evals_f64=evals_f64[: q.k],
                        iterations=m,
                        restarts=0,
                        timings=timings,
                        partition={},
                        spmv_format=prep.spmv_format,
                        tridiag=lres,
                        group_size=len(all_qs),
                    )
                )
        return out

    def _run_restarted(self, starts, prep: _Prepared, built: bool):
        out = []
        for qs in starts.values():
            q0 = qs[0]
            pol = q0.pol
            k_max = max(q.k for q in qs)
            m = max(q.subspace or max(2 * q.k, q.k + 8) for q in qs)
            m = max(m, k_max + 2)
            max_restarts = max(q.max_restarts for q in qs)
            budgets = [q.num_iters for q in qs]
            if all(b is not None for b in budgets):
                # num_iters is a total step budget: the first cycle costs m
                # steps, each further cycle refills m - k rows — take only
                # the cycles that fit entirely (floor), never overshoot.
                budget = max(budgets)
                m = min(m, budget)
                extra = max(0, math.floor((budget - m) / max(m - k_max, 1)))
                max_restarts = min(max_restarts, extra + 1)
            tol_target = min(q.tol_eff for q in qs)
            with span("repro.engine.restarted", m=m, k=k_max, max_restarts=max_restarts):
                sweep = solve_restarted(
                    prep.operator,
                    k_max,
                    policy=pol,
                    m=m,
                    max_restarts=max_restarts,
                    tol=tol_target,
                    seed=q0.seed,
                    v1=q0.v0,
                    probe=q0.recovery != "none",
                    checkpoint=self._solve_checkpoint(q0, pol, "restarted", k_max, m),
                )
            self.stats["sweeps"] += 1
            for q in qs:
                out.append(
                    self._finish(
                        q,
                        prep,
                        built,
                        eigenvalues=sweep.eigenvalues[: q.k],
                        eigenvectors=sweep.eigenvectors[:, : q.k],
                        residuals=sweep.residuals[: q.k],
                        evals_f64=sweep.eigenvalues_f64[: q.k],
                        iterations=sweep.iterations,
                        restarts=sweep.restarts,
                        timings=sweep.timings,
                        partition={},
                        spmv_format=prep.spmv_format,
                        tridiag=sweep.tridiag,
                        group_size=len(qs),
                    )
                )
        return out

    def _run_distributed(self, starts, prep: _Prepared, built: bool):
        out = []
        mesh = self._mesh_for_solve()
        for qs in starts.values():
            q0 = qs[0]
            k_max = max(q.k for q in qs)
            m = max(q.m for q in qs)
            sweep = solve_sharded(
                self.csr,
                k_max,
                mesh,
                policy=q0.pol,
                reorth=q0.reorth,
                num_iters=m,
                seed=q0.seed,
                axis=self.cfg.axis,
                v1=q0.v0,
                prepared=prep.shards,
                probe=q0.recovery != "none",
            )
            self.stats["sweeps"] += 1
            for q in qs:
                out.append(
                    self._finish(
                        q,
                        prep,
                        built,
                        eigenvalues=sweep.eigenvalues[: q.k],
                        eigenvectors=sweep.eigenvectors[:, : q.k],
                        residuals=sweep.residuals[: q.k],
                        evals_f64=sweep.eigenvalues_f64[: q.k],
                        iterations=sweep.iterations,
                        restarts=0,
                        timings=sweep.timings,
                        partition=sweep.partition,
                        spmv_format=sweep.spmv_format,
                        tridiag=sweep.tridiag,
                        group_size=len(qs),
                    )
                )
        return out


# ------------------------------------------------- plan (de)serialization


def _export_operator(op) -> Optional[Tuple[str, Dict[str, np.ndarray]]]:
    """(container type, host arrays) of a single-placement operator, or None
    when the operator is not persistable (matrix-free / unknown)."""
    from ..sparse.formats import DeviceBSR, DeviceCOO, DeviceELL, DeviceHybrid

    if isinstance(op, DenseOperator):
        return "dense", {"a": np.asarray(op.a)}
    if not isinstance(op, SparseOperator):
        return None
    m = op.mat
    if isinstance(m, DeviceCOO):
        return "coo", {
            "row": np.asarray(m.row),
            "col": np.asarray(m.col),
            "val": np.asarray(m.val),
        }
    if isinstance(m, DeviceELL):
        return "ell", {"val": np.asarray(m.val), "col": np.asarray(m.col)}
    if isinstance(m, DeviceBSR):
        return "bsr", {"val": np.asarray(m.val), "bcol": np.asarray(m.bcol)}
    if isinstance(m, DeviceHybrid):
        return "hybrid", {
            "ell_val": np.asarray(m.ell_val),
            "ell_col": np.asarray(m.ell_col),
            "tail_row": np.asarray(m.tail_row),
            "tail_col": np.asarray(m.tail_col),
            "tail_val": np.asarray(m.tail_val),
        }
    return None


def _import_plan(plan: dict, n: int) -> _Prepared:
    """Rebuild a :class:`_Prepared` from one exported plan record.  Uses the
    plain device-container constructors — never the ``to_device_*``
    converters — so the ``conversion_count()`` audit stays untouched; the
    persisted tiles ride into the engine, so no tuner probes either."""
    from ..kernels.engine import TileConfig
    from ..sparse.formats import DeviceBSR, DeviceCOO, DeviceELL, DeviceHybrid

    dtypes = plan.get("dtypes", {})

    def arr(name):
        a = plan["arrays"][name]
        want = dtypes.get(name)
        return jnp.asarray(a, dtype=jnp.dtype(want)) if want else jnp.asarray(a)

    engine = None
    ecfg = plan.get("engine")
    if ecfg:
        from ..kernels.engine import IterationPlan

        tiles = TileConfig(**{k: int(v) for k, v in ecfg["tiles"].items()})
        iter_plan = None
        ip = ecfg.get("iteration_plan")
        if ip:
            iter_plan = IterationPlan(
                update=ip["update"],
                tiles=TileConfig(
                    block_r=int(ip["block_r"]),
                    block_w=int(ip["block_w"]),
                    block_size=int(ip["block_size"]),
                ),
                source=ip.get("source", "tuned"),
            )
        engine = SpmvEngine(
            format=ecfg["format"],
            accum_dtype=jnp.dtype(ecfg["accum_dtype"]),
            tiles=tiles,
            # The execution mode belongs to this process's backend, not to
            # the one that exported the plan.
            interpret=default_interpret(),
            requested=ecfg.get("requested", ecfg["format"]),
            stats=None,
            tiles_from=ecfg.get("tiles_from", "override"),
            iteration_plan=iter_plan,
        )
    ctype = plan["container"]
    if ctype == "dense":
        op: LinearOperator = DenseOperator(arr("a"))
    else:
        if ctype == "coo":
            mat = DeviceCOO(arr("row"), arr("col"), arr("val"), n, n)
        elif ctype == "ell":
            mat = DeviceELL(arr("val"), arr("col"), n, n)
        elif ctype == "bsr":
            mat = DeviceBSR(arr("val"), arr("bcol"), n, n)
        elif ctype == "hybrid":
            mat = DeviceHybrid(
                arr("ell_val"),
                arr("ell_col"),
                arr("tail_row"),
                arr("tail_col"),
                arr("tail_val"),
                n,
                n,
            )
        else:
            raise ValueError(f"unknown persisted container type {ctype!r}")
        op = SparseOperator(mat, impl="engine" if engine is not None else "coo", engine=engine)
    return _Prepared("single", op, None, plan.get("spmv_format"), engine)


# --------------------------------------------------------------- frontends


def prepare(
    A,
    *,
    config: Optional[SolverConfig] = None,
    n: Optional[int] = None,
    mesh=None,
    policy: Union[str, PrecisionPolicy] = "FDF",
    backend: str = "auto",
    format: str = "auto",
    reorth: Optional[str] = None,
    tol: Optional[float] = None,
    num_iters: Optional[int] = None,
    subspace: Optional[int] = None,
    max_restarts: int = 30,
    seed: int = 0,
    chunk_nnz: int = 1 << 20,
    stage_depth: int = 1,
    staging: Optional[str] = None,
    jacobi: str = "host",
    axis: str = "data",
    recovery: Optional[str] = None,
    checkpoint_dir: Optional[str] = None,
    checkpoint_every: int = 8,
) -> EigenSession:
    """Plan phase of :func:`repro.api.eigsh`: coerce, place, convert, tune —
    once — and return the :class:`EigenSession` that owns the result.

    Arguments mirror :func:`repro.api.eigsh` (minus the per-query ``k`` /
    ``v0``); the solver knobs become the session's per-query *defaults* and
    the layout knobs (``format``, ``backend``, ``chunk_nnz``, ``stage_depth``,
    ``axis``, ``mesh``) decide what gets built.

    The session keeps a reference to the host matrix for lazy per-policy
    builds — do not mutate it in place while holding the session (re-run
    ``prepare`` on changed data; the frontend's cache copies instead).
    """
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
        staging=staging if staging is not None else "f32",
        jacobi=jacobi,
        axis=axis,
        recovery=recovery,
        checkpoint_dir=checkpoint_dir,
        checkpoint_every=checkpoint_every,
    )
    return EigenSession(A, cfg, mesh=mesh, n=n).warmup()


def eigsh_many(A, queries, *, config=None, n=None, mesh=None, **solver_kwargs):
    """Module-level batched solve: ``prepare`` (or hit the session cache),
    then :meth:`EigenSession.eigsh_many`.  ``solver_kwargs`` are the
    :func:`prepare` keywords; queries are dicts / :class:`EigQuery` / ints."""
    cfg = config or SolverConfig(**solver_kwargs)
    session, _ = get_session(A, cfg, mesh=mesh, n=n)
    return session.eigsh_many(queries, defaults=cfg)


# ----------------------------------------------------------- session cache


_SESSION_CACHE: "OrderedDict[str, EigenSession]" = OrderedDict()
_CACHE_LOCK = threading.Lock()  # eigsh() must stay safe to call concurrently


def _cache_limit() -> int:
    try:
        return envcfg.get_int("REPRO_EIGSH_SESSION_CACHE")
    except ValueError:
        return 8


def _cache_budget_bytes() -> int:
    """Byte budget across cached sessions (default 2 GB).  A session whose
    problem data alone exceeds it is never cached — the out-of-core sizes
    the chunked backend exists for must not stay pinned after the call."""
    try:
        return int(envcfg.get_float("REPRO_EIGSH_SESSION_CACHE_MB") * 1e6)
    except ValueError:
        return 2_048_000_000


def _session_key(matrix_fp: str, cfg: SolverConfig, mesh) -> str:
    if mesh is None:
        mesh_part = "mesh:none"
    else:
        ids = [int(d.id) for d in np.asarray(mesh.devices).flat]
        mesh_part = f"mesh:{tuple(mesh.axis_names)}:{ids}"
    # The staging pin rebuilds the chunked operator, so it is part of the
    # session identity — flipping it between calls must not serve the old plan.
    staging_pin = envcfg.raw("REPRO_CHUNK_STAGING") or ""
    return "|".join(
        (
            matrix_fp,
            config_fingerprint(cfg, _LAYOUT_FIELDS),
            mesh_part,
            f"dev{len(jax.devices())}",
            f"staging_pin:{staging_pin}",
        )
    )


def _cache_lookup(key: str) -> Optional[EigenSession]:
    with _CACHE_LOCK:
        hit = _SESSION_CACHE.get(key)
        if hit is not None:
            _SESSION_CACHE.move_to_end(key)
        return hit


def _cache_enforce_budget() -> None:
    """Evict LRU sessions until the cache fits its byte budget.  Called on
    store AND after any lazy per-policy plan build (plans grow a cached
    session's footprint after admission)."""
    budget = _cache_budget_bytes()
    with _CACHE_LOCK:
        while _SESSION_CACHE and (
            sum(s.approx_bytes() for s in _SESSION_CACHE.values()) > budget
        ):
            _SESSION_CACHE.popitem(last=False)


def _cache_store(key: str, session: EigenSession) -> None:
    if session.approx_bytes() > _cache_budget_bytes():
        return  # larger than the whole budget: serve it, don't pin it
    session._own_data()  # cached plans must not alias caller-mutable buffers
    with _CACHE_LOCK:
        _SESSION_CACHE[key] = session
        while len(_SESSION_CACHE) > _cache_limit():
            _SESSION_CACHE.popitem(last=False)
    _cache_enforce_budget()


def get_session(
    A, config: Optional[SolverConfig] = None, *, mesh=None, n: Optional[int] = None
) -> Tuple[EigenSession, bool]:
    """Session for (matrix, layout config): fingerprint-keyed LRU when the
    input has hashable bytes (CSR / scipy / dense), fresh prepare otherwise.

    Returns ``(session, cache_hit)``.  CSR and dense inputs are probed by
    content digest BEFORE any coercion, so a cache hit pays one O(bytes)
    hash and nothing else (no device transfer, no dtype cast); scipy inputs
    pay their one ``tocsr`` copy first (the digest is of the converted CSR).
    The cache holds at most ``REPRO_EIGSH_SESSION_CACHE`` sessions (default
    8; 0 disables) within a ``REPRO_EIGSH_SESSION_CACHE_MB`` byte budget;
    mutating a matrix in place changes its digest, so stale plans are never
    served — byte-identical re-submissions are.
    """
    with span("repro.session.get") as sp:
        session, hit = _lookup_or_build(A, config, mesh, n)
        sp.set_metadata(hit=int(hit))
    return session, hit


def _lookup_or_build(A, config, mesh, n) -> Tuple[EigenSession, bool]:
    cfg = config or SolverConfig()
    limit = _cache_limit()
    key = None
    fp = None
    if limit > 0 and (
        isinstance(A, (CSR, np.ndarray, jax.Array, DiskCSR))
        or (isinstance(A, (str, os.PathLike)) and is_diskcsr(A))
    ):
        # Digest-first fast path: a hit must not pay coercion.  (Note: a
        # device-resident jax.Array still pays one device->host read here —
        # the digest is of the host bytes; keep host copies of matrices you
        # re-submit in a hot loop.  Disk-backed inputs probe by the sampled
        # fingerprint — O(1) I/O however large the mapping.)
        fp = traced_fingerprint(A)
        if fp is not None:
            key = _session_key(fp, cfg, mesh)
            hit = _cache_lookup(key)
            if hit is not None:
                return hit, True
    pol0 = _plan_policy(cfg.policy).effective()
    ci = coerce_input(
        A, n=n, storage_dtype=pol0.storage, fingerprint=fp, want_fingerprint=limit > 0
    )
    if key is None and limit > 0 and ci.fingerprint is not None:
        key = _session_key(ci.fingerprint, cfg, mesh)
        hit = _cache_lookup(key)
        if hit is not None:
            return hit, True
    session = EigenSession(A, cfg, mesh=mesh, n=n, _coerced=ci)
    if key is not None:
        _cache_store(key, session)
    return session, False


def session_cache_clear() -> None:
    """Drop every cached session (frees their device buffers)."""
    with _CACHE_LOCK:
        _SESSION_CACHE.clear()


def session_cache_info() -> dict:
    with _CACHE_LOCK:
        size = len(_SESSION_CACHE)
        total = sum(s.approx_bytes() for s in _SESSION_CACHE.values())
    return {
        "size": size,
        "limit": _cache_limit(),
        "bytes": total,
        "budget_bytes": _cache_budget_bytes(),
    }
