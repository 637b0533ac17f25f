"""SpmvEngine layer: per-format SpMV wall time + the auto-selector's choice.

One section per matrix family (banded road lattice, power-law web, block
diagonal): times the COO / ELL / BSR / hybrid execution paths through the
engine on the same matrix and reports which format ``format="auto"`` picks.
A trailing section times one fused Lanczos update step (Pallas kernel) vs
the unfused three-op reference.  Interpret mode on CPU — absolute numbers
are CPU wall time of the kernel interpreter, useful as a regression
trajectory, not as TPU projections (those live in kernels_bench.py).
"""

import jax
import jax.numpy as jnp
import numpy as np

from .common import emit, emit_plan, ensure_x64, save_artifact, timeit


def _block_diag_csr(n_blocks: int, bs: int = 8, seed: int = 0):
    import scipy.sparse as sp

    from repro.sparse.formats import CSR

    rng = np.random.default_rng(seed)
    a = sp.block_diag([rng.random((bs, bs)) + 0.1 for _ in range(n_blocks)], format="csr")
    a = ((a + a.T) / 2).tocsr()
    a.sort_indices()
    return CSR(
        indptr=a.indptr.astype(np.int64),
        indices=a.indices.astype(np.int32),
        data=a.data.astype(np.float64),
        shape=a.shape,
    )


def run(scale: float = 1.0):
    ensure_x64()
    from repro.core.operators import make_operator
    from repro.kernels.engine import make_engine, matrix_stats
    from repro.sparse import generate

    n_road = max(256, int(2048 * scale))
    n_web = max(256, int(2048 * scale))
    cases = [
        ("road", generate("road", n_road, 3.0, seed=1, values="uniform")),
        ("web", generate("web", n_web, 6.0, seed=1, values="uniform")),
        ("blockdiag", _block_diag_csr(max(16, int(128 * scale)))),
    ]
    rows = []
    for name, csr in cases:
        stats = matrix_stats(csr)
        auto_fmt = make_engine(csr, "auto").format
        x = jnp.asarray(np.random.default_rng(0).standard_normal(csr.n), jnp.float32)
        case = dict(
            matrix=name,
            n=csr.n,
            nnz=csr.nnz,
            ell_overhead=stats.ell_overhead,
            block_fill=stats.block_fill,
            auto_format=auto_fmt,
        )
        for fmt in ("coo", "ell", "bsr", "hybrid"):
            engine = make_engine(csr, fmt, accum_dtype=jnp.float32)
            op = make_operator(csr, dtype=jnp.float32, engine=engine)
            t = timeit(lambda: op.matvec(x).block_until_ready())
            case[f"t_{fmt}_us"] = t * 1e6
            chosen = " (auto pick)" if fmt == auto_fmt else ""
            emit(f"engine/{name}/{fmt}", t * 1e6,
                 f"n={csr.n} nnz={csr.nnz} auto={auto_fmt}{chosen}")
        # Decision plan for compare.py --pair: which format a real solve
        # would route through.  A pair gate (e.g. hybrid:coo) is escaped
        # when the selector did not ship the losing leaf.
        emit_plan(f"engine/{name}", auto_fmt, f"format auto-selector, n={csr.n}")
        rows.append(case)
    rows.append(_chunked_staging(scale))
    rows.append(_lanczos_step(scale))
    rows.append(_lanczos_iteration(scale))
    rows.append(_serving_amortization(scale))
    rows.append(_serving_scheduler(scale))
    rows.append(_precision_policies(scale))
    rows.append(_robustness(scale))
    save_artifact("engine_bench.json", rows)
    return rows


def _chunked_staging(scale: float) -> dict:
    """Out-of-core staging cost: one full streamed matvec sweep with plain
    f32 chunk buffers vs bf16-packed staging (narrow values + per-row-block
    scales + delta int16 columns, decompressed in-kernel).  Lazy staging
    rebuilds + re-ships every chunk per sweep, so the measured time is the
    stage-and-compute path the ``chunked/staging_packed:chunked/staging_f32``
    CI pair gate holds; the recorded plan arms the gate only when packing
    actually multiplied the staged bandwidth (compression ratio >= 1.5)."""
    from repro.core.operators import ChunkedOperator
    from repro.kernels.engine import make_engine
    from repro.sparse import generate

    n = max(512, int(4096 * scale))
    csr = generate("web", n, 8.0, seed=4, values="normalized")
    eng = make_engine(csr, "ell", accum_dtype=jnp.float32)
    chunk_nnz = max(1024, csr.nnz // 6)  # several chunks at every scale
    x = jnp.asarray(np.random.default_rng(0).standard_normal(csr.n), jnp.float32)
    ops, stats = {}, {}
    for mode, label in (("f32", "staging_f32"), ("bf16", "staging_packed")):
        op = ChunkedOperator(csr, chunk_nnz=chunk_nnz, engine=eng, staging=mode)
        t = timeit(lambda: op.matvec(x).block_until_ready())
        st = op.staging_stats()
        ops[label] = t
        stats[label] = st
        emit(
            f"chunked/{label}",
            t * 1e6,
            f"n={csr.n} chunks={op.num_chunks} mode={st['mode']} "
            f"compression={st['compression_ratio']:.2f}x",
        )
    ratio = stats["staging_packed"]["compression_ratio"]
    selected = "staging_packed" if ratio >= 1.5 else "staging_f32"
    emit_plan(
        "chunked", selected,
        f"packed compression {ratio:.2f}x (gate armed when >= 1.5x)",
    )
    return {
        "matrix": "chunked_staging",
        "n": csr.n,
        "nnz": csr.nnz,
        "chunk_nnz": chunk_nnz,
        "t_staging_f32_us": ops["staging_f32"] * 1e6,
        "t_staging_packed_us": ops["staging_packed"] * 1e6,
        "packed_compression_x": ratio,
        "packed_bandwidth_gbps": stats["staging_packed"]["effective_bandwidth_gbps"],
    }


def _lanczos_step(scale: float) -> dict:
    """Fused three-term recurrence + norm (one memory pass) vs the unfused
    reference (update then separate dot) — the core/lanczos.py hot step."""
    from repro.kernels import ops as kops

    n = max(4096, int((1 << 16) * scale))
    rng = np.random.default_rng(0)
    w, v, vp = (jnp.asarray(rng.standard_normal(n), jnp.float32) for _ in range(3))
    alpha, beta = jnp.float32(0.37), jnp.float32(1.21)

    def fused():
        u, nrm = kops.lanczos_update(w, v, vp, alpha, beta, accum_dtype=jnp.float32)
        u.block_until_ready()
        return nrm

    @jax.jit
    def _unfused(w, v, vp):
        u = w - alpha * v - beta * vp
        return u, jnp.sum(u * u)

    def unfused():
        u, nrm = _unfused(w, v, vp)
        u.block_until_ready()
        return nrm

    t_f = timeit(fused)
    t_u = timeit(unfused)
    emit("engine/lanczos_step/fused", t_f * 1e6, f"n={n} fused Pallas update+norm")
    emit("engine/lanczos_step/unfused", t_u * 1e6, f"n={n} separate ops reference")
    return {
        "matrix": "lanczos_step",
        "n": n,
        "t_fused_us": t_f * 1e6,
        "t_unfused_us": t_u * 1e6,
    }


def _lanczos_iteration(scale: float) -> dict:
    """Whole-iteration probe, end to end: a short Lanczos sweep with the
    update pinned to each plan rung (unfused reference vs the fully-fused
    SpMV+alpha / update+norm two-pass path), on a real ELL-backed operator.
    These are the quantities the whole-iteration autotuner decides between;
    the emitted plan is the engine's *actual* measured (or table) decision,
    which arms/escapes the ``fused_iter:unfused_iter`` pair gate."""
    from repro.core.lanczos import lanczos_tridiag, make_local_ops
    from repro.core.operators import make_operator
    from repro.core.precision import FFF
    from repro.kernels.engine import IterationPlan, make_engine
    from repro.sparse import generate

    n = max(256, int(1024 * scale))
    csr = generate("web", n, 6.0, seed=3, values="normalized")
    engine = make_engine(csr, "ell", accum_dtype=jnp.float32)
    op = make_operator(csr, dtype=jnp.float32, engine=engine)
    pol = FFF.effective()
    iters = 8
    v1 = jnp.ones((csr.n,), jnp.float64)

    def sweep(update):
        plan = IterationPlan(update=update, tiles=engine.tiles, source="override")
        ops = make_local_ops(op.bound_matvec(pol), pol, plan=plan, operator=op)
        return lambda: lanczos_tridiag(
            None, v1, iters, pol, reorth="none", ops=ops
        ).alpha.block_until_ready()

    t_u = timeit(sweep("unfused"))
    t_f = timeit(sweep("fused_spmv"))
    emit("engine/lanczos_step/unfused_iter", t_u * 1e6,
         f"n={csr.n} m={iters} matvec+dot+update reference sweep")
    emit("engine/lanczos_step/fused_iter", t_f * 1e6,
         f"n={csr.n} m={iters} fused spmv+alpha / update+norm sweep")
    plan = engine.iteration_plan
    selected = {"fused_spmv": "fused_iter"}.get(plan.update, plan.update)
    emit_plan("engine/lanczos_step", selected,
              f"iteration plan source={plan.source}")
    return {
        "matrix": "lanczos_iteration",
        "n": csr.n,
        "iters": iters,
        "t_fused_iter_us": t_f * 1e6,
        "t_unfused_iter_us": t_u * 1e6,
        "plan": plan.as_dict(),
    }


def _serving_amortization(scale: float) -> dict:
    """Plan/execute split payoff: ``eigsh_many`` over N queries vs N
    independent ``eigsh`` calls, end-to-end (cold session per call — every
    call re-pays coercion/conversion/tuning) and solve-only (one prepared
    session — measures the shared-sweep amortization alone).  The batched
    path must win end-to-end: it pays one plan and one Lanczos sweep where
    the baseline pays N of each."""
    from repro.api import eigsh, eigsh_many, prepare, session_cache_clear
    from repro.sparse import generate

    n = max(256, int(2048 * scale))
    csr = generate("web", n, 6.0, seed=2, values="normalized")
    iters = 16
    queries = [{"k": k, "num_iters": iters} for k in (2, 3, 4, 6)]

    def run_many():
        session_cache_clear()
        return eigsh_many(csr, queries, reorth="full", backend="single")

    def run_independent():
        out = []
        for q in queries:
            session_cache_clear()  # cold: each call re-pays the plan phase
            r = eigsh(csr, q["k"], num_iters=q["num_iters"], reorth="full", backend="single")
            out.append(r)
        return out

    t_many = timeit(run_many)
    t_ind = timeit(run_independent)
    sess = prepare(csr, reorth="full", backend="single")
    t_solve_many = timeit(lambda: sess.eigsh_many(queries))
    t_solve_ind = timeit(lambda: [sess.eigsh(q["k"], num_iters=q["num_iters"]) for q in queries])
    nq = len(queries)
    # Persisting a full result is one json.dump away now (no ad-hoc array
    # conversion): what a serving layer would log per query.
    save_artifact("serving_result.json", sess.eigsh(2, num_iters=iters).to_dict())
    emit("serving/eigsh_many_e2e", t_many * 1e6, f"n={n} {nq} queries, one plan+sweep")
    emit("serving/n_calls_e2e", t_ind * 1e6, f"n={n} {nq} cold eigsh calls")
    emit("serving/eigsh_many_solve", t_solve_many * 1e6, "prepared session, shared sweep")
    emit("serving/n_calls_solve", t_solve_ind * 1e6, "prepared session, per-query sweeps")
    speedup = t_ind / max(t_many, 1e-12)
    emit("serving/amortization_x", speedup, f"N-calls / eigsh_many e2e ({nq} queries)")
    if speedup < 1.0:
        # Structural gate: batching must not LOSE to N independent calls.
        # (The expected margin is ~Nx on the plan phase plus the extra
        # sweeps; < 1.0 means the split regressed, not that CI was noisy.)
        raise RuntimeError(
            f"eigsh_many slower than {nq} independent eigsh calls: "
            f"{t_many * 1e3:.1f}ms vs {t_ind * 1e3:.1f}ms"
        )
    return {
        "matrix": "serving",
        "n": n,
        "queries": nq,
        "t_eigsh_many_e2e_us": t_many * 1e6,
        "t_n_calls_e2e_us": t_ind * 1e6,
        "t_eigsh_many_solve_us": t_solve_many * 1e6,
        "t_n_calls_solve_us": t_solve_ind * 1e6,
        "amortization_x": speedup,
    }


def _serving_scheduler(scale: float) -> dict:
    """Continuous batching end to end: an ``EigenScheduler`` serving a burst
    of compatible queries (one resident session, coalesced into shared
    sweeps) vs the same queries as N sequential *cold* ``eigsh`` calls.
    The scheduler pays one build + one sweep + scheduling overhead; the
    baseline re-pays coercion/conversion/tuning per call — so the scheduler
    must never lose, and the gate below makes that structural."""
    from repro.api import SolverConfig, eigsh, session_cache_clear
    from repro.serving import EigenScheduler, SchedulerConfig
    from repro.sparse import generate

    n = max(256, int(2048 * scale))
    csr = generate("web", n, 6.0, seed=2, values="normalized")
    iters = 16
    ks = (2, 3, 4, 6, 2, 3, 4, 6)
    cfg = SolverConfig(reorth="full", backend="single")
    last_stats = {}

    def run_scheduler():
        # Paused submit + start: the whole burst is queued when dispatch
        # begins, so coalescing is deterministic (and maximal) per repeat.
        sc = SchedulerConfig(admission_window_s=2e-3, max_group=len(ks))
        with EigenScheduler(sc, start=False) as sched:
            key = sched.add_matrix(csr, config=cfg)
            handles = [sched.submit(key, k=k, num_iters=iters) for k in ks]
            sched.start()
            out = [h.result(timeout=300.0) for h in handles]
            last_stats["stats"] = sched.stats()
        return out

    def run_cold():
        out = []
        for k in ks:
            session_cache_clear()  # every call re-pays the plan phase
            out.append(eigsh(csr, k, num_iters=iters, reorth="full", backend="single"))
        return out

    t_sched = timeit(run_scheduler)
    t_cold = timeit(run_cold)
    stats = last_stats["stats"]
    nq = len(ks)
    qps = nq / max(t_sched, 1e-12)
    p50_us = stats.latency["e2e"]["p50_s"] * 1e6
    p99_us = stats.latency["e2e"]["p99_s"] * 1e6
    speedup = t_cold / max(t_sched, 1e-12)
    emit("serving/scheduler_e2e", t_sched * 1e6, f"n={n} {nq} queries, one scheduler burst")
    emit("serving/scheduler_qps", qps, f"queries/s through the scheduler (burst of {nq})")
    emit("serving/scheduler_p50_us", p50_us, "e2e latency median (queue + solve)")
    emit("serving/scheduler_p99_us", p99_us, "e2e latency p99 (queue + solve)")
    emit("serving/scheduler_coalesce_rate", stats.coalesce_rate,
         f"occupancy {stats.batch_occupancy:.2f} over {stats.groups} dispatches")
    emit("serving/scheduler_speedup_vs_cold_x", speedup, f"{nq} cold eigsh calls / scheduler")
    if speedup < 1.0:
        # Structural gate: continuous batching must not LOSE to N sequential
        # cold calls.  The scheduler adds only an admission window + thread
        # handoff on top of eigsh_many; < 1.0 means the serving layer
        # regressed, not that CI was noisy.
        raise RuntimeError(
            f"scheduler slower than {nq} sequential cold eigsh calls: "
            f"{t_sched * 1e3:.1f}ms vs {t_cold * 1e3:.1f}ms"
        )
    return {
        "matrix": "serving_scheduler",
        "n": n,
        "queries": nq,
        "t_scheduler_e2e_us": t_sched * 1e6,
        "t_cold_calls_us": t_cold * 1e6,
        "qps": qps,
        "p50_us": p50_us,
        "p99_us": p99_us,
        "coalesce_rate": stats.coalesce_rate,
        "batch_occupancy": stats.batch_occupancy,
        "speedup_vs_cold_x": speedup,
    }


def _precision_policies(scale: float) -> dict:
    """Uniform vs per-phase vs auto precision on the smoke matrix: the cost
    of the paper's FDF knob, the cost of the reorth-in-f32 phase split that
    sheds most of its f64 work, and the end-to-end cost of the accuracy-
    driven ``policy="auto"`` ladder (solve-only, prepared session — the
    ladder pays solves, not plans)."""
    from repro.api import prepare, session_cache_clear
    from repro.core.precision import FDF
    from repro.sparse import generate

    n = max(256, int(2048 * scale))
    csr = generate("web", n, 6.0, seed=2, values="normalized")
    iters = 16
    split = FDF.with_phases(reorth="f32")

    session_cache_clear()
    sess = prepare(csr, reorth="full", backend="single")

    def run_uniform():
        return sess.eigsh(4, policy=FDF, num_iters=iters)

    def run_split():
        return sess.eigsh(4, policy=split, num_iters=iters)

    t_uni = timeit(run_uniform)
    t_split = timeit(run_split)
    # auto needs a tol to judge rungs against; 1e-4 lands on FFF after one
    # rejected bf16 probe — a 2-attempt ladder, the common serving case.
    sess_auto = prepare(csr, reorth="full", tol=1e-4)
    last = {}

    def run_auto():
        last["r"] = sess_auto.eigsh(4, policy="auto", tol=1e-4, subspace=12)

    t_auto = timeit(run_auto)
    r_auto = last["r"]
    attempts = len(r_auto.policy_escalations or [])
    emit("precision/uniform_fdf", t_uni * 1e6, f"n={n} m={iters} policy=FDF")
    emit("precision/phase_split", t_split * 1e6, f"n={n} m={iters} {split.name}")
    emit("precision/auto", t_auto * 1e6, f"n={n} tol=1e-4 {attempts} attempts -> {r_auto.policy}")
    return {
        "matrix": "precision",
        "n": n,
        "t_uniform_fdf_us": t_uni * 1e6,
        "t_phase_split_us": t_split * 1e6,
        "t_auto_us": t_auto * 1e6,
        "auto_attempts": attempts,
        "auto_policy": r_auto.policy,
    }


def _robustness(scale: float) -> dict:
    """Cost of the numerical-health layer.  Three quantities: the health
    probe (a host scan of the m-sized tridiagonal scalars, run once per
    sweep — its per-iteration amortization is what the CI pair gate holds
    under 2% of one whole unfused Lanczos iteration, so "the probe is free"
    stays a measured claim), ``recovery="auto"`` on a clean solve vs
    ``recovery="none"`` (the no-fault overhead of the recovery wrapper), and
    one injected mid-sweep NaN recovered end to end (what surviving a
    breakdown actually costs: the poisoned sweep + one rung-up re-solve)."""
    from repro.api import prepare, session_cache_clear
    from repro.core.lanczos import check_tridiag_health, lanczos_tridiag
    from repro.core.operators import make_operator
    from repro.core.precision import FFF
    from repro.sparse import generate
    from repro.testing import faults

    n = max(256, int(2048 * scale))
    csr = generate("web", n, 6.0, seed=2, values="normalized")
    iters = 16
    pol = FFF.effective()
    op = make_operator(csr, dtype=jnp.float32)
    v1 = jnp.ones((csr.n,), jnp.float64)
    lres = lanczos_tridiag(op.bound_matvec(pol), v1, iters, pol, reorth="full")
    t_probe = timeit(lambda: check_tridiag_health(lres, pol))
    emit("engine/health_probe", t_probe * 1e6,
         f"m={iters} tridiag health scan, absolute (1 probe per sweep)")
    emit("engine/health_probe_per_iter", t_probe / iters * 1e6,
         f"probe/m: per-iteration amortization (gated <2% of unfused_iter)")

    session_cache_clear()
    sess = prepare(csr, reorth="full", backend="single")
    t_off = timeit(lambda: sess.eigsh(4, num_iters=iters, recovery="none"))
    t_clean = timeit(lambda: sess.eigsh(4, num_iters=iters, recovery="auto"))

    def injected():
        with faults.inject("spmv_nan@iter=3"):
            return sess.eigsh(4, num_iters=iters, recovery="auto")

    r_inj = injected()
    actions = [t["action"] for t in (r_inj.recovery_trail or [])]
    t_inj = timeit(injected)
    emit("serving/recovery_off_e2e", t_off * 1e6,
         f"n={n} m={iters} probes off (legacy path)")
    emit("serving/recovery_clean_e2e", t_clean * 1e6,
         f"n={n} m={iters} recovery=auto, no fault (wrapper overhead)")
    emit("serving/recovery_injected_e2e", t_inj * 1e6,
         f"n={n} m={iters} injected NaN -> {'+'.join(actions) or 'none'} -> recovered")
    return {
        "matrix": "robustness",
        "n": n,
        "iters": iters,
        "t_health_probe_us": t_probe * 1e6,
        "t_recovery_off_us": t_off * 1e6,
        "t_recovery_clean_us": t_clean * 1e6,
        "t_recovery_injected_us": t_inj * 1e6,
        "injected_actions": actions,
    }


if __name__ == "__main__":
    run()
