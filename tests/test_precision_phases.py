"""Per-phase precision policies + the accuracy-driven ``policy="auto"``.

The Fig.4-style accuracy harness for ISSUE 5: a fixed seeded matrix is
solved under the paper's precision ladder and the measured eigenvalue error
and basis-orthogonality loss must be monotone FFF -> FCF -> FDF -> DDD
(f64 rungs skipped when x64 is unavailable); per-phase overrides that all
equal the compute dtype must reproduce the uniform policy bit-identically;
and ``policy="auto"`` must provably escalate (attempt trace asserted) and
land on a policy meeting ``tol``, with the f64-work reduction of a phase
split verified through the ``partition["spmv"]["precision"]`` audit
counters.

The ``compensated_sum`` property test runs from a fixed seeded case list so
the suite needs no optional dependencies; with ``hypothesis`` installed the
same check body is additionally driven from search strategies (the
``test_sparse.py`` fallback pattern).
"""

import math

import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import eigsh, resolve_policy, session_cache_clear
from repro.core import PHASES, auto_ladder
from repro.core.lanczos import fused_update_enabled, make_local_ops
from repro.core.metrics import eigsh_reference, pairwise_orthogonality_deg
from repro.core.precision import DDD, FCF, FDF, FFF, compensated_sum, x64_enabled
from repro.api.session import policy_key
from repro.sparse import generate

K = 3
SUBSPACE = 12
RESTARTS = 30


@pytest.fixture(scope="module")
def mat():
    """The harness matrix: fixed seed, normalized spectrum (|lambda| <= 1)."""
    return generate("web", 512, 6.0, seed=11, values="normalized")


@pytest.fixture(autouse=True)
def _fresh_sessions():
    # Ladder solves must not inherit another test's cached per-policy plans
    # when the test asserts on build/audit counters.
    session_cache_clear()
    yield


def _ladder_rungs():
    """The Fig.4 ladder, f64 rungs dropped when x64 is unavailable (they
    would alias their f32 neighbours and break strict comparisons)."""
    rungs = [FFF, FCF]
    if x64_enabled():
        rungs += [FDF, DDD]
    return rungs


def _accuracy(csr, policy):
    """(eigenvalue error, orthogonality loss) of a to-the-policy's-floor
    solve — the restarted engine iterates until the residual stalls at the
    arithmetic's own limit, so the metrics measure PRECISION, not Krylov
    truncation (the fig4 methodology)."""
    ref_vals, _ = eigsh_reference(csr, K)
    r = eigsh(
        csr,
        K,
        policy=policy,
        backend="restarted",
        tol=1e-13,
        subspace=SUBSPACE,
        max_restarts=RESTARTS,
    )
    lam = np.asarray(r.eigenvalues, dtype=np.float64)
    ev_err = float(np.max(np.abs(lam - ref_vals) / np.maximum(np.abs(ref_vals), 1e-300)))
    orth_loss = abs(90.0 - pairwise_orthogonality_deg(r.eigenvectors))
    return ev_err, orth_loss


# ------------------------------ accuracy ladder -------------------------------


def test_accuracy_ladder_monotone(mat):
    """Fig. 4: eigenvalue error and orthogonality loss are monotone down the
    FFF -> FCF -> FDF -> DDD ladder (1.5x slack per step for f32-floor noise;
    the FFF -> DDD drop must be strict and large)."""
    errs, orths, names = [], [], []
    for pol in _ladder_rungs():
        ev_err, orth = _accuracy(mat, pol)
        errs.append(ev_err)
        orths.append(orth)
        names.append(pol.name)
    for i in range(len(errs) - 1):
        assert errs[i + 1] <= errs[i] * 1.5 + 1e-15, (names, errs)
        assert orths[i + 1] <= orths[i] * 1.5 + 1e-12, (names, orths)
    if x64_enabled():
        assert errs[-1] < errs[0] / 10, (names, errs)  # DDD floor << FFF floor


def test_bf16_rung_is_least_accurate(mat):
    """The TPU-native bf16 rung sits above the f32 rung in error — the
    bottom of the auto ladder is really the cheapest/least accurate."""
    from repro.core.precision import BFF

    err_b, _ = _accuracy(mat, BFF)
    err_f, _ = _accuracy(mat, FFF)
    assert err_f < err_b


# ------------------------- per-phase override semantics -----------------------


@pytest.mark.parametrize("base", [FFF, FDF])
def test_uniform_phase_overrides_bit_identical(mat, base):
    """Overriding every phase with the policy's own compute dtype must
    reproduce the uniform-policy results bit-identically (the overrides are
    inherit-from-compute, not a parallel arithmetic)."""
    pol = base.effective()
    cdt = jnp.dtype(pol.compute).name
    overridden = pol.with_phases(spmv=cdt, alpha_beta=cdt, reorth=cdt, ritz=cdt)
    assert overridden.is_uniform()
    r_uni = eigsh(mat, K, policy=pol, num_iters=16, reorth="full", backend="single")
    r_ovr = eigsh(mat, K, policy=overridden, num_iters=16, reorth="full", backend="single")
    assert (
        np.asarray(r_uni.eigenvalues).tobytes() == np.asarray(r_ovr.eigenvalues).tobytes()
    )
    assert (
        np.asarray(r_uni.eigenvectors).tobytes() == np.asarray(r_ovr.eigenvectors).tobytes()
    )
    np.testing.assert_array_equal(r_uni.residuals, r_ovr.residuals)


@pytest.mark.skipif(not x64_enabled(), reason="f64 phase split needs x64")
def test_reorth_f32_split_matches_fdf_with_less_f64_work(mat):
    """The acceptance split: reorth in f32 while alpha/beta accumulate in
    f64 must match full-FDF residuals within 10x while reducing f64-dtype
    operations (verified via the partition["spmv"]["precision"] audit)."""
    split = FDF.with_phases(reorth="f32")
    r_fdf = eigsh(mat, K, policy=FDF, num_iters=16, reorth="full", backend="single")
    r_split = eigsh(mat, K, policy=split, num_iters=16, reorth="full", backend="single")
    assert r_split.residuals.max() <= 10 * r_fdf.residuals.max() + 1e-300
    ops_fdf = r_fdf.partition["spmv"]["precision"]["ops_by_dtype"]
    ops_split = r_split.partition["spmv"]["precision"]["ops_by_dtype"]
    assert ops_split["float64"] < ops_fdf["float64"]
    assert ops_split.get("float32", 0) > 0  # the reorth work moved to f32
    # provenance: the executed phase map is surfaced on the result
    prec = r_split.partition["spmv"]["precision"]
    assert prec["phase_map"] == split.phase_map()
    assert prec["phase_map"]["reorth"] == "float32"
    assert prec["phase_map"]["alpha_beta"] == "float64"
    assert not prec["uniform"]


@pytest.mark.skipif(not x64_enabled(), reason="f64 phase split needs x64")
def test_alpha_beta_f64_upgrade_improves_fff(mat):
    """The converse split: FFF with only the alpha/beta reductions widened
    to f64 should not be less accurate than plain FFF (the wide-accumulator
    role of FDF at a fraction of its f64 work)."""
    upgraded = FFF.with_phases(alpha_beta="f64")
    err_fff, _ = _accuracy(mat, FFF)
    err_up, _ = _accuracy(mat, upgraded)
    assert err_up <= err_fff * 1.5 + 1e-15


def test_phase_split_runs_on_chunked_backend(mat):
    """Per-phase dtypes thread through the out-of-core engine too: a split
    policy on the chunked path agrees with the same split single-device."""
    split = FFF.with_phases(alpha_beta="f32", reorth="f32")  # uniform-equivalent
    v1 = jnp.ones((mat.n,), jnp.float64)
    r_s = eigsh(mat, 2, policy=split, num_iters=8, backend="single", v0=v1)
    r_c = eigsh(mat, 2, policy=split, num_iters=8, backend="chunked", chunk_nnz=2048, v0=v1)
    np.testing.assert_allclose(
        np.asarray(r_s.eigenvalues), np.asarray(r_c.eigenvalues), rtol=1e-5
    )


def test_fused_update_gating_respects_alpha_beta_phase():
    """A split alpha_beta dtype must disable the fused Pallas update (its
    fused norm runs in the recurrence dtype); other phase overrides keep it."""
    pol = FFF.effective()
    assert fused_update_enabled(pol)
    assert not fused_update_enabled(pol.with_phases(alpha_beta="f64" if x64_enabled() else "bf16"))
    assert fused_update_enabled(pol.with_phases(reorth="bf16"))
    split = pol.with_phases(alpha_beta="bf16")
    assert make_local_ops(lambda x: x, split).fused_update is None


def test_phase_split_shares_uniform_plan(mat):
    """A reorth/alpha_beta/ritz split changes per-query arithmetic only: it
    must reuse the uniform policy's built plan (the device operator depends
    on storage + spmv dtype alone), paying zero conversions."""
    eigsh(mat, 2, policy="FDF", num_iters=8)
    r2 = eigsh(mat, 2, policy=FDF.with_phases(reorth="f32"), num_iters=8)
    assert r2.session_reuse
    assert r2.partition["spmv"]["conversions"] == 0
    assert r2.partition["spmv"]["reused"]


def test_ritz_phase_honored_by_jax_jacobi(mat):
    """The device-Jacobi path must run phase-2 in the ritz dtype too (the
    audit's phase_map reports it as executed)."""
    split = FFF.with_phases(ritz="f64") if x64_enabled() else FFF
    r_jax = eigsh(mat, 2, policy=split, num_iters=8, jacobi="jax")
    r_host = eigsh(mat, 2, policy=split, num_iters=8, jacobi="host")
    np.testing.assert_allclose(
        np.asarray(r_jax.eigenvalues, np.float64),
        np.asarray(r_host.eigenvalues, np.float64),
        rtol=1e-4,
    )


# ------------------------------ resolve_policy -------------------------------


def test_resolve_policy_case_insensitive():
    assert resolve_policy("fdf").name == "FDF"
    assert resolve_policy("Bcf").name == "BCF"
    assert resolve_policy(" fff ").name == "FFF"


def test_resolve_policy_unknown_name_is_value_error():
    with pytest.raises(ValueError, match="unknown precision policy"):
        resolve_policy("FDX")


def test_resolve_policy_phase_override_mapping():
    p = resolve_policy({"base": "fdf", "reorth": "f32"})
    assert p.storage is FDF.storage and jnp.dtype(p.phase_dtype("reorth")) == jnp.float32
    assert jnp.dtype(p.phase_dtype("alpha_beta")) == jnp.dtype(jnp.float64)


def test_resolve_policy_unknown_phase_key_named_error():
    """A typo'd phase key must be a named ValueError listing the valid
    phases — never a raw KeyError."""
    with pytest.raises(ValueError, match="valid phases"):
        resolve_policy({"base": "FDF", "reorthh": "f32"})
    with pytest.raises(ValueError, match="valid phases"):
        FDF.with_phases(sppmv="f32")
    with pytest.raises(ValueError, match="valid phases"):
        FDF.phase_dtype("jacobi")


def test_resolve_policy_auto_is_mode_not_policy():
    with pytest.raises(ValueError, match="auto"):
        resolve_policy("auto")


def test_policy_key_is_phase_aware():
    """Session operator caching: overrides equal to compute key like the
    uniform policy (same plan); a real split keys differently."""
    cdt = jnp.dtype(FDF.effective().compute).name
    assert policy_key(FDF) == policy_key(FDF.with_phases(reorth=cdt))
    assert policy_key(FDF.with_phases(reorth="bf16")) != policy_key(FDF)
    assert set(PHASES) == {"spmv", "alpha_beta", "reorth", "ritz"}


# ------------------------------- policy="auto" --------------------------------


def test_auto_escalates_and_meets_tol(mat):
    """tol between the bf16 and f32 floors: auto must try BFF, measure it
    failing, escalate to FFF, and stop there with the trace recorded."""
    res = eigsh(mat, K, policy="auto", tol=1e-4, subspace=SUBSPACE, max_restarts=RESTARTS)
    trace = res.policy_escalations
    assert trace is not None and len(trace) == 2
    assert [a["policy"] for a in trace] == ["BFF", "FFF"]
    assert not trace[0]["converged"] and trace[1]["converged"]
    assert trace[0]["max_residual"] > 1e-4 >= trace[1]["max_residual"]
    assert trace[1]["residual_kind"] == "verified"
    assert res.policy == "FFF"
    # the attempt order is a prefix of the ladder
    ladder = list(auto_ladder())
    assert [a["policy"] for a in trace] == ladder[: len(trace)]


def test_auto_loose_tol_stops_at_first_rung(mat):
    res = eigsh(mat, K, policy="auto", tol=5e-2, subspace=SUBSPACE, max_restarts=RESTARTS)
    assert [a["policy"] for a in res.policy_escalations] == [auto_ladder()[0]]
    assert res.all_converged


@pytest.mark.skipif(not x64_enabled(), reason="the f64 rungs need x64")
def test_auto_reaches_f64_rung_for_tight_tol(mat):
    """tol below every f32-storage floor: the ladder must run to DDD, every
    earlier rung measured and rejected."""
    res = eigsh(mat, K, policy="auto", tol=1e-9, subspace=SUBSPACE, max_restarts=RESTARTS)
    trace = res.policy_escalations
    assert [a["policy"] for a in trace] == ["BFF", "FFF", "FCF", "FDF", "DDD"]
    assert [a["converged"] for a in trace] == [False, False, False, False, True]
    assert res.policy == "DDD"
    assert trace[-1]["max_residual"] <= 1e-9


def test_auto_ladder_capped_by_x64():
    rungs = auto_ladder()
    if x64_enabled():
        assert rungs == ("BFF", "FFF", "FCF", "FDF", "DDD")
    else:
        assert rungs == ("BFF", "FFF", "FCF")


def test_explicit_policy_has_no_escalations(mat):
    res = eigsh(mat, 2, policy="FFF", num_iters=8)
    assert res.policy_escalations is None


def test_auto_reuses_session_plans(mat):
    """The second auto solve reuses the per-policy operator plans the first
    one built (phase-aware policy_key): zero conversions, session_reuse."""
    eigsh(mat, K, policy="auto", tol=1e-4, subspace=SUBSPACE, max_restarts=RESTARTS)
    res2 = eigsh(mat, K, policy="auto", tol=1e-4, subspace=SUBSPACE, max_restarts=RESTARTS)
    assert res2.session_reuse
    assert res2.partition["spmv"]["conversions"] == 0
    assert res2.partition["spmv"]["tuner_probes"] == 0


def test_auto_result_roundtrips_to_json(mat):
    import json

    res = eigsh(mat, 2, policy="auto", tol=5e-2, subspace=SUBSPACE, max_restarts=RESTARTS)
    d = json.loads(json.dumps(res.to_dict()))
    from repro.api import EigenResult

    back = EigenResult.from_dict(d)
    assert back.policy_escalations == res.policy_escalations


# --------------------------- compensated_sum property -------------------------


def _cancellation_cases(num=20, seed=7):
    """Adversarial cancellation inputs: mixed-magnitude values paired with
    their negations plus a small survivor, shuffled — the naive sum loses
    the survivor to absorption, fsum never does."""
    rng = np.random.default_rng(seed)
    cases = []
    for _ in range(num):
        n = int(rng.integers(4, 400))
        base = (rng.standard_normal(n) * (10.0 ** rng.integers(0, 8, n))).astype(np.float32)
        vals = np.concatenate([base, -base, rng.standard_normal(3).astype(np.float32)])
        rng.shuffle(vals)
        cases.append(vals)
    return cases


def check_compensated_vs_fsum(vals_f32: np.ndarray) -> None:
    """compensated_sum must track math.fsum within a ~wide-accumulator bound
    and never be (meaningfully) worse than the naive sum."""
    vals_f32 = np.asarray(vals_f32, dtype=np.float32)
    ref = math.fsum(float(v) for v in vals_f32)  # exact in double
    got = float(compensated_sum(jnp.asarray(vals_f32), jnp.float32))
    naive = float(jnp.sum(jnp.asarray(vals_f32)))
    scale = float(np.sum(np.abs(vals_f32), dtype=np.float64))
    eps = float(np.finfo(np.float32).eps)
    slack = eps * scale + 1e-30
    assert abs(got - ref) <= abs(naive - ref) + 4 * slack
    assert abs(got - ref) <= 8 * slack  # ~2x-wider-accumulator bound


@pytest.mark.parametrize("case", range(len(_cancellation_cases())))
def test_compensated_sum_vs_fsum_seeded(case):
    check_compensated_vs_fsum(_cancellation_cases()[case])


def test_compensated_sum_vs_fsum_hypothesis():
    """Hypothesis-driven variant of the same check (skipped without the
    ``[test]`` extra; the seeded cases above always run)."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    finite = st.floats(
        min_value=-1e8, max_value=1e8, allow_nan=False, allow_infinity=False, width=32
    )

    @settings(max_examples=25, deadline=None)
    @given(st.lists(finite, min_size=1, max_size=300), st.integers(0, 2**31 - 1))
    def prop(xs, seed):
        vals = np.asarray(xs, dtype=np.float32)
        # force cancellation structure: append the negation, shuffled
        rng = np.random.default_rng(seed)
        vals = np.concatenate([vals, -vals, np.float32([0.125])])
        rng.shuffle(vals)
        check_compensated_vs_fsum(vals)

    prop()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
@pytest.mark.parametrize("a_shape, b_shape", [((5, 40), (40,)), ((5,), (5, 40)), ((3, 5), (5, 40))])
def test_basis_matmul_matches_dot(dtype, a_shape, b_shape):
    """The Krylov-basis product: f64 goes through multiply + reduce (a TPU's
    f64 dot needs huge temporaries), every shape and dtype matches ``a @ b``."""
    from repro.core.precision import basis_matmul

    if dtype == jnp.float64 and not x64_enabled():
        pytest.skip("f64 needs x64")
    rng = np.random.default_rng(7)
    a = jnp.asarray(rng.standard_normal(a_shape), dtype)
    b = jnp.asarray(rng.standard_normal(b_shape), dtype)
    got = basis_matmul(a, b)
    assert got.shape == (a @ b).shape and got.dtype == jnp.dtype(dtype)
    ref = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    tol = 1e-12 if dtype == jnp.float64 else 1e-5
    np.testing.assert_allclose(np.asarray(got, np.float64), ref, rtol=tol, atol=tol)
