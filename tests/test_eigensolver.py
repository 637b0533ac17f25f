"""End-to-end behaviour of the Top-K eigensolver (the paper's pipeline)."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    DDD,
    FDF,
    FFF,
    DenseOperator,
    ChunkedOperator,
    make_operator,
    topk_eigs,
)
from repro.core.jacobi import jacobi_eigh, jacobi_eigh_host
from repro.core.metrics import (
    eigsh_reference,
    pairwise_orthogonality_deg,
    reconstruction_error,
)


def test_jacobi_host_matches_numpy():
    rng = np.random.default_rng(0)
    a = rng.standard_normal((24, 24))
    a = (a + a.T) / 2
    evals, evecs = jacobi_eigh_host(a)
    ref = np.linalg.eigvalsh(a)
    np.testing.assert_allclose(np.sort(evals), np.sort(ref), atol=1e-10)
    # eigenvector residual
    assert np.linalg.norm(a @ evecs - evecs @ np.diag(evals)) < 1e-9


def test_jacobi_jax_matches_host():
    rng = np.random.default_rng(1)
    a = rng.standard_normal((16, 16))
    a = (a + a.T) / 2
    ev_h, _ = jacobi_eigh_host(a)
    ev_j, w_j = jacobi_eigh(jnp.asarray(a))
    np.testing.assert_allclose(np.asarray(ev_j), ev_h, atol=1e-10)
    assert np.linalg.norm(a @ np.asarray(w_j) - np.asarray(w_j) @ np.diag(np.asarray(ev_j))) < 1e-8


def test_dense_operator_topk_exact():
    """On a small dense symmetric matrix with m=n, Lanczos+Jacobi is exact."""
    rng = np.random.default_rng(2)
    a = rng.standard_normal((64, 64))
    a = (a + a.T) / 2
    op = DenseOperator(jnp.asarray(a, dtype=jnp.float64))
    res = topk_eigs(op, 5, policy=DDD, reorth="full2", num_iters=64)
    ref = np.linalg.eigvalsh(a)
    ref = ref[np.argsort(-np.abs(ref))][:5]
    np.testing.assert_allclose(np.asarray(res.eigenvalues), ref, rtol=1e-8)


def test_topk_matches_arpack(web_csr):
    """Top eigenvalues agree with ARPACK (the paper's CPU baseline library)."""
    ref_vals, _ = eigsh_reference(web_csr, 4)
    op = make_operator(web_csr, "coo", dtype=jnp.float32)
    res = topk_eigs(op, 4, policy=FDF, reorth="full", num_iters=24)
    np.testing.assert_allclose(
        np.asarray(res.eigenvalues, dtype=np.float64), ref_vals, rtol=1e-4
    )


def test_reconstruction_error_and_orthogonality(web_csr):
    op = make_operator(web_csr, "coo", dtype=jnp.float32)
    res = topk_eigs(op, 4, policy=FDF, reorth="full", num_iters=24)
    err = reconstruction_error(op, res.eigenvalues, res.eigenvectors, accum_dtype=jnp.float64)
    assert err < 1e-3
    orth = pairwise_orthogonality_deg(res.eigenvectors)
    assert abs(orth - 90.0) < 0.1


def test_precision_ladder(web_csr):
    """Paper Fig. 4: DDD <= FDF << FFF in error; FDF close to DDD."""
    errs = {}
    for pol in (FFF, FDF, DDD):
        op = make_operator(web_csr, "coo", dtype=pol.storage)
        res = topk_eigs(op, 4, policy=pol, reorth="full", num_iters=24)
        errs[pol.name] = reconstruction_error(
            op, res.eigenvalues, res.eigenvectors, accum_dtype=jnp.float64
        )
    assert errs["DDD"] <= errs["FDF"] * 1.5 + 1e-12
    assert errs["FDF"] < errs["FFF"]  # the paper's 12x headline, qualitatively


def test_reorth_improves_orthogonality(web_csr):
    """Paper Fig. 3b: reorthogonalization improves pairwise angles."""
    op = make_operator(web_csr, "coo", dtype=jnp.float32)
    r_none = topk_eigs(op, 6, policy=FFF, reorth="none", num_iters=18)
    r_full = topk_eigs(op, 6, policy=FFF, reorth="full", num_iters=18)
    d_none = abs(pairwise_orthogonality_deg(r_none.eigenvectors) - 90)
    d_full = abs(pairwise_orthogonality_deg(r_full.eigenvectors) - 90)
    assert d_full <= d_none


def test_chunked_out_of_core_matches_incore(web_csr):
    """Out-of-core streaming SpMV gives the same spectrum as in-core."""
    op_ic = make_operator(web_csr, "coo", dtype=jnp.float32)
    op_oc = ChunkedOperator(web_csr, chunk_nnz=4096, dtype=jnp.float32)
    assert op_oc.num_chunks > 1
    v1 = jnp.ones((web_csr.n,), jnp.float64)
    r_ic = topk_eigs(op_ic, 3, policy=FDF, reorth="full", num_iters=12, v1=v1)
    r_oc = topk_eigs(op_oc, 3, policy=FDF, reorth="full", num_iters=12, v1=v1)
    np.testing.assert_allclose(
        np.asarray(r_ic.eigenvalues), np.asarray(r_oc.eigenvalues), rtol=1e-6
    )


def test_ell_impl_matches_coo(web_csr):
    v1 = jnp.ones((web_csr.n,), jnp.float64)
    r_coo = topk_eigs(
        make_operator(web_csr, "coo"), 3, policy=FFF, reorth="full", num_iters=9, v1=v1
    )
    r_ell = topk_eigs(
        make_operator(web_csr, "ell"), 3, policy=FFF, reorth="full", num_iters=9, v1=v1
    )
    np.testing.assert_allclose(
        np.asarray(r_coo.eigenvalues), np.asarray(r_ell.eigenvalues), rtol=1e-5
    )


def test_num_iters_improves_accuracy(norm_csr):
    op = make_operator(norm_csr, "coo")
    e = {}
    for m in (8, 32):
        r = topk_eigs(op, 8, policy=FDF, reorth="full", num_iters=m)
        e[m] = reconstruction_error(op, r.eigenvalues, r.eigenvectors, accum_dtype=jnp.float64)
    assert e[32] < e[8]


def test_thick_restart_matches_arpack_tightly(norm_csr):
    """Restarted solver reaches ARPACK-class residuals on crowded spectra
    where the paper's fixed-m solver is truncation-limited."""
    from repro.core.restarted import topk_eigs_restarted

    op = make_operator(norm_csr, "coo", dtype=jnp.float32)
    ref_vals, _ = eigsh_reference(norm_csr, 6)
    r = topk_eigs_restarted(op, 6, policy=FDF, m=20, tol=1e-7, max_restarts=40)
    np.testing.assert_allclose(
        np.asarray(r.eigenvalues, np.float64), ref_vals, rtol=1e-5, atol=1e-7
    )
    rec = reconstruction_error(op, r.eigenvalues, r.eigenvectors, accum_dtype=jnp.float64)
    assert rec < 1e-5  # the paper's headline accuracy bar


def test_thick_restart_bounded_memory(norm_csr):
    """Subspace never exceeds m vectors regardless of restarts."""
    from repro.core.restarted import topk_eigs_restarted

    op = make_operator(norm_csr, "coo", dtype=jnp.float32)
    r = topk_eigs_restarted(op, 4, policy=FDF, m=12, tol=1e-6, max_restarts=25)
    assert r.tridiag.basis.shape[0] == 12
    rec = reconstruction_error(op, r.eigenvalues, r.eigenvectors, accum_dtype=jnp.float64)
    assert rec < 1e-4


# --------------------------- fused Lanczos update ----------------------------


def test_fused_update_policy_gating():
    """Non-compensated policies may route through the fused Pallas kernel;
    compensated policies keep the reference reductions for beta.  Routing is
    plan-driven: with no measured plan the static mode table decides (unfused
    in interpret mode), so the fused record needs an explicit pin here."""
    from repro.core import FCF
    from repro.core.lanczos import fused_update_enabled, make_local_ops

    assert fused_update_enabled(FFF) and fused_update_enabled(FDF)
    assert not fused_update_enabled(FCF)
    assert make_local_ops(lambda x: x, FFF, fused=True).fused_update is not None
    # The policy gate wins over any pin or plan for compensated policies.
    assert make_local_ops(lambda x: x, FCF, fused=True).fused_update is None
    assert make_local_ops(lambda x: x, FCF).fused_update is None


def test_update_mode_table_default(monkeypatch):
    """With no pin, interpret mode defaults to the unfused update (measured:
    the Pallas interpreter loses on per-step overhead)."""
    from repro.core.lanczos import make_local_ops, resolve_update_mode

    monkeypatch.delenv("REPRO_FUSED_LANCZOS", raising=False)
    assert resolve_update_mode(FFF.effective()) == "unfused"
    ops = make_local_ops(lambda x: x, FFF)
    assert ops.fused_update is None


def test_fused_update_kill_switch(monkeypatch):
    from repro.core.lanczos import fused_update_enabled, make_local_ops

    monkeypatch.setenv("REPRO_FUSED_LANCZOS", "0")
    assert not fused_update_enabled(FFF)
    assert make_local_ops(lambda x: x, FFF).fused_update is None


@pytest.mark.parametrize(
    "reorth, matrix", [("none", "norm_csr"), ("half", "web_csr"), ("full", "web_csr")]
)
def test_fused_lanczos_matches_reference_loop(request, reorth, matrix):
    """Loop parity: the fused-kernel recurrence (and, with reorth='none',
    its fused norm) reproduces the unfused reference loop.  The kernel sums
    the norm lane by lane, in another order than ``jnp.sum``; only the
    unreorthogonalized loop consumes that norm, and on the unit adjacency
    (dominant eigenvalue ~64) it amplifies the last-ulp difference (the
    fourth eigenvalue moves by 1.5e-4 relative on the CPU, 6e-8 on the
    normalized matrix), so that case runs on the normalized matrix.  With
    reorthogonalization the norm is recomputed, and the badly scaled unit
    adjacency stays."""
    from repro.core import make_operator, solve_fixed
    from repro.core.lanczos import ops_for_operator
    from repro.kernels.engine import make_engine

    csr = request.getfixturevalue(matrix)
    op = make_operator(csr, dtype=jnp.float32, engine=make_engine(csr))
    pol = FFF.effective()

    def solve(fused):
        ops = ops_for_operator(op, pol, fused=fused)
        assert (ops.fused_update is not None) == fused
        return solve_fixed(op, 4, pol, num_iters=12, reorth=reorth, seed=3, ops=ops)

    r_fused, r_ref = solve(True), solve(False)
    np.testing.assert_allclose(
        np.asarray(r_fused.eigenvalues), np.asarray(r_ref.eigenvalues),
        rtol=2e-5, atol=2e-5,
    )


def test_fused_update_wired_into_loop(monkeypatch):
    """The jitted loop actually calls the kernel wrapper when permitted (the
    call is observed at trace time) and skips it when the policy forbids."""
    from repro.core import FCF
    from repro.core.lanczos import lanczos_tridiag, make_local_ops
    from repro.kernels import ops as kops

    calls = []
    real = kops.lanczos_update

    def spy(*a, **k):
        calls.append(1)
        return real(*a, **k)

    monkeypatch.setattr(kops, "lanczos_update", spy)
    a = np.diag(np.arange(1.0, 17.0))
    mv = lambda x: jnp.asarray(a, x.dtype) @ x  # noqa: E731
    v1 = jnp.ones((16,), jnp.float32)

    def run(pol):
        pol = pol.effective()
        ops = make_local_ops(mv, pol, fused=True)  # pin: the interpreter's table is unfused
        lanczos_tridiag(mv, v1, 4, pol, reorth="half", jit=False, ops=ops)

    run(FFF)
    assert calls  # fused path traced/executed
    calls.clear()
    run(FCF)
    assert not calls  # compensated policy keeps the reference recurrence
