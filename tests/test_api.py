"""The unified ``eigsh`` frontend: coercion, dispatch, result schema, shims."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.api import (
    BACKENDS,
    CHUNKED_NNZ_THRESHOLD,
    EigenResult,
    SolverConfig,
    eigsh,
    resolve_policy,
    select_backend,
)
from repro.core import POLICIES, make_operator
from repro.core.metrics import eigsh_reference

K = 4
ITERS = 24


@pytest.fixture(scope="module")
def ref_vals(web_csr):
    vals, _ = eigsh_reference(web_csr, K)
    return vals


# ---------------------------------------------------------------- coercion


def _schema_check(res, n):
    assert isinstance(res, EigenResult)
    assert res.eigenvalues.shape == (K,)
    assert res.eigenvectors.shape == (n, K)
    assert res.residuals.shape == (K,)
    assert res.converged.shape == (K,)
    assert res.converged.dtype == bool
    assert res.backend in BACKENDS
    assert res.iterations >= K
    assert "total_s" in res.timings
    assert res.k == K and res.n == n


def test_accepts_all_input_forms(web_csr, ref_vals):
    """Dense / CSR / scipy-sparse / operator / callable give the same answer
    through an identical result schema."""
    n = web_csr.n
    sp = web_csr.to_scipy()
    inputs = {
        "csr": web_csr,
        "dense": web_csr.toarray(),
        "scipy": sp,
        "operator": make_operator(web_csr, "coo", dtype=jnp.float32),
        "callable": lambda x: sp @ np.asarray(x, dtype=np.float64),
    }
    for name, a in inputs.items():
        res = eigsh(a, K, policy="FDF", reorth="full", num_iters=ITERS,
                    n=n if name == "callable" else None)
        _schema_check(res, n)
        np.testing.assert_allclose(
            np.asarray(res.eigenvalues, dtype=np.float64), ref_vals, rtol=1e-4,
            err_msg=f"input form {name}",
        )


def test_scipy_linearoperator_input(web_csr, ref_vals):
    import scipy.sparse.linalg as spla

    lo = spla.aslinearoperator(web_csr.to_scipy())
    res = eigsh(lo, K, policy="FDF", reorth="full", num_iters=ITERS)
    np.testing.assert_allclose(
        np.asarray(res.eigenvalues, dtype=np.float64), ref_vals, rtol=1e-4
    )


def test_callable_without_n_rejected():
    with pytest.raises(ValueError, match="n="):
        eigsh(lambda x: x, 2)


def test_non_square_rejected():
    with pytest.raises(ValueError, match="square"):
        eigsh(np.zeros((4, 5)), 2)


def test_unknown_input_type_rejected():
    with pytest.raises(TypeError, match="does not understand"):
        eigsh(object(), 2)


# ---------------------------------------------------------------- dispatch


def test_auto_dispatch_decisions():
    # >1 device and an explicit matrix -> distributed (the paper's headline mode)
    assert select_backend("auto", has_matrix=True, nnz=10_000, device_count=4) == "distributed"
    # huge nnz, single device -> out-of-core chunked path
    assert (
        select_backend("auto", has_matrix=True, nnz=CHUNKED_NNZ_THRESHOLD, device_count=1)
        == "chunked"
    )
    # host-memory pressure alone also forces chunked
    assert (
        select_backend(
            "auto", has_matrix=True, nnz=1_000_000, device_count=1, free_bytes=1_000_000
        )
        == "chunked"
    )
    # a tolerance request -> restarted (fixed-m cannot promise residuals),
    # even when multiple devices are visible
    assert select_backend("auto", has_matrix=True, nnz=100, tol=1e-8, device_count=1) == "restarted"
    assert select_backend("auto", has_matrix=True, nnz=100, tol=1e-8, device_count=8) == "restarted"
    assert select_backend("auto", has_matrix=False, tol=1e-8) == "restarted"
    # default -> the paper's single-device pipeline
    assert select_backend("auto", has_matrix=True, nnz=100, device_count=1) == "single"
    assert select_backend("auto", has_matrix=False) == "single"


WK_NNZ = 90_000_000  # the order of the paper's Wikipedia matrix (Table I) nnz
V5E_HBM = 16 * 2**30


@pytest.mark.parametrize(
    "nnz, tol, device_bytes, want",
    [
        # in HBM many times over: stays in core although far above the
        # host-RAM stand-in's 25M-nnz threshold
        (WK_NNZ, None, V5E_HBM, "single"),
        (WK_NNZ, 1e-5, V5E_HBM, "restarted"),
        # a working set (12 B/nnz) over half the device: out of core
        (V5E_HBM // 12, None, V5E_HBM, "chunked"),
        (V5E_HBM // 24 - 1, None, V5E_HBM, "single"),
    ],
)
def test_auto_dispatch_judges_device_memory(nnz, tol, device_bytes, want):
    got = select_backend(
        "auto", has_matrix=True, nnz=nnz, tol=tol, device_count=1,
        device_bytes=device_bytes, free_bytes=1 << 40,
    )
    assert got == want


@pytest.mark.parametrize("fmt", ["coo", "ell", "hybrid", "bsr"])
def test_layout_bytes_match_the_built_layout(norm_csr, fmt):
    """The residency estimate counts what the engine really builds, padding
    included (hybrid's tail is counted at the unaligned cap: at most 1% over
    here)."""
    from repro.core.operators import make_operator
    from repro.kernels.engine import make_engine, matrix_stats

    est = matrix_stats(norm_csr).layout_bytes(fmt, 4)
    op = make_operator(norm_csr, dtype=jnp.float32, engine=make_engine(norm_csr, fmt))
    built = sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(op.mat))
    assert built <= est <= 1.01 * built


# 1 MB of device memory: half of it holds norm_csr's 13,144 non-zeros at 12 B
# each (158 KB) with room to spare, so the 12 B figure alone would keep
# every case below in core.
SMALL_DEVICE = 1_000_000


@pytest.mark.parametrize(
    "fmt, num_iters, want",
    [
        # 158 KB of COO and an 8-vector f32 basis (66 KB) fit
        ("coo", 8, "single"),
        # ELL pads every row to the hub row's 2,037 entries: 34 MB
        ("ell", 8, "chunked"),
        # a 64-vector basis (524 KB) pushes the same COO over
        ("coo", 64, "chunked"),
    ],
)
def test_auto_dispatch_counts_padding_and_basis(norm_csr, monkeypatch, fmt, num_iters, want):
    from repro.api import dispatch, session_cache_clear

    monkeypatch.setattr(dispatch, "device_memory_bytes", lambda: SMALL_DEVICE)
    session_cache_clear()
    res = eigsh(norm_csr, 4, format=fmt, num_iters=num_iters, policy="FDF")
    session_cache_clear()
    assert res.backend == want


def test_auto_dispatch_cpu_has_no_device_figure():
    """The CPU backend reports no memory_stats, so host RAM stands in."""
    from repro.api.dispatch import device_memory_bytes

    assert device_memory_bytes() is None
    assert select_backend("auto", has_matrix=True, nnz=WK_NNZ, device_count=1) == "chunked"


def test_explicit_backend_validation():
    with pytest.raises(ValueError, match="unknown backend"):
        select_backend("warp", has_matrix=True)
    # distributed / chunked need a host-side sparse matrix
    with pytest.raises(ValueError, match="host-side sparse matrix"):
        select_backend("distributed", has_matrix=False)
    with pytest.raises(ValueError, match="host-side sparse matrix"):
        select_backend("chunked", has_matrix=False)
    assert select_backend("single", has_matrix=False) == "single"


def test_single_process_auto_is_single(norm_csr):
    """In this 1-device container, auto must not pick distributed."""
    assert len(jax.devices()) == 1
    res = eigsh(norm_csr, K, policy="FDF", num_iters=ITERS)
    assert res.backend == "single"
    # The plan/execute split reports what the call paid in partition["spmv"]
    # on every backend (single included).
    assert res.partition["spmv"]["format"] == res.spmv_format


def test_chunked_backend_matches_single(norm_csr):
    v0 = jnp.ones((norm_csr.n,), jnp.float64)
    r_s = eigsh(norm_csr, K, backend="single", policy="FDF", reorth="full",
                num_iters=ITERS, v0=v0)
    r_c = eigsh(norm_csr, K, backend="chunked", chunk_nnz=4096, policy="FDF",
                reorth="full", num_iters=ITERS, v0=v0)
    assert r_c.backend == "chunked"
    np.testing.assert_allclose(
        np.asarray(r_s.eigenvalues), np.asarray(r_c.eigenvalues), rtol=1e-6
    )


# ---------------------------------------------------------------- policies


def test_string_policies_resolve(norm_csr):
    for name in POLICIES:
        assert resolve_policy(name).name == name
    res = eigsh(norm_csr, K, policy="FFF", num_iters=ITERS)
    assert res.policy == "FFF"


def test_unknown_policy_rejected():
    with pytest.raises(ValueError, match="unknown precision policy"):
        resolve_policy("XYZ")


def test_policy_instance_accepted(norm_csr):
    from repro.core import FDF

    res = eigsh(norm_csr, K, policy=FDF, num_iters=ITERS)
    assert res.policy == "FDF"  # x64 enabled in conftest, no downgrade


# ---------------------------------------------------------------- results


def test_residuals_shrink_with_num_iters(norm_csr):
    r8 = eigsh(norm_csr, K, policy="FDF", reorth="full", num_iters=8)
    r32 = eigsh(norm_csr, K, policy="FDF", reorth="full", num_iters=32)
    assert r32.residuals.max() < r8.residuals.max()
    assert r8.iterations == 8 and r32.iterations == 32


def test_converged_flags_consistent_with_tol(norm_csr):
    tol = 1e-6
    res = eigsh(norm_csr, K, policy="FDF", backend="single", reorth="full",
                num_iters=ITERS, tol=tol)
    lam = np.abs(np.asarray(res.eigenvalues, dtype=np.float64))
    np.testing.assert_array_equal(res.converged, res.residuals <= tol * lam)
    assert res.tol == tol


def test_restarted_backend_converges(web_csr, ref_vals):
    res = eigsh(web_csr, K, policy="FDF", tol=1e-7, subspace=16)
    assert res.backend == "restarted"
    assert res.all_converged
    assert res.restarts >= 1
    assert res.iterations > 16  # more than one cycle was needed
    np.testing.assert_allclose(
        np.asarray(res.eigenvalues, dtype=np.float64), ref_vals, rtol=1e-5, atol=1e-7
    )


def test_num_iters_budget_caps_restarts(norm_csr):
    res = eigsh(norm_csr, K, policy="FDF", backend="restarted", tol=1e-14,
                subspace=12, num_iters=20)
    # budget: first cycle 12 steps + one restart of (12 - 4) steps
    assert res.iterations <= 20
    assert not res.all_converged  # unreachable tol, budget respected
    # a budget that doesn't fit a second cycle must not overshoot
    res13 = eigsh(norm_csr, K, policy="FDF", backend="restarted", tol=1e-14,
                  subspace=12, num_iters=13)
    assert res13.iterations <= 13
    # a budget below the minimum viable subspace is an error, not an overshoot
    with pytest.raises(ValueError, match="num_iters"):
        eigsh(norm_csr, K, backend="restarted", tol=1e-8, num_iters=K + 1)


def test_unconverged_restarted_vectors_stay_consistent(norm_csr):
    """Exhausting the restart budget must still return eigenvectors in the
    coordinates of the final basis (unit norm, residuals matching the
    reported Ritz bounds to order of magnitude)."""
    res = eigsh(norm_csr, K, policy="FDF", backend="restarted", tol=1e-30,
                subspace=12, max_restarts=1)
    assert not res.all_converged
    x = np.asarray(res.eigenvectors, dtype=np.float64)
    norms = np.linalg.norm(x, axis=0)
    np.testing.assert_allclose(norms, 1.0, atol=1e-3)
    a = norm_csr.to_scipy()
    lam = np.asarray(res.eigenvalues, dtype=np.float64)
    true_resid = np.linalg.norm(a @ x - x * lam, axis=0)
    # the Ritz bound equals the true residual for an exact Krylov subspace
    np.testing.assert_allclose(true_resid, res.residuals, rtol=0.5, atol=1e-6)


def test_restarted_rejects_zero_max_restarts(norm_csr):
    with pytest.raises(ValueError, match="max_restarts"):
        eigsh(norm_csr, K, backend="restarted", tol=1e-8, max_restarts=0)


def test_restarted_without_tol_iterates_to_reported_default(norm_csr):
    """backend='restarted' with tol=None must iterate toward the same
    tolerance the converged flags are judged against — not a hardcoded one."""
    res = eigsh(norm_csr, K, backend="restarted", policy="FFF", subspace=16)
    assert res.tol == pytest.approx(float(np.sqrt(np.finfo(np.float32).eps)))
    np.testing.assert_array_equal(
        res.converged,
        res.residuals <= res.tol * np.abs(np.asarray(res.eigenvalues, dtype=np.float64)),
    )


def test_explicit_mesh_forces_distributed_under_auto(norm_csr):
    """mesh= must not be silently dropped when tol would pick restarted;
    and mesh + matrix-free input is a clear error."""
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()).reshape(1), ("data",))
    res = eigsh(norm_csr, K, mesh=mesh, tol=1e-6, num_iters=ITERS)
    assert res.backend == "distributed"
    with pytest.raises(ValueError, match="matrix-free"):
        eigsh(lambda x: x, 2, n=16, mesh=mesh)


def test_restarted_warns_on_ignored_reorth(norm_csr):
    with pytest.warns(UserWarning, match="ignored by the restarted backend"):
        eigsh(norm_csr, K, backend="restarted", tol=1e-6, reorth="none",
              subspace=16, max_restarts=2)


def test_reorth_default_is_per_backend():
    from repro.api.frontend import _resolve_reorth

    assert _resolve_reorth(None, "single") == "half"       # paper Alg. 1
    assert _resolve_reorth(None, "chunked") == "half"
    assert _resolve_reorth(None, "distributed") == "full"  # paper multi-GPU
    assert _resolve_reorth("none", "distributed") == "none"  # explicit wins


def test_scipy_style_unpack(norm_csr):
    w, v = eigsh(norm_csr, K, policy="FDF", num_iters=ITERS)
    assert w.shape == (K,) and v.shape == (norm_csr.n, K)


def test_solver_config_reusable(norm_csr):
    cfg = SolverConfig(policy="FFF", reorth="full", num_iters=ITERS)
    r1 = eigsh(norm_csr, K, config=cfg)
    r2 = eigsh(norm_csr, K, config=cfg)
    np.testing.assert_array_equal(np.asarray(r1.eigenvalues), np.asarray(r2.eigenvalues))
    assert r1.policy == "FFF"


def test_summary_renders(norm_csr):
    res = eigsh(norm_csr, K, policy="FDF", num_iters=ITERS)
    s = res.summary()
    assert "backend=single" in s and "policy=FDF" in s


# ---------------------------------------------------------------- shims


def test_topk_eigs_shim_deprecated(norm_csr):
    from repro.core import topk_eigs

    op = make_operator(norm_csr, "coo", dtype=jnp.float32)
    with pytest.warns(DeprecationWarning, match="eigsh"):
        old = topk_eigs(op, K, reorth="full", num_iters=ITERS)
    new = eigsh(op, K, policy="FDF", reorth="full", num_iters=ITERS)
    np.testing.assert_allclose(
        np.asarray(old.eigenvalues), np.asarray(new.eigenvalues), rtol=1e-6
    )
    assert old.wall_time_s > 0


def test_topk_eigs_restarted_shim_deprecated(norm_csr):
    from repro.core import topk_eigs_restarted

    op = make_operator(norm_csr, "coo", dtype=jnp.float32)
    with pytest.warns(DeprecationWarning, match="eigsh"):
        old = topk_eigs_restarted(op, K, m=16, tol=1e-6, max_restarts=20)
    assert old.eigenvalues.shape == (K,)
    assert old.tridiag.basis.shape[0] == 16  # bounded-memory contract intact
