"""The vector Pallas kernels (interpret mode) vs their pure-jnp oracles, and
the SpMV bodies the engines run, in both modes, vs a float64 SciPy product."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.engine import TileConfig, make_engine
from repro.kernels.lanczos_update import lanczos_update_kernel_call
from repro.kernels.mixed_dot import mixed_dot_kernel_call
from repro.sparse import generate, to_device_ell
from repro.sparse.formats import blocked_ell_from_csr

DTYPES = [jnp.float32, jnp.bfloat16]
TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _scipy_product(csr, x, dtype):
    """float64 SciPy product of the matrix and ``x`` as they are stored."""
    a = csr.to_scipy().astype(np.float64)
    a.data = np.asarray(jnp.asarray(a.data, dtype), np.float64)
    return a @ np.asarray(x, np.float64)


@pytest.mark.parametrize("n", [512, 2048])
@pytest.mark.parametrize("deg", [2.0, 10.0])
@pytest.mark.parametrize("dtype", DTYPES)
def test_spmv_ell_kernel_sweep(n, deg, dtype):
    """``SpmvEngine.ell_matvec`` over the padded layout, f32 accumulation."""
    csr = generate("urand", n, deg, seed=int(deg) + n, values="uniform")
    ell = to_device_ell(csr, dtype=dtype)
    engine = make_engine(csr, "ell", accum_dtype=jnp.float32)
    x = jnp.asarray(np.random.default_rng(0).standard_normal(csr.n), dtype=dtype)
    y = engine.ell_matvec(ell.val, ell.col, x)[: csr.n]
    np.testing.assert_allclose(
        np.asarray(y, np.float64), _scipy_product(csr, x, dtype),
        rtol=TOL[jnp.float32], atol=TOL[jnp.float32] * 10,
    )


@pytest.mark.parametrize("block_r,block_w", [(8, 128), (8, 512), (16, 256)])
def test_spmv_ell_block_shapes(block_r, block_w):
    """The layout padding of a ``TileConfig`` changes the shape, not the
    product: rows padded to ``block_r``, widths to ``block_w``."""
    csr = generate("web", 1024, 6.0, seed=1, values="uniform")
    ell = to_device_ell(csr, dtype=jnp.float32, row_tile=block_r, slot_tile=block_w)
    assert ell.val.shape[0] % block_r == 0 and ell.val.shape[1] % block_w == 0
    engine = make_engine(
        csr, "ell", tiles=TileConfig(block_r=block_r, block_w=block_w)
    )
    x = jnp.asarray(np.random.default_rng(1).standard_normal(csr.n), jnp.float32)
    y = engine.ell_matvec(ell.val, ell.col, x)
    assert y.shape == (ell.val.shape[0],)
    np.testing.assert_allclose(
        np.asarray(y, np.float64)[: csr.n], _scipy_product(csr, x, jnp.float32),
        rtol=2e-5, atol=1e-4,
    )


@pytest.mark.parametrize("n", [1024, 16384])
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("compensated", [False, True])
def test_mixed_dot_kernel_sweep(n, dtype, compensated):
    rng = np.random.default_rng(n)
    a = jnp.asarray(rng.standard_normal(n), dtype=dtype)
    b = jnp.asarray(rng.standard_normal(n), dtype=dtype)
    out = mixed_dot_kernel_call(a, b, compensated=compensated, interpret=True)
    got = float(out.sum())
    want = float(ref.mixed_dot_ref(a, b, accum_dtype=jnp.float64))
    assert abs(got - want) < TOL[dtype] * max(1.0, abs(want)) * 10


def test_mixed_dot_compensation_beats_naive_f32():
    """Neumaier compensation recovers accuracy on an adversarial sum."""
    n = 1 << 18
    rng = np.random.default_rng(9)
    big = rng.standard_normal(n // 2) * 1e4
    a_np = np.stack([big, -big], axis=1).reshape(-1) + rng.standard_normal(n) * 1e-3
    a = jnp.asarray(a_np, jnp.float32)
    one = jnp.ones_like(a)
    want = float(np.sum(a_np.astype(np.float64)))
    naive = float(
        mixed_dot_kernel_call(a, one, compensated=False, block=1024, interpret=True).sum()
    )
    comp = float(mixed_dot_kernel_call(a, one, compensated=True, block=1024, interpret=True).sum())
    assert abs(comp - want) <= abs(naive - want)


@pytest.mark.parametrize("n", [1024, 8192])
@pytest.mark.parametrize("dtype", DTYPES)
def test_lanczos_update_kernel_sweep(n, dtype):
    rng = np.random.default_rng(n + 1)
    w, v, vp = (jnp.asarray(rng.standard_normal(n), dtype=dtype) for _ in range(3))
    alpha, beta = jnp.float32(0.37), jnp.float32(1.21)
    u_k, n_k = lanczos_update_kernel_call(w, v, vp, alpha, beta, interpret=True)
    u_r, n_r = ref.lanczos_update_ref(w, v, vp, alpha, beta)
    np.testing.assert_allclose(
        np.asarray(u_k, np.float64), np.asarray(u_r, np.float64), rtol=TOL[dtype], atol=TOL[dtype]
    )
    assert abs(float(n_k[0]) - float(n_r)) < TOL[dtype] * max(1.0, float(n_r)) * 10


def test_ops_wrappers_dispatch(web_csr):
    """ops.py wrappers: kernel path (f32) and jnp fallback (f64) both correct."""
    rng = np.random.default_rng(3)
    a, b = (jnp.asarray(rng.standard_normal(web_csr.n), jnp.float32) for _ in range(2))
    d32 = ops.mixed_dot(a, b, accum_dtype=jnp.float32)
    d64 = ops.mixed_dot(a, b, accum_dtype=jnp.float64)
    assert d32.dtype == jnp.float32 and d64.dtype == jnp.float64
    np.testing.assert_allclose(float(d32), float(d64), rtol=1e-4, atol=1e-4)
    alpha, beta = jnp.float32(0.37), jnp.float32(1.21)
    u32, n32 = ops.lanczos_update(a, b, a, alpha, beta, accum_dtype=jnp.float32)
    u64, n64 = ops.lanczos_update(a, b, a, alpha, beta, accum_dtype=jnp.float64)
    np.testing.assert_allclose(np.asarray(u32, np.float64), np.asarray(u64, np.float64),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(n32), float(n64), rtol=1e-4)


@pytest.mark.parametrize("bs", [4, 8])
@pytest.mark.parametrize("kind", ["road", "urand"])
def test_spmv_bsr_kernel(bs, kind):
    """``SpmvEngine.bsr_matvec`` over the blocked-ELL layout."""
    csr = generate(kind, 512, 3.0, seed=bs, values="uniform")
    val, bcol, n_rows = blocked_ell_from_csr(csr, block_size=bs, dtype=jnp.float32)
    engine = make_engine(csr, "bsr", block_size=bs)
    x = jnp.asarray(np.random.default_rng(7).standard_normal(csr.n), jnp.float32)
    y = engine.bsr_matvec(val, bcol, x)[:n_rows]
    np.testing.assert_allclose(
        np.asarray(y, np.float64), _scipy_product(csr, x, jnp.float32), rtol=2e-5, atol=1e-4
    )


def test_spmv_bsr_eigensolver_path():
    """Full Top-K solve through the blocked-ELL SpMV engine.

    Uses a road-lattice matrix: block-local structure keeps the slot count
    small — the regime BSR targets.
    """
    from repro.api import eigsh

    csr = generate("road", 484, 3.0, seed=11, values="normalized")
    r_coo = eigsh(csr, 3, policy="FFF", reorth="full", num_iters=9, seed=0, format="coo")
    r_bsr = eigsh(csr, 3, policy="FFF", reorth="full", num_iters=9, seed=0, format="bsr")
    assert r_bsr.spmv_format == "bsr"
    np.testing.assert_allclose(
        np.asarray(r_coo.eigenvalues), np.asarray(r_bsr.eigenvalues), rtol=1e-4
    )


def test_lanczos_update_wrapper_pads_arbitrary_lengths():
    """ops.lanczos_update handles n not divisible by the kernel block
    (zero-padded lanes produce u=0 and leave the norm untouched)."""
    rng = np.random.default_rng(9)
    n = 5000  # 5000 % 4096 != 0
    w, v, vp = (jnp.asarray(rng.standard_normal(n), jnp.float32) for _ in range(3))
    alpha, beta = jnp.float32(0.37), jnp.float32(1.21)
    u, nrm = ops.lanczos_update(w, v, vp, alpha, beta, accum_dtype=jnp.float32)
    u_r, n_r = ref.lanczos_update_ref(w, v, vp, alpha, beta)
    assert u.shape == (n,)
    np.testing.assert_allclose(
        np.asarray(u, np.float64), np.asarray(u_r, np.float64), rtol=1e-5, atol=1e-5
    )
    assert abs(float(nrm) - float(n_r)) < 1e-2 * max(1.0, float(n_r))
