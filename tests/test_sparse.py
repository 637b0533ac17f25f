"""Sparse formats, generators, and the nnz-balanced partition.

Property-style checks run here from a fixed seeded-random case list so the
suite needs no optional dependencies; when ``hypothesis`` is installed
(the ``[test]`` extra), ``test_sparse_properties.py`` additionally drives
the same check bodies from search strategies.
"""

import numpy as np
import pytest

from sparse_checks import check_nnz_balance, check_partition_spmv_equivalence

from repro.sparse import SUITE, csr_from_coo, generate, suite_matrix, to_device_coo, to_device_ell

import jax.numpy as jnp


@pytest.mark.parametrize("kind", ["web", "road", "urand", "kron"])
def test_generators_symmetric_no_selfloops(kind):
    csr = generate(kind, 1024, 4.0, seed=3)
    a = csr.toarray()
    np.testing.assert_allclose(a, a.T, atol=0)
    assert np.all(np.diag(a) == 0)


def test_normalized_spectrum_bounded():
    csr = generate("web", 2048, 6.0, seed=0, values="normalized")
    from repro.core.metrics import eigsh_reference

    vals, _ = eigsh_reference(csr, 3)
    assert np.all(np.abs(vals) <= 1.0 + 1e-9)


def _seeded_spmv_cases(num=20, seed=2024):
    rng = np.random.default_rng(seed)
    return [
        (int(rng.integers(16, 301)), float(rng.uniform(1.0, 8.0)), int(rng.integers(1, 8)))
        for _ in range(num)
    ]


@pytest.mark.parametrize("n,deg,g", _seeded_spmv_cases())
def test_partition_spmv_equivalence(n, deg, g):
    check_partition_spmv_equivalence(n, deg, g)


@pytest.mark.parametrize("g", range(1, 10))
def test_nnz_balance_property(g):
    check_nnz_balance(g)


def test_ell_roundtrip(web_csr):
    ell = to_device_ell(web_csr)
    coo = to_device_coo(web_csr)
    x = jnp.asarray(np.random.default_rng(5).standard_normal(web_csr.n), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(ell.matvec(x, accum_dtype=jnp.float64)),
        np.asarray(coo.matvec(x, accum_dtype=jnp.float64)),
        rtol=1e-10,
    )


def test_suite_covers_paper_table():
    assert len(SUITE) == 15  # the paper's Table I has 15 matrices
    csr = suite_matrix("WB-TA", scale=0.1)
    assert csr.nnz > 0


def test_csr_from_coo_dedupes():
    rows = np.array([0, 0, 1])
    cols = np.array([1, 1, 0])
    vals = np.array([1.0, 2.0, 3.0])
    csr = csr_from_coo(rows, cols, vals, 2)
    assert csr.nnz == 2
    assert csr.toarray()[0, 1] == 3.0


@pytest.mark.parametrize("order", ["sorted", "shuffled"])
def test_row_sums_accurate_on_a_long_row(order):
    """A 400k-entry f32 row summed entry by entry (a plain scatter-add) drifts
    by ~1e-5; row_sums stays near f32 rounding, for any entry order, and
    sums every short row exactly as the scatter does."""
    import jax

    from repro.sparse.formats import row_sums

    rng = np.random.default_rng(3)
    long_len = 400_000
    rows = np.concatenate(
        [np.repeat(np.arange(4), 3), np.full(long_len, 4), np.repeat(np.arange(5, 9), 5)]
    ).astype(np.int32)
    vals = (rng.random(rows.size) / long_len).astype(np.float32)
    if order == "shuffled":
        perm = rng.permutation(rows.size)
        rows, vals = rows[perm], vals[perm]
    exact = np.bincount(rows, vals.astype(np.float64), minlength=10)
    got = np.asarray(row_sums(jnp.asarray(vals), jnp.asarray(rows), 10), np.float64)
    plain = np.asarray(jax.ops.segment_sum(jnp.asarray(vals), jnp.asarray(rows), 10), np.float64)
    rel = np.abs(got - exact) / np.maximum(np.abs(exact), 1e-30)
    assert rel[4] < 1e-6 < abs(plain[4] - exact[4]) / exact[4]
    assert np.max(rel) < 1e-6
    assert got[9] == 0.0
