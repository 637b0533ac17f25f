"""The generators follow the GAP definitions (checked at scale 10-12)."""

import numpy as np
import pytest

from bench.gen import generate

KRON = {"generator": "kron", "scale": 12, "edge_factor": 16, "graph_seed": 3}
URAND = {"generator": "urand", "scale": 12, "edge_factor": 16, "graph_seed": 3}


def _dense_rows(g):
    return np.repeat(np.arange(g.n), np.diff(g.indptr))


@pytest.mark.parametrize("cfg", [KRON, URAND], ids=["kron", "urand"])
def test_same_seed_same_csr(cfg):
    a, b = generate(cfg, 7), generate(cfg, 7)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    c = generate(cfg, 8)
    assert not np.array_equal(a.indices, c.indices)


@pytest.mark.parametrize("cfg", [KRON, URAND], ids=["kron", "urand"])
def test_symmetric_simple_unit(cfg):
    g = generate(cfg, 2**31 + 9)
    rows = _dense_rows(g)
    assert np.all(rows != g.indices), "self-loop"
    keys = rows * g.n + g.indices
    assert np.all(np.diff(keys) > 0), "duplicate or unsorted entry"
    transposed = np.sort(g.indices.astype(np.int64) * g.n + rows)
    np.testing.assert_array_equal(keys, transposed)
    assert np.all(g.data == 1.0)
    assert g.indptr.dtype == np.int64 and g.indices.dtype == np.int32


@pytest.mark.parametrize("cfg", [KRON, URAND], ids=["kron", "urand"])
def test_edge_count_within_edge_factor(cfg):
    g = generate(cfg, 1)
    bound = 2 * g.n * cfg["edge_factor"]
    assert 0.5 * bound < g.nnz <= bound
    assert g.nnz % 2 == 0


def test_seed_changes_labels_not_sizes():
    a, b = generate(KRON, 1), generate(KRON, 2)
    assert a.nnz == b.nnz
    np.testing.assert_array_equal(np.sort(np.diff(a.indptr)), np.sort(np.diff(b.indptr)))


def test_kron_labels_permuted():
    hubs = {int(np.argmax(np.diff(generate(KRON, s).indptr))) for s in range(5)}
    assert 0 not in hubs and len(hubs) > 1
    deg = np.diff(generate(KRON, 1).indptr)
    assert deg.max() > 20 * deg.mean(), "kron rows should be skewed"


def test_urand_rows_near_poisson():
    g = generate(URAND, 4)
    deg = np.diff(g.indptr)
    lam = 2 * URAND["edge_factor"]
    assert abs(deg.mean() - lam) < 0.05 * lam
    assert abs(deg.var() - lam) < 0.15 * lam
    assert deg.max() < lam + 8 * np.sqrt(lam)
