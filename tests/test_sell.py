"""The row-length-bucketed ELL layout (``DeviceSELL``, format ``"sell"``).

Its SpMV against SciPy in float64 on the GAP generators' matrices (kron:
skewed, urand: uniform) and on edge cases, its padding bound, its byte
count, where format selection takes it (compiled execution only, never on
the distributed or chunked engines), and a solve and a persisted plan over
it.
"""

import os
import sys
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench.gen import generate as gap_generate  # noqa: E402
from repro.api import EigenSession, SolverConfig, eigsh, prepare  # noqa: E402
from repro.core.distributed import DISTRIBUTED_FORMATS  # noqa: E402
from repro.core.partition import nnz_balanced_splits  # noqa: E402
from repro.kernels.engine import (  # noqa: E402
    choose_format,
    make_engine,
    matrix_stats,
    shard_stats,
)
from repro.sparse import CSR, csr_from_coo  # noqa: E402
from repro.sparse.formats import (  # noqa: E402
    ROW_BLOCK,
    conversion_count,
    sell_classes,
    to_device_sell,
)

FAMILIES = ("kron", "urand")
# What format="auto" picks for these matrices under the interpreter.
INTERPRET_PICK = {"kron": "coo", "urand": "ell"}


def gap_csr(family: str, scale: int = 11, seed: int = 1) -> CSR:
    cfg = {
        "generator": family, "scale": scale, "edge_factor": 16, "graph_seed": 20,
        "kronecker_abc": [0.57, 0.19, 0.19],
    }
    g = gap_generate(cfg, seed)
    # Non-unit values, so a misplaced value shows.
    data = np.random.default_rng(seed).uniform(0.5, 1.5, g.nnz)
    return CSR(g.indptr, g.indices, data, (g.n, g.n))


@pytest.fixture(scope="module", params=FAMILIES)
def family_csr(request):
    return request.param, gap_csr(request.param)


def _spmv(csr, storage, acc, x):
    mat = to_device_sell(csr, dtype=storage)
    return np.asarray(mat.matvec(jnp.asarray(x, acc), accum_dtype=acc), np.float64), mat


@pytest.mark.parametrize(
    "storage,acc,rtol",
    [
        (jnp.float32, jnp.float32, 1e-5),
        (jnp.float64, jnp.float64, 1e-12),
        (jnp.bfloat16, jnp.float32, 1e-5),
    ],
    ids=["f32", "f64", "bf16-storage"],
)
def test_sell_spmv_matches_scipy_f64(family_csr, storage, acc, rtol):
    _, csr = family_csr
    x = np.random.default_rng(2).standard_normal(csr.n)
    stored = np.asarray(jnp.asarray(csr.data, storage), np.float64)
    xs = np.asarray(jnp.asarray(x, acc), np.float64)
    want = CSR(csr.indptr, csr.indices, stored, csr.shape).to_scipy() @ xs
    got, _ = _spmv(csr, storage, acc, x)
    np.testing.assert_allclose(got, want, rtol=rtol, atol=rtol * np.abs(want).max())


def test_sell_padding_and_stats_match_the_built_layout(family_csr):
    family, csr = family_csr
    stats = matrix_stats(csr, with_blocks=False)
    mat = to_device_sell(csr)
    summary = mat.summary()
    assert summary["slots_per_nnz"] <= 1.125
    assert stats.sell_slots == summary["slots"] == mat.slots
    assert stats.sell_classes == summary["classes"] == len(mat.classes)
    lens = csr.row_nnz()
    assert stats.sell_pieces == int((-(-lens // ROW_BLOCK)).sum()) == mat.order.shape[0]
    # Classes tile the flat arrays in order, each padded only to its width.
    off = 0
    for width, rows, offset in mat.classes:
        assert offset == off
        off += width * rows
    assert off == mat.slots


@pytest.mark.parametrize("storage", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_sell_layout_bytes_equal_the_built_container(family_csr, storage):
    _, csr = family_csr
    mat = to_device_sell(csr, dtype=storage)
    built = sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(mat))
    est = matrix_stats(csr, with_blocks=False).layout_bytes("sell", jnp.dtype(storage).itemsize)
    assert est == built


def test_sell_classes_pieces_widths_and_growth():
    lens = np.array([0, 3, 1, 17, 18, 0, 40, 16, 2, 1000, 3, 2 * ROW_BLOCK + 5])
    row, first, classes = sell_classes(lens)
    # Rows become pieces of at most ROW_BLOCK entries; empty rows none.
    assert sorted(row.tolist()) == sorted(np.flatnonzero(lens).tolist() + [11, 11])
    plen = np.minimum(lens[row] - first, ROW_BLOCK)
    assert sorted(first[row == 11].tolist()) == [0, ROW_BLOCK, 2 * ROW_BLOCK]
    assert np.all(np.diff(plen) >= 0) and plen.min() >= 1
    widths = [w for w, _ in classes]
    assert widths[:5] == [1, 2, 3, 5, 16]  # exact classes up to 16
    assert widths[-1] == ROW_BLOCK
    assert sum(r for _, r in classes) == row.size
    done = 0
    for width, rows in classes:
        got = plen[done : done + rows]
        assert got.max() == width and np.all(width <= 1.125 * np.maximum(got, 16))
        done += rows


def _edge_case(name: str) -> CSR:
    rng = np.random.default_rng(4)
    n = 300
    if name == "all_empty":
        return csr_from_coo(np.zeros(0, int), np.zeros(0, int), np.zeros(0), n)
    if name == "empty_rows":
        rows = rng.integers(0, n // 3, 900) * 3  # two rows in three stay empty
        cols = rng.integers(0, n, 900)
    else:  # wide_row: one row of 2,500 entries, wider than ROW_BLOCK
        n = 3000
        rows = np.concatenate([np.full(2500, 7), rng.integers(0, n, 2000)])
        cols = np.concatenate([rng.permutation(n)[:2500], rng.integers(0, n, 2000)])
    vals = rng.standard_normal(rows.size)
    return csr_from_coo(rows, cols, vals, n)


@pytest.mark.parametrize("case", ["empty_rows", "wide_row", "all_empty"])
@pytest.mark.parametrize("acc", [jnp.float32, jnp.float64])
def test_sell_edge_cases(case, acc):
    csr = _edge_case(case)
    x = np.random.default_rng(5).standard_normal(csr.n)
    want = csr.to_scipy() @ np.asarray(jnp.asarray(x, acc), np.float64)
    got, mat = _spmv(csr, acc, acc, x)
    tol = 1e-5 if acc == jnp.float32 else 1e-12
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol * max(1.0, np.abs(want).max()))
    if case == "all_empty":
        assert mat.classes == () and mat.slots == 0 and not got.any()
    if case == "wide_row":  # cut into pieces of ROW_BLOCK entries and a rest
        assert max(w for w, _, _ in mat.classes) == ROW_BLOCK
        assert int((np.asarray(mat.order) == 7).sum()) == 3


def test_sell_long_row_accurate_in_f32():
    """A row of 2^20 entries summed in float32 stays within ``row_sums``'
    accuracy (1e-6 of f64): the layout sums it in pieces of ROW_BLOCK, then
    adds the pieces' partials, as ``row_sums`` sums COO's hub rows."""
    rng = np.random.default_rng(3)
    n, long_len = 1 << 20, 1 << 20
    rows = np.concatenate([np.full(long_len, 4), np.repeat(np.arange(5, 9), 5)])
    cols = np.concatenate([np.arange(long_len), rng.integers(0, n, 20)])
    vals = rng.random(rows.size) / long_len
    csr = csr_from_coo(rows, cols, vals, n)
    x = np.ones(n)
    exact = csr.to_scipy() @ x
    got, _ = _spmv(csr, jnp.float32, jnp.float32, x)
    f32_exact = CSR(
        csr.indptr, csr.indices, csr.data.astype(np.float32).astype(np.float64), csr.shape
    ).to_scipy() @ x
    rel = np.abs(got - f32_exact) / np.maximum(np.abs(exact), 1e-30)
    assert rel[4] < 1e-6
    assert np.max(rel[rows[-20:]]) < 1e-6


def test_compiled_selection_picks_sell_interpret_keeps_todays_choice(family_csr):
    family, csr = family_csr
    stats = matrix_stats(csr)
    assert make_engine(csr, "auto", interpret=False).format == "sell"
    assert choose_format(stats, compiled=True) == "sell"
    assert make_engine(csr, "auto", interpret=True).format == INTERPRET_PICK[family]
    assert choose_format(stats) == INTERPRET_PICK[family]


def test_block_dense_matrix_keeps_bsr_when_compiled():
    rng = np.random.default_rng(0)
    blocks = [rng.random((8, 8)) + 0.1 for _ in range(32)]
    a = sp.block_diag(blocks, format="coo")
    csr = csr_from_coo(a.row, a.col, a.data, a.shape[0])
    assert choose_format(matrix_stats(csr), compiled=True) == "bsr"


@pytest.mark.parametrize("g", [1, 4])
def test_distributed_and_chunked_engines_never_get_sell(family_csr, g):
    _, csr = family_csr
    per_shard = shard_stats(csr, nnz_balanced_splits(csr.indptr, g))
    for allowed in (("ell", "bsr"), DISTRIBUTED_FORMATS, ("coo", "ell")):
        with warnings.catch_warnings():  # kernel-only fallbacks warn
            warnings.simplefilter("ignore")
            before = choose_format(per_shard, allowed)
            got = choose_format(per_shard, allowed, compiled=True)
        assert got == before != "sell"
    chunked = [
        make_engine(csr, "auto", allowed=("coo", "ell"), interpret=i).format
        for i in (False, True)
    ]
    assert chunked[0] == chunked[1] != "sell"
    with pytest.raises(ValueError, match="not supported by this backend"):
        make_engine(csr, "sell", allowed=DISTRIBUTED_FORMATS, interpret=False)


def test_eigsh_over_sell_matches_coo_and_reports_the_layout(family_csr):
    _, csr = family_csr
    kw = dict(k=4, tol=1e-8, subspace=24, policy="DDD", v0=np.ones(csr.n))
    ref = eigsh(csr, format="coo", **kw)
    res = eigsh(csr, format="sell", **kw)
    assert res.spmv_format == "sell"
    np.testing.assert_allclose(res.eigenvalues, ref.eigenvalues, rtol=1e-9)
    spmv = res.partition["spmv"]
    assert spmv["kernels"]["spmv"] == "xla"
    assert spmv["sell"]["slots_per_nnz"] <= 1.125
    assert spmv["sell"]["slots"] == matrix_stats(csr, with_blocks=False).sell_slots


def test_sell_plan_persists_without_a_conversion():
    csr = gap_csr("kron", scale=10)
    cfg = SolverConfig(backend="single", format="sell")
    s1 = EigenSession(csr, cfg)
    s1.warmup()
    state = s1.export_state()
    assert [p["container"] for p in state["plans"]] == ["sell"]
    r1 = s1.eigsh(k=3, num_iters=12)
    s2 = EigenSession(csr, cfg)
    assert s2.import_plans(state) == 1
    conv0 = conversion_count()
    r2 = s2.eigsh(k=3, num_iters=12)
    assert conversion_count() == conv0
    assert r2.spmv_format == "sell"
    np.testing.assert_array_equal(np.asarray(r2.eigenvalues), np.asarray(r1.eigenvalues))


def test_sell_multistart_runs_as_one_vmapped_sweep():
    csr = gap_csr("urand", scale=9)
    sess = prepare(csr, reorth="full", format="sell")
    rs = sess.eigsh_many([{"k": 3, "seed": s, "num_iters": 12} for s in range(3)])
    assert sess.stats["sweeps"] == 1  # one vmapped sweep for all three starts
    for s, r in enumerate(rs):
        ref = eigsh(csr, 3, reorth="full", num_iters=12, seed=s, format="coo")
        np.testing.assert_allclose(
            np.asarray(r.eigenvalues, np.float64), np.asarray(ref.eigenvalues, np.float64),
            rtol=1e-6,
        )
