"""SpmvEngine layer: format auto-selection, tiles, and kernel-backed solves.

Property-style coverage of the selector (synthetic block-diagonal -> BSR,
banded -> ELL, power-law -> COO) plus cross-format agreement against the
dense reference SpMV, the shard-local conversions, and the engine-driven
solver paths (single, chunked, and a 1-shard distributed run proving the
hot loop never calls ``segment_sum``).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

from repro.api import eigsh
from repro.core.distributed import solve_sharded
from repro.core.operators import ChunkedOperator, make_operator
from repro.core.partition import nnz_balanced_splits
from repro.kernels.engine import (
    SpmvEngine,
    TileConfig,
    choose_format,
    make_engine,
    matrix_stats,
    select_tiles,
    shard_stats,
)
from repro.sparse import generate
from repro.sparse.formats import (
    CSR,
    shard_to_blocked_ell,
    shard_to_ell,
    to_device_bsr,
)

ACCUM_TOL = {jnp.float32: 2e-5, jnp.float64: 1e-12}


def _csr_from_scipy(m) -> CSR:
    m = m.tocsr()
    m.sort_indices()
    return CSR(
        indptr=m.indptr.astype(np.int64),
        indices=m.indices.astype(np.int32),
        data=m.data.astype(np.float64),
        shape=m.shape,
    )


def block_diagonal_csr(n_blocks: int, bs: int = 8, seed: int = 0) -> CSR:
    """Dense symmetric (bs x bs) blocks on the diagonal: the BSR regime."""
    rng = np.random.default_rng(seed)
    blocks = [rng.random((bs, bs)) + 0.1 for _ in range(n_blocks)]
    a = sp.block_diag(blocks, format="csr")
    return _csr_from_scipy(((a + a.T) / 2).tocsr())


def banded_csr(n: int, bandwidth: int = 2, seed: int = 0) -> CSR:
    """Symmetric banded matrix (near-uniform rows): the ELL regime."""
    rng = np.random.default_rng(seed)
    diags = [rng.random(n - abs(o)) + 0.1 for o in range(-bandwidth, bandwidth + 1)]
    a = sp.diags(diags, range(-bandwidth, bandwidth + 1), format="csr")
    return _csr_from_scipy(((a + a.T) / 2).tocsr())


def powerlaw_csr(n: int = 1024, deg: float = 6.0, seed: int = 0) -> CSR:
    """Heavy-hub web graph (max row >> mean row): the COO regime."""
    return generate("web", n, deg, seed=seed, values="uniform")


# --------------------------- format auto-selection ---------------------------


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_selector_block_diagonal_picks_bsr(seed):
    csr = block_diagonal_csr(32, bs=8, seed=seed)
    stats = matrix_stats(csr, block_size=8)
    assert stats.block_fill > 0.5
    assert choose_format(stats) == "bsr"


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("bandwidth", [1, 3])
def test_selector_banded_picks_ell(seed, bandwidth):
    csr = banded_csr(512, bandwidth=bandwidth, seed=seed)
    stats = matrix_stats(csr)
    assert stats.ell_overhead <= 1.5  # near-uniform rows: padding is cheap
    assert choose_format(stats) == "ell"


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_selector_powerlaw_picks_hybrid(seed):
    """Hub rows blow the plain-ELL bound, but the quantile-capped split
    bounds the padding — power-law matrices now reach the kernel path."""
    csr = powerlaw_csr(seed=seed)
    stats = matrix_stats(csr)
    assert stats.ell_overhead > 3.0  # hub rows make ELL padding explode
    assert stats.hyb_overhead <= 3.0  # ...but the capped split bounds it
    assert 0 < stats.hyb_width < stats.max_row_nnz
    assert choose_format(stats) == "hybrid"


def hub_dense_csr(n: int = 400, hubs: int = 40, seed: int = 0) -> CSR:
    """>5% of rows fully dense: the hybrid quantile cap lands on the hub
    width itself, so even the capped split blows the padding bound."""
    rng = np.random.default_rng(seed)
    a = sp.lil_matrix((n, n))
    a[:hubs, :] = rng.random((hubs, n)) + 0.1
    a = ((a + a.T) / 2).tocsr()
    return _csr_from_scipy(a)


def test_selector_tail_dominated_picks_coo():
    """When >1-quantile of the rows are hubs the cap lands on the hub width
    itself: even the capped split blows the bound and COO wins.  (BSR is
    excluded: contiguous dense hub strips would legitimately pick it.)"""
    stats = matrix_stats(hub_dense_csr())
    assert stats.ell_overhead > 3.0
    assert stats.hyb_overhead > 3.0 or stats.hyb_tail_frac > 0.6
    assert choose_format(stats, allowed=("coo", "ell", "hybrid")) == "coo"


def test_selector_kernel_only_falls_back_to_ell():
    # A kernel-only path without the hybrid split: padding-heavy matrices
    # still get a correct (kernel) format rather than an error — with a
    # warning, since padded ELL on hub matrices costs O(n * max_row_nnz).
    stats = matrix_stats(powerlaw_csr())
    with pytest.warns(UserWarning, match="padding overhead"):
        assert choose_format(stats, allowed=("ell", "bsr")) == "ell"


def test_selector_kernel_only_prefers_hybrid_no_warning():
    """The distributed allow-list now contains the hub split: the power-law
    case that used to warn-and-pad resolves to hybrid silently."""
    import warnings as w

    stats = matrix_stats(powerlaw_csr())
    with w.catch_warnings():
        w.simplefilter("error")
        assert choose_format(stats, allowed=("ell", "bsr", "hybrid")) == "hybrid"


def test_selector_respects_allowed_and_thresholds():
    bd = matrix_stats(block_diagonal_csr(16))
    assert choose_format(bd, allowed=("coo", "ell")) == "ell"  # bsr excluded
    assert choose_format(bd, bsr_fill_factor=1e9) != "bsr"
    pl = matrix_stats(powerlaw_csr())
    assert choose_format(pl, ell_max_overhead=1e9) == "ell"


def test_make_engine_validates_format():
    csr = banded_csr(128)
    with pytest.raises(ValueError, match="unknown SpMV format"):
        make_engine(csr, "ellpack")
    with pytest.raises(ValueError, match="not supported"):
        make_engine(csr, "bsr", allowed=("coo", "ell"))


# ------------------------------- tile table ----------------------------------


def test_tile_table_scales_with_shape():
    small = select_tiles(512, 64, interpret=False)
    large = select_tiles(1 << 20, 4096, interpret=False)
    assert large.block_r >= small.block_r
    assert large.block_w >= small.block_w


def test_tile_table_16bit_sublane_minimum():
    t = select_tiles(512, 64, dtype=jnp.bfloat16, interpret=False)
    assert t.block_r >= 16


def test_tile_env_override(monkeypatch):
    monkeypatch.setenv("REPRO_SPMV_TILES", "64,256,16")
    t = select_tiles(1 << 20, 4096, interpret=False)
    assert t == TileConfig(block_r=64, block_w=256, block_size=16)
    monkeypatch.setenv("REPRO_SPMV_TILES", "not,numbers")
    with pytest.raises(ValueError):
        select_tiles(64, 64)


# --------------------- cross-format SpMV agreement ---------------------------


@pytest.mark.parametrize(
    "make_csr",
    [
        lambda: block_diagonal_csr(24, seed=3),
        lambda: banded_csr(300, bandwidth=2, seed=3),
        lambda: powerlaw_csr(512, seed=3),
    ],
    ids=["blockdiag", "banded", "powerlaw"],
)
@pytest.mark.parametrize("fmt", ["coo", "ell", "bsr", "hybrid", "sell"])
@pytest.mark.parametrize("acc", [jnp.float32, jnp.float64])
def test_all_formats_match_dense_reference(make_csr, fmt, acc):
    csr = make_csr()
    dense = csr.toarray()
    x = np.random.default_rng(5).standard_normal(csr.n)
    engine = make_engine(csr, fmt, accum_dtype=acc)
    op = make_operator(csr, dtype=jnp.float64, engine=engine)
    y = np.asarray(op.matvec(jnp.asarray(x), accum_dtype=acc), dtype=np.float64)
    tol = ACCUM_TOL[acc]
    np.testing.assert_allclose(y, dense @ x, rtol=tol, atol=tol * 10)


def test_engine_spmv_accum_dtype_override():
    csr = banded_csr(256)
    engine = make_engine(csr, "ell", accum_dtype=jnp.float32)
    op = make_operator(csr, dtype=jnp.float32, engine=engine)
    y64 = op.matvec(jnp.ones(csr.n, jnp.float32), accum_dtype=jnp.float64)
    assert y64.dtype == jnp.float64


# ------------------------- shard-local conversions ---------------------------


@pytest.mark.parametrize("g", [2, 4])
def test_shard_to_ell_matches_dense(g):
    csr = powerlaw_csr(700, seed=7)
    dense = csr.toarray()
    x = np.random.default_rng(1).standard_normal(csr.n)
    splits = nnz_balanced_splits(csr.indptr, g)
    n_pad = int((splits[1:] - splits[:-1]).max())
    n_pad = -(-n_pad // 8) * 8
    val, col, stats = shard_to_ell(csr, splits, n_pad, dtype=jnp.float64, row_tile=8)
    assert val.shape[0] == g and stats["width_padded"] % 128 == 0
    xp = np.zeros(g * n_pad)
    for s in range(g):
        lo, hi = int(splits[s]), int(splits[s + 1])
        xp[s * n_pad : s * n_pad + hi - lo] = x[lo:hi]
    y = (np.asarray(val) * xp[np.asarray(col)]).sum(axis=2)
    got = np.concatenate(
        [y[s, : int(splits[s + 1] - splits[s])] for s in range(g)]
    )
    np.testing.assert_allclose(got, dense @ x, atol=1e-10)


@pytest.mark.parametrize("g", [2, 4])
def test_shard_to_blocked_ell_matches_dense(g):
    csr = block_diagonal_csr(40, bs=8, seed=2)
    dense = csr.toarray()
    x = np.random.default_rng(2).standard_normal(csr.n)
    splits = nnz_balanced_splits(csr.indptr, g)
    n_pad = int((splits[1:] - splits[:-1]).max())
    n_pad = -(-n_pad // 8) * 8
    val, bcol, stats = shard_to_blocked_ell(csr, splits, n_pad, block_size=8, dtype=jnp.float64)
    assert val.shape[:2] == (g, n_pad // 8)
    xp = np.zeros(g * n_pad)
    for s in range(g):
        lo, hi = int(splits[s]), int(splits[s + 1])
        xp[s * n_pad : s * n_pad + hi - lo] = x[lo:hi]
    xb = xp.reshape(-1, 8)
    parts = []
    for s in range(g):
        gathered = xb[np.asarray(bcol[s])]  # (nbr, slots, 8)
        ys = np.einsum("rsij,rsj->ri", np.asarray(val[s]), gathered).reshape(-1)
        parts.append(ys[: int(splits[s + 1] - splits[s])])
    np.testing.assert_allclose(np.concatenate(parts), dense @ x, atol=1e-10)


def test_shard_to_blocked_ell_requires_alignment():
    csr = block_diagonal_csr(8)
    splits = nnz_balanced_splits(csr.indptr, 2)
    with pytest.raises(ValueError, match="multiple of block_size"):
        shard_to_blocked_ell(csr, splits, n_pad=33, block_size=8)


def test_to_device_bsr_matches_legacy_tuple():
    from repro.kernels.spmv_bsr import blocked_ell_from_csr

    csr = generate("road", 484, 3.0, seed=11, values="uniform")
    bsr = to_device_bsr(csr, block_size=8, dtype=jnp.float32)
    val, bcol, n = blocked_ell_from_csr(csr, block_size=8, dtype=jnp.float32)
    assert n == bsr.n_rows
    np.testing.assert_array_equal(np.asarray(val), np.asarray(bsr.val))
    np.testing.assert_array_equal(np.asarray(bcol), np.asarray(bsr.bcol))


# --------------------------- solver integration ------------------------------


def test_eigsh_format_auto_surfaces_decision():
    road = generate("road", 900, 3.0, seed=1, values="normalized")
    r = eigsh(road, 3, num_iters=10)
    assert r.spmv_format == "ell"
    r_coo = eigsh(road, 3, num_iters=10, format="coo")
    assert r_coo.spmv_format == "coo"
    np.testing.assert_allclose(
        np.asarray(r.eigenvalues), np.asarray(r_coo.eigenvalues), rtol=1e-4
    )


def test_eigsh_format_bsr_on_block_structure():
    csr = block_diagonal_csr(48, bs=8, seed=4)
    r = eigsh(csr, 3, num_iters=9)
    assert r.spmv_format == "bsr"
    r_coo = eigsh(csr, 3, num_iters=9, format="coo")
    np.testing.assert_allclose(
        np.asarray(r.eigenvalues), np.asarray(r_coo.eigenvalues), rtol=1e-4
    )


def test_eigsh_format_validation():
    road = generate("road", 256, 3.0, seed=1, values="normalized")
    with pytest.raises(ValueError, match="unknown SpMV format"):
        eigsh(road, 2, format="ellpack")


def test_chunked_ell_staging_matches_coo():
    road = generate("road", 900, 3.0, seed=2, values="normalized")
    r_ell = eigsh(road, 3, backend="chunked", num_iters=9, chunk_nnz=800, format="ell")
    assert r_ell.spmv_format == "ell"
    r_coo = eigsh(road, 3, backend="chunked", num_iters=9, chunk_nnz=800, format="coo")
    np.testing.assert_allclose(
        np.asarray(r_ell.eigenvalues), np.asarray(r_coo.eigenvalues), rtol=1e-5
    )


def test_chunked_auto_guards_padded_memory():
    """The chunked backend exists under memory pressure: auto must not stage
    a padded ELL that dwarfs the COO triplets (width is 128-aligned, so very
    narrow rows lose), but keeps ELL when rows are wide enough to amortize."""
    narrow = generate("road", 900, 3.0, seed=2, values="normalized")  # ~5 nnz/row
    r_n = eigsh(narrow, 3, backend="chunked", num_iters=9, chunk_nnz=800)
    assert r_n.spmv_format == "coo"
    wide = banded_csr(400, bandwidth=30, seed=5)  # ~61 nnz/row: padding amortized
    r_w = eigsh(wide, 3, backend="chunked", num_iters=9, chunk_nnz=6000)
    assert r_w.spmv_format == "ell"


def test_chunked_rejects_bsr():
    csr = block_diagonal_csr(16)
    with pytest.raises(ValueError, match="not supported"):
        eigsh(csr, 2, backend="chunked", format="bsr")
    engine = make_engine(csr, "bsr")
    with pytest.raises(ValueError, match="per-chunk BSR"):
        ChunkedOperator(csr, engine=engine)


def test_chunked_ell_many_small_chunks_reference():
    csr = banded_csr(500, bandwidth=2, seed=9)
    engine = make_engine(csr, "ell", accum_dtype=jnp.float64)
    op = ChunkedOperator(csr, chunk_nnz=64, dtype=jnp.float64, engine=engine)
    assert op.num_chunks > 5
    x = np.random.default_rng(3).standard_normal(csr.n)
    y = np.asarray(op.matvec(jnp.asarray(x), accum_dtype=jnp.float64))
    np.testing.assert_allclose(y, csr.toarray() @ x, atol=1e-10)


def test_distributed_hot_loop_never_calls_segment_sum(monkeypatch):
    """1-shard distributed solve with segment_sum poisoned: the auto-selected
    kernel path (ELL here) must not touch the COO reference reduction."""
    from jax.sharding import Mesh

    road = generate("road", 400, 3.0, seed=3, values="normalized")
    baseline = solve_sharded(
        road, 3, Mesh(np.array(jax.devices()[:1]), ("data",)),
        num_iters=9, seed=1, spmv_format="coo",
    )

    def _poisoned(*a, **k):
        raise AssertionError("segment_sum reached the distributed hot loop")

    monkeypatch.setattr(jax.ops, "segment_sum", _poisoned)
    out = solve_sharded(
        road, 3, Mesh(np.array(jax.devices()[:1]), ("data",)),
        num_iters=9, seed=1, spmv_format="auto",
    )
    assert out.spmv_format == ("ell",)
    assert out.partition["spmv"]["format"] == "ell"
    np.testing.assert_allclose(
        np.asarray(out.eigenvalues), np.asarray(baseline.eigenvalues), rtol=1e-4
    )


def test_engine_is_jit_static():
    """SpmvEngine must be hashable/frozen so it can ride static jit args."""
    csr = banded_csr(128)
    e1 = make_engine(csr, "ell")
    e2 = dataclasses.replace(e1, accum_dtype=jnp.float64)
    assert hash(e1) != hash(e2) or e1 != e2
    assert isinstance(e1, SpmvEngine)


def test_forced_format_skips_block_census():
    """Explicit COO/ELL never pays the O(nnz log nnz) block-key sort."""
    csr = banded_csr(256)
    e = make_engine(csr, "ell")
    assert e.stats[0].n_blocks == 0  # census skipped
    assert make_engine(csr, "auto").stats[0].n_blocks > 0


# ------------------------------ hybrid format --------------------------------


def test_hybrid_container_bounds_padding():
    """Acceptance: on a hub-heavy matrix the built hybrid layout keeps
    padded-slots/nnz within the ELL bound plain ELL blew."""
    from repro.kernels.engine import ELL_MAX_OVERHEAD
    from repro.sparse.formats import to_device_hybrid

    csr = powerlaw_csr(seed=0)
    hyb = to_device_hybrid(csr, dtype=jnp.float64)
    ell_part_slots = hyb.ell_val.shape[0] * hyb.ell_val.shape[1]
    stored = ell_part_slots + hyb.tail_slots
    assert stored / csr.nnz <= ELL_MAX_OVERHEAD
    # and the plain-ELL layout would NOT have been bounded
    assert matrix_stats(csr).ell_overhead > ELL_MAX_OVERHEAD
    x = np.random.default_rng(0).standard_normal(csr.n)
    y = np.asarray(hyb.matvec(jnp.asarray(x), accum_dtype=jnp.float64))
    np.testing.assert_allclose(y, csr.toarray() @ x, atol=1e-10)


def test_eigsh_powerlaw_auto_runs_hybrid_kernel_path():
    """format="auto" on a hub matrix now reports 'hybrid' and matches the
    COO baseline (single-device)."""
    csr = powerlaw_csr(seed=1)
    r = eigsh(csr, 3, num_iters=10, seed=2)
    assert r.spmv_format == "hybrid"
    r_coo = eigsh(csr, 3, num_iters=10, seed=2, format="coo")
    np.testing.assert_allclose(
        np.asarray(r.eigenvalues), np.asarray(r_coo.eigenvalues), rtol=1e-4
    )


@pytest.mark.parametrize("g", [2, 4])
def test_shard_to_hybrid_matches_dense(g):
    from repro.sparse.formats import shard_to_hybrid

    csr = powerlaw_csr(700, seed=7)
    dense = csr.toarray()
    x = np.random.default_rng(1).standard_normal(csr.n)
    splits = nnz_balanced_splits(csr.indptr, g)
    n_pad = int((splits[1:] - splits[:-1]).max())
    n_pad = -(-n_pad // 8) * 8
    mats, stats = shard_to_hybrid(csr, splits, n_pad, dtype=jnp.float64, row_tile=8)
    val, col, trow, tcol, tval = (np.asarray(m) for m in mats)
    assert val.shape[0] == g and stats["tail_nnz"] > 0
    # realized padded-slots/nnz of the split stays bounded
    assert (val.size + stats["tail_nnz"]) / csr.nnz <= 3.0 * 2  # rows_pad inflation
    xp = np.zeros(g * n_pad)
    for s in range(g):
        lo, hi = int(splits[s]), int(splits[s + 1])
        xp[s * n_pad : s * n_pad + hi - lo] = x[lo:hi]
    got_parts = []
    for s in range(g):
        y = (val[s] * xp[col[s]]).sum(axis=1)
        np.add.at(y, trow[s], tval[s] * xp[tcol[s]])
        got_parts.append(y[: int(splits[s + 1] - splits[s])])
    np.testing.assert_allclose(np.concatenate(got_parts), dense @ x, atol=1e-10)


def test_distributed_powerlaw_auto_selects_hybrid():
    """Acceptance: the matrix class that used to trigger the padding-blowup
    warning on the kernel-only distributed path now runs hybrid, silently,
    and matches an independent COO baseline."""
    import warnings as w

    from jax.sharding import Mesh

    csr = powerlaw_csr(700, seed=7)
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    baseline = solve_sharded(csr, 3, mesh, num_iters=9, seed=1, spmv_format="coo")
    with w.catch_warnings():
        w.simplefilter("error")
        out = solve_sharded(csr, 3, mesh, num_iters=9, seed=1, spmv_format="auto")
    assert out.spmv_format == ("hybrid",)
    assert out.partition["spmv"]["format"] == "hybrid"
    assert out.partition["spmv"]["tail_nnz"] > 0
    np.testing.assert_allclose(
        np.asarray(out.eigenvalues), np.asarray(baseline.eigenvalues), rtol=1e-4
    )


def test_chunked_rejects_hybrid():
    csr = powerlaw_csr(512, seed=3)
    engine = make_engine(csr, "hybrid")
    with pytest.raises(ValueError, match="per-chunk HYBRID"):
        ChunkedOperator(csr, engine=engine)


# ------------------------------ tile autotuner -------------------------------


@pytest.fixture
def tune_env(tmp_path, monkeypatch):
    """Isolated tuner: fresh JSON cache path + enabled tuning."""
    import repro.kernels.engine as eng_mod

    cache = tmp_path / "spmv_tune.json"
    monkeypatch.setenv("REPRO_SPMV_TUNE_CACHE", str(cache))
    monkeypatch.setenv("REPRO_SPMV_TUNE", "1")
    monkeypatch.setenv("REPRO_SPMV_TUNE_BUDGET", "2")
    eng_mod._TUNER = None
    yield cache
    eng_mod._TUNER = None


def test_autotuner_disabled_is_static_table(monkeypatch):
    """Cold start with tuning off: behavior identical to the static table
    (interpret-mode large tiles preserved), provenance 'table'."""
    monkeypatch.delenv("REPRO_SPMV_TUNE", raising=False)
    csr = banded_csr(256)
    e = make_engine(csr, "ell")
    assert e.tiles_from == "table"
    assert e.tiles == TileConfig(block_r=512, block_w=2048)  # interpret tiles
    assert e.describe()["tiles_from"] == "table"


def test_autotuner_tunes_caches_and_persists(tune_env):
    import json

    import repro.kernels.engine as eng_mod

    csr = banded_csr(256)
    e1 = make_engine(csr, "ell")
    tuner = eng_mod.get_tuner()
    assert e1.tiles_from == "tuned"
    # two probe passes: SpMV tiles + the whole-iteration plan
    assert tuner.measure_count == 2
    assert tune_env.exists()
    payload = json.loads(tune_env.read_text())
    assert payload["version"] == 2 and len(payload["entries"]) == 2
    rec = next(r for r in payload["entries"].values() if r.get("kind") != "iteration")
    assert rec["block_r"] == e1.tiles.block_r and rec["block_w"] == e1.tiles.block_w
    assert rec["grid"] == eng_mod.grid_fingerprint()
    # same shape bucket: memoized, no second measurement
    e2 = make_engine(csr, "ell")
    assert tuner.measure_count == 2 and e2.tiles == e1.tiles


def test_autotuner_frozen_cache_is_deterministic(tune_env, monkeypatch):
    """A pre-written cache is authoritative: no measurement runs (probes are
    poisoned) and the pinned tiles come back verbatim."""
    import json

    import repro.kernels.engine as eng_mod

    # width is the *layout* width the engine probes: banded max_row 5 pads
    # to the 128-lane ELL tile.  Entries carry the live grid fingerprint —
    # unstamped or stale entries are (correctly) dropped and re-measured.
    key = eng_mod._tune_key("ell", jnp.float32, 256, 128, interpret=True)
    fp = eng_mod.grid_fingerprint()
    tune_env.write_text(
        json.dumps(
            {
                "version": 2,
                "entries": {
                    key: {"block_r": 128, "block_w": 1024, "grid": fp},
                    "iter|" + key: {
                        "kind": "iteration",
                        "update": "unfused",
                        "block_r": 128,
                        "block_w": 1024,
                        "block_size": 8,
                        "grid": fp,
                    },
                },
            }
        )
    )

    def _poisoned(*a, **k):
        raise AssertionError("a frozen tune cache must not re-measure")

    monkeypatch.setattr(eng_mod, "_measure_ell_tiles", _poisoned)
    monkeypatch.setattr(eng_mod, "_measure_iteration", _poisoned)
    e = make_engine(banded_csr(256), "ell")
    assert e.tiles_from == "tuned"
    assert (e.tiles.block_r, e.tiles.block_w) == (128, 1024)
    assert e.iteration_plan.update == "unfused" and e.iteration_plan.source == "tuned"


def test_autotuner_override_wins(tune_env, monkeypatch):
    monkeypatch.setenv("REPRO_SPMV_TILES", "64,256")
    e = make_engine(banded_csr(256), "ell")
    assert e.tiles_from == "override"
    assert (e.tiles.block_r, e.tiles.block_w) == (64, 256)


def test_autotuner_solve_end_to_end(tune_env):
    """A tuned engine still solves correctly and surfaces provenance."""
    road = generate("road", 400, 3.0, seed=3, values="normalized")
    r_t = eigsh(road, 3, num_iters=9, format="ell")
    assert r_t.spmv_format == "ell"
    r_ref = eigsh(road, 3, num_iters=9, format="coo")
    np.testing.assert_allclose(
        np.asarray(r_t.eigenvalues), np.asarray(r_ref.eigenvalues), rtol=1e-4
    )


# ------------------------- chunked double buffering --------------------------


def test_chunked_stages_each_chunk_once_per_instance():
    """Acceptance: host->device *conversion* happens once per chunk lifetime
    (lazily, on the first sweep — nothing is pre-pinned at construction),
    never per matvec; repeat matvecs are pure transfers."""
    road = generate("road", 900, 3.0, seed=2, values="normalized")
    engine = make_engine(road, "ell", accum_dtype=jnp.float64)
    op = ChunkedOperator(road, chunk_nnz=800, dtype=jnp.float64, engine=engine)
    assert op.num_chunks > 1
    assert op.staging["conversions"] == 0  # lazy: construction stages nothing
    x = jnp.asarray(np.random.default_rng(0).standard_normal(road.n))
    for _ in range(3):
        op.matvec(x, accum_dtype=jnp.float64).block_until_ready()
    assert op.staging["conversions"] == op.num_chunks  # first sweep only
    assert op.staging["transfers"] == 3 * op.num_chunks


@pytest.mark.parametrize("depth", [0, 1, 2])
def test_chunked_residency_bounded_by_stage_depth(depth):
    road = generate("road", 900, 3.0, seed=2, values="normalized")
    engine = make_engine(road, "ell", accum_dtype=jnp.float64)
    op = ChunkedOperator(
        road, chunk_nnz=500, dtype=jnp.float64, engine=engine, stage_depth=depth
    )
    x = jnp.asarray(np.random.default_rng(0).standard_normal(road.n))
    y = np.asarray(op.matvec(x, accum_dtype=jnp.float64))
    assert op.staging["max_resident"] <= depth + 1
    np.testing.assert_allclose(y, road.toarray() @ np.asarray(x), atol=1e-10)


def test_chunked_per_chunk_widths_cut_hub_padding():
    """Satellite bugfix: one hub row no longer inflates every chunk's ELL
    width — total padded slots drop vs the old global-width layout."""
    web = powerlaw_csr(512, seed=3)
    engine = make_engine(web, "ell", accum_dtype=jnp.float32)
    op = ChunkedOperator(web, chunk_nnz=400, dtype=jnp.float32, engine=engine)
    assert op.num_chunks > 2
    rows_pad = op._rows_pads[0]
    global_width = -(-int(web.row_nnz().max()) // 128) * 128
    global_slots = op.num_chunks * rows_pad * global_width
    assert op.padded_slots < global_slots
    assert len(set(op._widths)) > 1  # hub chunk is wide, the rest stay narrow
    x = np.random.default_rng(5).standard_normal(web.n)
    y = np.asarray(op.matvec(jnp.asarray(x, jnp.float64), accum_dtype=jnp.float64))
    np.testing.assert_allclose(y, web.toarray() @ x, rtol=1e-5, atol=1e-5)


def test_chunked_auto_judges_ell_on_per_chunk_layout():
    """The chunked selector judges ELL on the *realized per-chunk* padding:
    a hub matrix the global-max-row criterion would veto (16x overhead)
    reaches the kernel path once the chunking isolates the hub row."""
    rng = np.random.default_rng(5)
    n = 1000
    diags = [rng.random(n - abs(o)) + 0.1 for o in range(-30, 31)]
    a = sp.diags(diags, range(-30, 31), format="lil")
    a[0, :] = rng.random(n) + 0.1  # one hub row
    hub = _csr_from_scipy(((a + a.T) / 2).tocsr())
    assert matrix_stats(hub).ell_overhead > 10  # whole-matrix view says no
    r = eigsh(hub, 3, backend="chunked", num_iters=9, chunk_nnz=2000)
    assert r.spmv_format == "ell"  # per-chunk view: hub pays for its chunk only
    r_coo = eigsh(hub, 3, backend="chunked", num_iters=9, chunk_nnz=2000, format="coo")
    np.testing.assert_allclose(
        np.asarray(r.eigenvalues), np.asarray(r_coo.eigenvalues), rtol=1e-5
    )


def test_chunked_eigsh_surfaces_staging_stats():
    road = generate("road", 900, 3.0, seed=2, values="normalized")
    r = eigsh(road, 3, backend="chunked", num_iters=9, chunk_nnz=800, stage_depth=2)
    part = r.partition
    assert part is not None and part["stage_depth"] == 2
    st = part["staging"]
    assert st["conversions"] == part["num_chunks"]
    assert st["max_resident"] <= 3
    assert st["transfers"] >= part["num_chunks"]  # one stream per iteration
    assert part["spmv"]["format"] == r.spmv_format


def test_shard_stats_use_remapped_block_coordinates():
    """Block fill must describe the layout ``shard_to_blocked_ell`` builds
    (columns remapped to ``owner * n_pad + local``), not global coordinates:
    a non-block-aligned split genuinely shears the dense blocks of the second
    shard, and the selector must see that and avoid BSR there."""
    csr = block_diagonal_csr(32, bs=8, seed=1)
    aligned = shard_stats(csr, np.array([0, 96, csr.n], dtype=np.int64), block_size=8)
    assert min(s.block_fill for s in aligned) == pytest.approx(1.0)
    assert choose_format(aligned) == "bsr"
    unaligned = shard_stats(csr, np.array([0, 100, csr.n], dtype=np.int64), block_size=8)
    # Shard 1's local coordinates are shifted by 100 (== 4 mod 8): every
    # dense block straddles four local blocks, so the realized fill drops
    # well below the BSR crossover and the selector must fall back.
    assert min(s.block_fill for s in unaligned) < 0.5
    assert choose_format(unaligned) != "bsr"


# --------------------------------------- what runs each phase, as reported


@pytest.mark.parametrize(
    "fmt, interpret, update, compute, want",
    [
        # compiled (TPU): the SpMV is an XLA gather, the update a Mosaic kernel
        ("ell", False, "fused", jnp.float32, {"spmv": "xla", "update": "mosaic"}),
        ("hybrid", False, "fused_spmv", jnp.float32, {"spmv": "xla", "update": "mosaic"}),
        ("coo", False, "fused", jnp.float32, {"spmv": "xla", "update": "mosaic"}),
        ("bsr", False, "unfused", jnp.float32, {"spmv": "xla", "update": "xla"}),
        ("ell", False, "fused", jnp.float64, {"spmv": "xla", "update": "xla"}),
        # interpreter (CPU): every kernel runs interpreted
        ("ell", True, "fused", jnp.float32,
         {"spmv": "pallas_interpret", "update": "pallas_interpret"}),
        ("coo", True, "unfused", jnp.float32, {"spmv": "xla", "update": "xla"}),
    ],
)
def test_phase_executors_name_the_mosaic_xla_split(fmt, interpret, update, compute, want):
    from repro.kernels.engine import phase_executors

    assert phase_executors(fmt, interpret, update, compute) == want


@pytest.mark.parametrize("interpret", [False, True])
@pytest.mark.parametrize("gather", ["mosaic", "pallas_interpret", "xla", None])
def test_phase_executors_report_what_gathered_sells_x(interpret, gather):
    """``"sell"``'s SpMV is what gathered its ``x`` (its kernel or XLA's
    gather), whatever the mode; not given, it reads ``"xla"``."""
    from repro.kernels.engine import phase_executors

    got = phase_executors("sell", interpret, "unfused", jnp.float32, gather=gather)
    assert got == {"spmv": gather or "xla", "update": "xla"}


@pytest.mark.parametrize(
    "pin, tol, want_update",
    [("unfused", None, "xla"), ("fused", None, "pallas_interpret"), ("fused", 1e-4, "xla")],
)
def test_partition_reports_phase_executors(norm_csr, monkeypatch, pin, tol, want_update):
    """partition["spmv"]["kernels"] reports what ran; the restarted engine
    (any tol) runs its own jnp update whatever the plan says."""
    from repro.api import session_cache_clear

    monkeypatch.setenv("REPRO_ITER_UPDATE", pin)
    session_cache_clear()
    r = eigsh(norm_csr, 4, format="ell", num_iters=24, tol=tol, policy="FFF", seed=1)
    session_cache_clear()
    assert r.partition["spmv"]["kernels"] == {"spmv": "pallas_interpret", "update": want_update}
