"""Plan/execute split: prepared sessions, fingerprint cache, eigsh_many."""

import hashlib
import json
import os
import sys
import threading

import numpy as np
import pytest

import jax

from repro.api import (
    EigenResult,
    EigenSession,
    EigQuery,
    SolverConfig,
    config_fingerprint,
    eigsh,
    eigsh_many,
    matrix_fingerprint,
    prepare,
    session_cache_clear,
    session_cache_info,
)
from repro.api import coerce
from repro.api.session import policy_key
from repro.core import FDF, POLICIES
from repro.core.metrics import eigsh_reference
from repro.sparse import generate
from repro.sparse.formats import CSR, conversion_count

K = 4
ITERS = 24


@pytest.fixture(autouse=True)
def _fresh_cache():
    session_cache_clear()
    yield
    session_cache_clear()


@pytest.fixture()
def small_csr():
    return generate("web", 512, 6.0, seed=3, values="normalized")


# ------------------------------------------------------------- fingerprints


def test_matrix_fingerprint_tracks_content(small_csr):
    fp = matrix_fingerprint(small_csr)
    assert fp == matrix_fingerprint(small_csr)  # byte-identical -> same digest
    mutated = generate("web", 512, 6.0, seed=3, values="normalized")
    mutated.data[0] += 1.0
    assert matrix_fingerprint(mutated) != fp
    # dtype change alone changes the digest too
    retyped = generate("web", 512, 6.0, seed=3, values="normalized")
    retyped.data = retyped.data.astype(np.float32)
    assert matrix_fingerprint(retyped) != fp


@pytest.fixture()
def small_chunks(monkeypatch):
    """Hash in 256-byte chunks, so the test CSR's arrays span many."""
    monkeypatch.setattr(coerce, "_FP_CHUNK_BYTES", 256)
    return 256


def _csr_copy(csr, **replace):
    arrays = {f: getattr(csr, f).copy() for f in ("indptr", "indices", "data")}
    arrays.update(replace)
    return CSR(shape=tuple(csr.shape), **arrays)


@pytest.mark.parametrize("where", ["first", "middle", "last"])
@pytest.mark.parametrize("field", ["indptr", "indices", "data"])
def test_fingerprint_sees_one_flipped_byte_in_any_chunk(small_csr, small_chunks, field, where):
    fp = matrix_fingerprint(small_csr)
    raw = getattr(small_csr, field).copy()
    nbytes = raw.nbytes
    chunks = -(-nbytes // small_chunks)
    assert chunks >= 3
    chunk = {"first": 0, "middle": chunks // 2, "last": chunks - 1}[where]
    at = min(chunk * small_chunks + 5, nbytes - 1)
    raw.view(np.uint8)[at] ^= 0x01
    assert matrix_fingerprint(_csr_copy(small_csr, **{field: raw})) != fp
    # ... and the same flip back gives the original digest
    raw.view(np.uint8)[at] ^= 0x01
    assert matrix_fingerprint(_csr_copy(small_csr, **{field: raw})) == fp


@pytest.mark.parametrize("workers", [1, 2, 8])
def test_fingerprint_is_of_content_whatever_the_threads(
    small_csr, small_chunks, monkeypatch, workers
):
    """A byte-identical copy in new arrays, hashed on one thread or many,
    gives the digest of the original hashed on the calling thread alone."""
    monkeypatch.setattr(coerce, "_usable_cpus", lambda: 1)
    want = matrix_fingerprint(small_csr)
    threads = set()
    digest = coerce._chunk_digest

    def spy(chunk):
        threads.add(threading.current_thread().name)
        return digest(chunk)

    monkeypatch.setattr(coerce, "_chunk_digest", spy)
    monkeypatch.setattr(coerce, "_usable_cpus", lambda: workers)
    monkeypatch.setattr(coerce, "_fp_pool", None)
    try:
        got = matrix_fingerprint(_csr_copy(small_csr))
    finally:
        if coerce._fp_pool is not None:
            coerce._fp_pool.shutdown()
    assert got == want and got.startswith("v2-")
    pooled = {t for t in threads if t.startswith("repro-fingerprint")}
    assert (len(pooled) > 0) == (workers > 1)
    assert pooled or threads == {threading.current_thread().name}


def test_concurrent_fingerprints_share_the_pool(small_chunks, monkeypatch):
    """Callers on many threads at once, more than the cores, each get the
    digest their matrix has when hashed alone on the calling thread."""
    mats = [generate("web", 256, 4.0, seed=s, values="normalized") for s in range(6)]
    monkeypatch.setattr(coerce, "_usable_cpus", lambda: 1)
    want = [matrix_fingerprint(m) for m in mats]
    assert len(set(want)) == len(mats)
    monkeypatch.setattr(coerce, "_usable_cpus", lambda: 4)
    monkeypatch.setattr(coerce, "_fp_pool", None)
    callers = 3 * (os.cpu_count() or 1)
    got = [[] for _ in range(callers)]

    def work(i):
        for r in range(5):
            got[i].append(matrix_fingerprint(mats[(i + r) % len(mats)]))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(i,)) for i in range(callers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
        coerce._fp_pool.shutdown()
    for i, digests in enumerate(got):
        assert digests == [want[(i + r) % len(mats)] for r in range(5)]


def test_a_pool_made_in_another_process_is_not_reused(monkeypatch):
    """A forked child inherits the pool but not its threads, and work sent
    to it would wait forever: the child makes a pool of its own."""
    monkeypatch.setattr(coerce, "_fp_pool", None)
    first = coerce._fingerprint_pool()
    try:
        assert coerce._fingerprint_pool() is first
        monkeypatch.setattr(coerce, "_fp_pool_pid", -1)  # as seen from a child
        second = coerce._fingerprint_pool()
        assert second is not first and coerce._fp_pool_pid == os.getpid()
        second.shutdown()
    finally:
        first.shutdown()


@pytest.mark.parametrize(
    "field, dtype",
    [("indptr", np.float64), ("indices", np.float32), ("data", np.int64), ("dense", np.int64)],
)
def test_fingerprint_sees_a_dtype_change_of_the_same_bytes(small_csr, field, dtype):
    if field == "dense":
        dense = small_csr.toarray()
        assert matrix_fingerprint(dense.view(dtype)) != matrix_fingerprint(dense)
        return
    retyped = getattr(small_csr, field).view(dtype)
    assert matrix_fingerprint(_csr_copy(small_csr, **{field: retyped})) != (
        matrix_fingerprint(small_csr)
    )


def _unversioned_digest(csr) -> str:
    """The single-pass digest fingerprints had before they carried a version."""
    h = hashlib.blake2b(digest_size=16)
    h.update(b"csr")
    for arr in (csr.indptr, csr.indices, csr.data):
        h.update(np.ascontiguousarray(arr).tobytes())
    h.update(repr(csr.shape).encode())
    return h.hexdigest()


def test_plans_exported_under_an_unversioned_digest_are_stale(small_csr):
    cfg = SolverConfig(reorth="full", backend="single")
    state = prepare(small_csr, config=cfg).export_state()
    assert state["plans"] and state["matrix_fingerprint"].startswith("v2-")
    old = dict(state, matrix_fingerprint=_unversioned_digest(small_csr))
    with pytest.warns(UserWarning, match="stale persisted session rejected.*matrix_fingerprint"):
        assert EigenSession(small_csr, cfg).import_plans(old) == 0
    assert EigenSession(small_csr, cfg).import_plans(state) >= 1


def test_config_fingerprint_normalizes_policy():
    """Satellite bugfix: a PrecisionPolicy instance and its name must hash
    identically (resolve_policy normalization), and the hash must be stable
    across equal configs."""
    by_name = SolverConfig(policy="FDF")
    by_instance = SolverConfig(policy=FDF)
    assert config_fingerprint(by_name) == config_fingerprint(by_instance)
    assert policy_key("FDF") == policy_key(FDF)
    assert policy_key("fdf") == policy_key(FDF)
    # different dtype triples must not collide
    assert policy_key("FFF") != policy_key("FDF")
    assert config_fingerprint(SolverConfig(format="ell")) != config_fingerprint(
        SolverConfig(format="coo")
    )


def test_policy_instance_hits_name_keyed_session(small_csr):
    """eigsh(policy=<instance>) after eigsh(policy=<name>) must reuse the
    session AND its per-policy operator."""
    eigsh(small_csr, K, policy="FDF", num_iters=ITERS)
    c0 = conversion_count()
    res = eigsh(small_csr, K, policy=FDF, num_iters=ITERS)
    assert res.session_reuse
    assert conversion_count() == c0


# ---------------------------------------------------------- cache semantics


def test_byte_identical_recall_is_zero_conversion(small_csr):
    r1 = eigsh(small_csr, K, policy="FDF", num_iters=ITERS)
    assert not r1.session_reuse
    assert r1.partition["spmv"]["conversions"] >= 1
    c0 = conversion_count()
    r2 = eigsh(small_csr, K, policy="FDF", num_iters=ITERS)
    assert r2.session_reuse
    assert conversion_count() == c0  # zero format conversions
    assert r2.partition["spmv"]["conversions"] == 0
    assert r2.timings["prepare_s"] == 0.0
    np.testing.assert_array_equal(np.asarray(r1.eigenvalues), np.asarray(r2.eigenvalues))


def test_mutation_invalidates_session(small_csr):
    r1 = eigsh(small_csr, K, policy="FDF", num_iters=ITERS)
    small_csr.data[:4] *= 1.5
    r2 = eigsh(small_csr, K, policy="FDF", num_iters=ITERS)
    assert not r2.session_reuse
    assert r2.partition["spmv"]["conversions"] >= 1
    # and the answers legitimately differ (it IS a different matrix)
    assert not np.allclose(np.asarray(r1.eigenvalues), np.asarray(r2.eigenvalues))


def test_cached_session_does_not_alias_caller_buffers(small_csr):
    """Review regression: after caching, mutating the submitted CSR in place
    must not poison plans lazily built later under the ORIGINAL digest —
    a byte-identical re-submission must solve the original matrix."""
    from repro.api.session import get_session

    original = generate("web", 512, 6.0, seed=3, values="normalized")
    r0 = eigsh(small_csr, K, policy="FDF", num_iters=ITERS)  # caches the session
    small_csr.data *= 2.0  # caller mutates their buffer in place
    # Fresh CSR with the original bytes: hits the cached key; a NEW policy
    # (different storage dtype) forces a lazy build inside that session.
    sess, hit = get_session(original, SolverConfig())
    assert hit  # same digest -> the session built from small_csr's buffers
    r1 = sess.eigsh(K, policy="DDD", num_iters=ITERS)
    ref = eigsh(
        generate("web", 512, 6.0, seed=3, values="normalized"),
        K,
        policy="DDD",
        num_iters=ITERS,
        format="coo",
    )
    np.testing.assert_allclose(
        np.asarray(r1.eigenvalues, dtype=np.float64),
        np.asarray(ref.eigenvalues, dtype=np.float64),
        rtol=1e-8,
    )
    assert not np.allclose(
        np.asarray(r1.eigenvalues, dtype=np.float64),
        2.0 * np.asarray(r0.eigenvalues, dtype=np.float64),
    )


def test_layout_config_change_invalidates_session(small_csr):
    eigsh(small_csr, K, policy="FDF", format="coo", num_iters=ITERS)
    c0 = conversion_count()
    r2 = eigsh(small_csr, K, policy="FDF", format="ell", num_iters=ITERS)
    assert not r2.session_reuse
    assert conversion_count() > c0
    # per-query knobs (num_iters / tol / k) must NOT invalidate
    r3 = eigsh(small_csr, K - 1, policy="FDF", format="ell", num_iters=8)
    assert r3.session_reuse
    assert r3.iterations == 8


def test_cache_respects_limit_env(small_csr, monkeypatch):
    monkeypatch.setenv("REPRO_EIGSH_SESSION_CACHE", "0")
    session_cache_clear()
    eigsh(small_csr, K, num_iters=ITERS)
    assert session_cache_info()["size"] == 0
    r = eigsh(small_csr, K, num_iters=ITERS)
    assert not r.session_reuse  # caching disabled -> every call re-prepares


def test_cache_byte_budget_excludes_large_sessions(small_csr, monkeypatch):
    """A matrix bigger than the whole byte budget is served but never pinned
    (the out-of-core sizes the chunked backend targets must not accumulate)."""
    monkeypatch.setenv("REPRO_EIGSH_SESSION_CACHE_MB", "0.01")  # 10 kB budget
    session_cache_clear()
    eigsh(small_csr, K, num_iters=ITERS)  # ~300 kB of CSR arrays
    assert session_cache_info()["size"] == 0
    r = eigsh(small_csr, K, num_iters=ITERS)
    assert not r.session_reuse


def test_dense_inputs_are_cached_too(small_csr):
    dense = small_csr.toarray()
    eigsh(dense, K, num_iters=ITERS)
    r2 = eigsh(dense, K, num_iters=ITERS)
    assert r2.session_reuse
    assert r2.spmv_format == "dense"


# ------------------------------------------------------------ session API


def test_prepared_session_serves_queries(small_csr):
    sess = prepare(small_csr, reorth="full")
    assert sess.prepare_conversions >= 1
    c0 = conversion_count()
    r1 = sess.eigsh(K, num_iters=ITERS)
    r2 = sess.eigsh(K - 2, num_iters=ITERS)
    assert r1.session_reuse and r2.session_reuse
    assert conversion_count() == c0  # both executes: zero conversions
    vals, _ = eigsh_reference(small_csr, K)
    np.testing.assert_allclose(
        np.abs(np.asarray(r1.eigenvalues, dtype=np.float64)), np.abs(vals), rtol=1e-4
    )


def test_session_serves_multiple_policies(small_csr):
    """Different dtype triples build lazily, once each, inside one session."""
    sess = prepare(small_csr)
    sess.eigsh(K, policy="FFF", num_iters=ITERS)
    c0 = conversion_count()
    r = sess.eigsh(K, policy="FFF", num_iters=ITERS)  # same policy: reuse
    assert conversion_count() == c0 and r.session_reuse
    r64 = sess.eigsh(K, policy="DDD", num_iters=ITERS)  # new storage dtype: build
    assert not r64.session_reuse
    assert conversion_count() > c0
    c1 = conversion_count()
    sess.eigsh(K, policy="DDD", num_iters=ITERS)
    assert conversion_count() == c1  # now cached too


# -------------------------------------------------------------- eigsh_many


def test_eigsh_many_slices_match_independent_solves(small_csr):
    queries = [
        {"k": 2, "num_iters": ITERS},
        {"k": K, "num_iters": ITERS},
        {"k": 3, "num_iters": ITERS, "tol": 1e-3},
        EigQuery(k=K, num_iters=ITERS),
    ]
    # backend pinned: under "auto" the tol query would dispatch to the
    # restarted backend (its own group); here tol only defines the flags.
    sess = prepare(small_csr, reorth="full", backend="single")
    rs = sess.eigsh_many(queries)
    assert [r.k for r in rs] == [2, K, 3, K]
    # one shared sweep for the whole fixed-m group
    assert sess.stats["sweeps"] == 1
    ref = eigsh(small_csr, K, reorth="full", num_iters=ITERS)
    for r in rs:
        np.testing.assert_allclose(
            np.asarray(r.eigenvalues, dtype=np.float64),
            np.asarray(ref.eigenvalues, dtype=np.float64)[: r.k],
            rtol=1e-8,
        )
        assert r.eigenvectors.shape == (small_csr.n, r.k)
        assert r.residuals.shape == (r.k,)
        assert r.timings.get("amortized_over") == 4.0
    # per-query tol judged per query
    assert rs[2].tol == 1e-3


def test_eigsh_many_groups_by_policy(small_csr):
    sess = prepare(small_csr, reorth="full")
    rs = sess.eigsh_many(
        [
            {"k": 2, "policy": "FFF", "num_iters": ITERS},
            {"k": 3, "policy": "FDF", "num_iters": ITERS},
            {"k": 2, "policy": "FDF", "num_iters": ITERS},
        ]
    )
    assert sess.stats["sweeps"] == 2  # one per policy group
    assert rs[0].policy == "FFF" and rs[1].policy == "FDF"
    ref = eigsh(small_csr, 3, policy="FDF", reorth="full", num_iters=ITERS)
    np.testing.assert_allclose(
        np.asarray(rs[2].eigenvalues, dtype=np.float64),
        np.asarray(ref.eigenvalues, dtype=np.float64)[:2],
        rtol=1e-8,
    )


def test_eigsh_many_restarted_group(small_csr):
    sess = prepare(small_csr)
    rs = sess.eigsh_many(
        [{"k": 2, "tol": 1e-7, "subspace": 16}, {"k": K, "tol": 1e-6, "subspace": 16}]
    )
    assert all(r.backend == "restarted" for r in rs)
    assert sess.stats["sweeps"] == 1  # merged: one restarted run at k_max
    assert all(r.all_converged for r in rs)
    vals, _ = eigsh_reference(small_csr, K)
    np.testing.assert_allclose(
        np.abs(np.asarray(rs[1].eigenvalues, dtype=np.float64)), np.abs(vals), rtol=1e-5
    )


def test_eigsh_many_vmapped_multistart_dense(small_csr):
    dense = small_csr.toarray()
    sess = prepare(dense, reorth="full")
    rs = sess.eigsh_many([{"k": 3, "seed": s, "num_iters": ITERS} for s in range(3)])
    assert sess.stats["sweeps"] == 1  # one vmapped sweep for all three starts
    for s, r in enumerate(rs):
        ref = eigsh(dense, 3, reorth="full", num_iters=ITERS, seed=s)
        np.testing.assert_allclose(
            np.asarray(r.eigenvalues, dtype=np.float64),
            np.asarray(ref.eigenvalues, dtype=np.float64),
            rtol=1e-6,
        )


def test_module_level_eigsh_many(small_csr):
    rs = eigsh_many(small_csr, [2, K], reorth="full", num_iters=ITERS)
    assert [r.k for r in rs] == [2, K]
    rs2 = eigsh_many(small_csr, [2, K], reorth="full", num_iters=ITERS)
    assert all(r.session_reuse for r in rs2)  # second batch hits the cache


def test_eigsh_many_rejects_bad_query(small_csr):
    sess = prepare(small_csr)
    with pytest.raises(TypeError, match="EigQuery"):
        sess.eigsh_many(["nope"])
    with pytest.raises(ValueError, match="exceeds the operator dimension"):
        sess.eigsh_many([small_csr.n + 1])


# ---------------------------------------------------------- impl deprecation


def test_impl_maps_onto_format_with_deprecation(small_csr):
    with pytest.warns(DeprecationWarning, match="impl= is deprecated"):
        r = eigsh(small_csr, K, impl="ell", num_iters=ITERS, reorth="full")
    assert r.spmv_format == "ell"
    ref = eigsh(small_csr, K, format="ell", num_iters=ITERS, reorth="full")
    np.testing.assert_allclose(
        np.asarray(r.eigenvalues, dtype=np.float64),
        np.asarray(ref.eigenvalues, dtype=np.float64),
        rtol=1e-6,
    )
    with pytest.warns(DeprecationWarning):
        r_bsr = eigsh(small_csr, K, impl="bsr_kernel", num_iters=ITERS)
    assert r_bsr.spmv_format == "bsr"
    with pytest.warns(DeprecationWarning):
        with pytest.raises(ValueError, match="unknown legacy impl"):
            eigsh(small_csr, K, impl="bogus")
    # an explicit format= wins over a deprecated impl=
    with pytest.warns(DeprecationWarning):
        r_fmt = eigsh(small_csr, K, impl="ell", format="coo", num_iters=ITERS)
    assert r_fmt.spmv_format == "coo"
    # impl="coo" is an explicit pin now (impl defaults to None), so it must
    # force the segment-sum path, not fall through to auto-selection
    with pytest.warns(DeprecationWarning):
        r_coo = eigsh(small_csr, K, impl="coo", num_iters=ITERS)
    assert r_coo.spmv_format == "coo"


def test_solver_config_has_no_impl_field():
    assert "impl" not in {f.name for f in __import__("dataclasses").fields(SolverConfig)}


# ------------------------------------------------------------ result dicts


def test_eigenresult_json_roundtrip(small_csr):
    res = eigsh(small_csr, K, policy="FDF", reorth="full", num_iters=ITERS, tol=1e-5)
    payload = json.dumps(res.to_dict())  # must be JSON-serializable as-is
    back = EigenResult.from_dict(json.loads(payload))
    np.testing.assert_allclose(
        np.asarray(back.eigenvalues, dtype=np.float64),
        np.asarray(res.eigenvalues, dtype=np.float64),
        rtol=1e-6,
    )
    np.testing.assert_allclose(
        np.asarray(back.eigenvectors, dtype=np.float64),
        np.asarray(res.eigenvectors, dtype=np.float64),
        rtol=1e-6,
    )
    np.testing.assert_array_equal(back.converged, res.converged)
    np.testing.assert_allclose(back.residuals, res.residuals)
    assert back.backend == res.backend
    assert back.policy == res.policy
    assert back.k == res.k and back.n == res.n
    assert back.tol == res.tol
    assert back.partition["spmv"]["format"] == res.partition["spmv"]["format"]
    assert back.timings["total_s"] == pytest.approx(res.timings["total_s"])
    assert back.session_reuse == res.session_reuse
    # dtypes restored
    assert np.asarray(back.eigenvalues).dtype == np.asarray(res.eigenvalues).dtype


def test_eigenresult_roundtrip_distributed(small_csr):
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()).reshape(len(jax.devices())), ("data",))
    res = eigsh(small_csr, K, mesh=mesh, num_iters=ITERS)
    back = EigenResult.from_dict(json.loads(json.dumps(res.to_dict())))
    assert back.spmv_format == tuple(res.spmv_format)
    assert back.num_devices == res.num_devices
    assert back.partition["num_shards"] == res.partition["num_shards"]


def test_bf16_result_roundtrips(small_csr):
    res = eigsh(small_csr, K, policy="BFF", num_iters=ITERS)
    back = EigenResult.from_dict(json.loads(json.dumps(res.to_dict())))
    assert np.asarray(back.eigenvectors).dtype == np.asarray(res.eigenvectors).dtype


# ----------------------------------------------------------- compat checks


def test_all_policies_still_resolve_through_sessions(small_csr):
    for name in POLICIES:
        r = eigsh(small_csr, 2, policy=name, num_iters=8)
        assert r.eigenvalues.shape == (2,)


def test_prepared_distributed_reuse(small_csr):
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()).reshape(len(jax.devices())), ("data",))
    sess = prepare(small_csr, mesh=mesh)
    c0 = conversion_count()
    r1 = sess.eigsh(K, num_iters=ITERS)
    r2 = sess.eigsh(2, num_iters=8)
    assert r1.backend == r2.backend == "distributed"
    assert conversion_count() == c0
    assert r1.session_reuse and r2.session_reuse
    assert r2.timings.get("convert_s") == 0.0  # plan reused: no conversion paid


def test_chunked_session_reuse(small_csr):
    sess = prepare(small_csr, backend="chunked", chunk_nnz=2048)
    r1 = sess.eigsh(3, num_iters=9)
    c0 = conversion_count()
    r2 = sess.eigsh(3, num_iters=9)
    assert conversion_count() == c0
    assert r1.partition["staging"]["conversions"] == r1.partition["num_chunks"]
    assert r2.partition["staging"]["conversions"] == r2.partition["num_chunks"]
    np.testing.assert_allclose(
        np.asarray(r1.eigenvalues, dtype=np.float64),
        np.asarray(r2.eigenvalues, dtype=np.float64),
    )
