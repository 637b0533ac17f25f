"""The host spans of ``repro.tracing``, read back from a profiler trace.

Each case runs ``eigsh`` twice on one matrix under ``jax.profiler.trace``
(a session-cache miss, then a hit) and reads the ``repro.*`` events of the
host plane: their counts, nesting and stats.  A third, untraced call checks
that tracing leaves the answer bit for bit as it was.
"""

import dataclasses

import jax
import numpy as np
import pytest

from repro import eigsh
from repro.api import session_cache_clear
from repro.sparse import generate
from repro.tracing import SPANS

N = 1024
K = 8
M = 16
# (num_iters, tol): one cycle of 16 steps, as in the benchmark's requests;
# and three cycles (16 + 8 + 8 steps) under a tol no cycle meets.
SOLVES = {"one_cycle": (16, None), "three_cycles": (32, 1e-12)}


@dataclasses.dataclass
class Span:
    name: str
    start: float
    end: float
    stats: dict
    thread: tuple

    def inside(self, other: "Span") -> bool:
        return (
            self.thread == other.thread and other.start <= self.start and self.end <= other.end
        )


def _host_spans(path) -> list:
    profile = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for j, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("repro."):
                    out.append(
                        Span(e.name, e.start_ns, e.end_ns, dict(e.stats), (plane.name, j))
                    )
    return sorted(out, key=lambda s: s.start)


@pytest.fixture(scope="module", params=sorted(SOLVES))
def traced(request, tmp_path_factory):
    num_iters, tol = SOLVES[request.param]
    csr = generate("web", N, 8.0, seed=13, values="normalized")
    kw = dict(policy="FFF", subspace=M, num_iters=num_iters, tol=tol, backend="restarted")
    session_cache_clear()
    tdir = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(tdir)):
        results = [eigsh(csr, K, **kw) for _ in range(2)]
    untraced = eigsh(csr, K, **kw)
    session_cache_clear()
    (path,) = list(tdir.rglob("*.xplane.pb"))
    spans = _host_spans(str(path))
    calls = [s for s in spans if s.name == "repro.eigsh"]
    per_call = [[s for s in spans if s.inside(c)] for c in calls]
    return dict(
        csr=csr, results=results, untraced=untraced, spans=spans, calls=calls,
        per_call=per_call, num_iters=num_iters,
    )


def _named(spans, name):
    return [s for s in spans if s.name == name]


def test_one_eigsh_span_per_call(traced):
    calls = traced["calls"]
    assert len(calls) == 2
    assert len({c.stats["request"] for c in calls}) == 2
    assert calls[0].stats["request"] < calls[1].stats["request"]
    assert all(c.stats["k"] == K and c.stats["policy"] == "FFF" for c in calls)


def test_every_span_lies_inside_its_eigsh(traced):
    """Each ``repro.*`` span nests in exactly one call's ``repro.eigsh``."""
    inner = [s for s in traced["spans"] if s.name != "repro.eigsh"]
    assert inner
    for s in inner:
        assert sum(s.inside(c) for c in traced["calls"]) == 1, s


@pytest.mark.parametrize("call", [0, 1])
def test_lanczos_steps_nest_in_the_engine(traced, call):
    spans = traced["per_call"][call]
    (engine,) = _named(spans, "repro.engine.restarted")
    restarts = (traced["num_iters"] - M) // (M - K)
    assert engine.stats == {"m": M, "k": K, "max_restarts": restarts + 1}
    steps = _named(spans, "repro.lanczos.step")
    assert len(steps) == traced["num_iters"] == traced["results"][call].iterations
    assert all(s.inside(engine) for s in steps)
    cycles = sorted({s.stats["cycle"] for s in steps})
    want_i = list(range(M)) + [i for _ in cycles[1:] for i in range(K, M)]
    assert [s.stats["i"] for s in steps] == want_i
    assert sum(s.stats["host_reads"] for s in steps) == 2 * len(steps)
    jacobi = _named(spans, "repro.restarted.jacobi")
    assert len(jacobi) == len(cycles) == traced["results"][call].restarts + 1
    assert all(j.stats == {"m": M} and j.inside(engine) for j in jacobi)
    # one compression per restart, then the final projection
    ritz = _named(spans, "repro.restarted.ritz")
    assert len(ritz) == len(cycles) and all(r.inside(engine) for r in ritz)
    (finish,) = _named(spans, "repro.session.finish")
    assert finish.start >= engine.end


@pytest.mark.parametrize("call, hit", [(0, 0), (1, 1)])
def test_fingerprint_inside_session_get(traced, call, hit):
    spans = traced["per_call"][call]
    (get,) = _named(spans, "repro.session.get")
    assert get.stats == {"hit": hit}
    (fp,) = _named(spans, "repro.session.fingerprint")
    assert fp.inside(get)
    csr = traced["csr"]
    assert fp.stats == {"bytes": csr.indptr.nbytes + csr.indices.nbytes + csr.data.nbytes}


def test_every_documented_name_is_emitted(traced):
    assert {s.name for s in traced["spans"]} == set(SPANS)


@pytest.mark.parametrize("call", [0, 1])
def test_tracing_leaves_the_answer_unchanged(traced, call):
    got, want = traced["results"][call], traced["untraced"]
    np.testing.assert_array_equal(np.asarray(got.eigenvalues), np.asarray(want.eigenvalues))
    np.testing.assert_array_equal(np.asarray(got.eigenvectors), np.asarray(want.eigenvectors))
    np.testing.assert_array_equal(got.residuals, want.residuals)
