"""The trace reduction gives the numbers of a trace recorded on the chip.

``bench/testdata/kron-s10.xplane.pb.gz``: one traced run of ``kron-s20.fff``
at scale 10 (seed 14, one request of 16 steps) on a TPU v5 lite, recorded
with ``bench/tests/record_trace.py``.  Its numbers below were read by hand
from the trace and are checked here against the reduction, and the busy
time also against a second, independent sweep over the same events."""

import gzip
import os
import types

import jax
import numpy as np
import pytest

from bench import harness, roofline, trace
from bench.gen import generate
from bench.metrics import idle_pct, reorth_ms, spmv_ms, spmv_roofline

DATA = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "testdata")
WINDOW_NS = (45_308_398.0, 256_155_092.0)
BUSY_NS = 4_941_873.0
SPMV = (16, 4_693_706.0)  # executions, device ns of jit__container_spmv
ORTH = (16, 20_846.0)


@pytest.fixture(scope="module")
def profile():
    with gzip.open(os.path.join(DATA, "kron-s10.xplane.pb.gz")) as f:
        return jax.profiler.ProfileData.from_serialized_xspace(f.read())


@pytest.fixture(scope="module")
def reduced(profile):
    return trace.reduce(profile)


def test_window_and_busy_time(reduced):
    assert reduced.window == WINDOW_NS
    assert reduced.busy_ns == [BUSY_NS]
    assert reduced.requests == 1
    assert reduced.window_s == pytest.approx(0.210846694)


def test_busy_time_by_a_counting_sweep(profile):
    """Busy = time with at least one operation running, by +1/-1 events."""
    (dev,) = [p for p in profile.planes if p.name == "/device:TPU:0"]
    (ops,) = [line for line in dev.lines if line.name == "XLA Ops"]
    lo, hi = WINDOW_NS
    iv = np.array([(e.start_ns, e.start_ns + e.duration_ns) for e in ops.events])
    iv = np.clip(iv, lo, hi)
    times = np.concatenate([iv[:, 0], iv[:, 1]])
    steps = np.concatenate([np.ones(len(iv)), -np.ones(len(iv))])
    order = np.lexsort((-steps, times))
    depth = np.cumsum(steps[order])
    busy = np.sum(np.diff(times[order])[depth[:-1] > 0])
    assert busy == pytest.approx(BUSY_NS, abs=1.0)


def test_programs(reduced, profile):
    (dev,) = [p for p in profile.planes if p.name == "/device:TPU:0"]
    (mods,) = [line for line in dev.lines if line.name == "XLA Modules"]
    spmv = [e.duration_ns for e in mods.events if e.name.startswith("jit__container_spmv(")]
    assert (len(spmv), sum(spmv)) == SPMV
    count, seconds = reduced.program("_container_spmv")
    assert (count, seconds) == (SPMV[0], pytest.approx(SPMV[1] * 1e-9))
    count, seconds = reduced.program("jit_orth")
    assert (count, seconds) == (ORTH[0], pytest.approx(ORTH[1] * 1e-9))
    assert reduced.program("no_such_program") is None


def test_idle_gaps_cover_the_idle_time(reduced):
    lo, hi = WINDOW_NS
    assert sum(reduced.idle.values()) == pytest.approx(hi - lo - BUSY_NS)
    assert all(label.startswith("bench.request/") for label in reduced.idle)
    b = reduced.breakdown()
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 10
    assert b["device_ops"][0][0].startswith("jit__container_spmv/")
    assert sum(s for _, s in b["device_ops"]) <= BUSY_NS * 1e-9


def test_metric_readers(reduced):
    cell = harness.load_cell("kron-s20.fff")
    g = generate(cell.config, 14, 10)
    o = types.SimpleNamespace(trace=reduced, cell=cell, nnz=g.nnz, n=g.n, requests=[])
    peaks = roofline.device_peaks("TPU v5 lite")
    spmv_s = SPMV[1] * 1e-9 / SPMV[0]
    assert spmv_ms.read(o, peaks) == pytest.approx(1e3 * spmv_s)
    assert reorth_ms.read(o, peaks) == pytest.approx(1e3 * ORTH[1] * 1e-9 / ORTH[0])
    window_ns = WINDOW_NS[1] - WINDOW_NS[0]
    assert idle_pct.read(o, peaks) == pytest.approx(100 * (1 - BUSY_NS / window_ns))
    least = (g.nnz * 12 + g.n * 4) / 819e9
    assert spmv_roofline.read(o, peaks) == pytest.approx(100 * least / spmv_s)
    untraced = types.SimpleNamespace(trace=None, cell=cell, nnz=g.nnz, n=g.n, requests=[])
    for reader in (spmv_ms, reorth_ms, idle_pct, spmv_roofline):
        assert reader.read(untraced, peaks) is None
