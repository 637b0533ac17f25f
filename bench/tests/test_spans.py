"""The span reduction (``bench/spans.py``) on recorded traces.

* ``kron-s10.xplane.pb.gz`` was recorded before the program had spans: it
  reduces to none, and its idle time is the window less the busy time that
  ``test_trace.py`` pins.
* A run traced on the CPU here has the spans but no device plane: counts,
  stats and host time, and no idle time.
* ``kron-s10-spans.xplane.pb.gz``: one traced run of ``kron-s20.fff`` at
  scale 10 (seed 14, one request of 16 steps) on a TPU v5 lite, recorded
  with ``bench/tests/record_trace.py``.  Its idle time inside the steps is
  checked against an independent +1/-1 sweep, and every SpMV the chip ran
  against the host's step spans: the two share one clock.
"""

import gzip
import os
import time

import jax
import numpy as np
import pytest

from bench import harness, spans, trace

DATA = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "testdata")
NAMES = (
    "repro.eigsh",
    "repro.session.get",
    "repro.session.fingerprint",
    "repro.engine.restarted",
    "repro.lanczos.step",
    "repro.restarted.jacobi",
    "repro.restarted.ritz",
    "repro.session.finish",
)
STEPS = 16
# kron-s10-spans: chip 0's idle ns inside the 16 steps, and the CSR bytes
# the one request hashed (read from the trace by the sweep below)
STEP_IDLE_NS = 136_057_509.0
FINGERPRINT_BYTES = 261_712


def _load(name):
    with gzip.open(os.path.join(DATA, name)) as f:
        return jax.profiler.ProfileData.from_serialized_xspace(f.read())


@pytest.fixture(scope="module")
def old_profile():
    return _load("kron-s10.xplane.pb.gz")


@pytest.fixture(scope="module")
def chip_profile():
    return _load("kron-s10-spans.xplane.pb.gz")


@pytest.fixture(scope="module")
def cpu_run(tmp_path_factory):
    cell = harness.load_cell("kron-s20.fff")
    tdir = str(tmp_path_factory.mktemp("trace"))
    o = harness.run(cell, 2**31 + 29, 0.3, True, t_start=time.perf_counter(), scale=10,
                    trace_dir=tdir)
    return o, trace.load(trace.find_xplane(tdir))


def test_a_trace_without_spans(old_profile):
    assert spans.reduce(old_profile) == {}
    reduced = trace.reduce(old_profile)
    idle = spans.idle_split(old_profile)
    assert idle["total_ns"] == pytest.approx(sum(reduced.idle.values()))
    assert idle["outside_spans_ns"] == idle["total_ns"]
    assert spans.per_request({}) == {}


def _check_counts(s, requests):
    assert set(s) == set(NAMES)
    for name in NAMES:
        want = STEPS * requests if name == "repro.lanczos.step" else requests
        assert s[name]["count"] == want, name
        assert s[name]["host_ns"] > 0
    assert s["repro.session.get"]["stats"] == {"hit": requests}
    step = s["repro.lanczos.step"]["stats"]
    assert step == {
        "i": requests * sum(range(STEPS)), "cycle": 0, "host_reads": 2 * STEPS * requests
    }
    assert s["repro.restarted.jacobi"]["stats"] == {"m": STEPS * requests}
    # the spans nest: a child's host time fits in its parent's
    assert s["repro.lanczos.step"]["host_ns"] < s["repro.engine.restarted"]["host_ns"]
    assert s["repro.engine.restarted"]["host_ns"] < s["repro.eigsh"]["host_ns"]
    assert s["repro.session.fingerprint"]["host_ns"] < s["repro.session.get"]["host_ns"]


def test_a_cpu_trace_has_spans_and_no_idle_time(cpu_run):
    o, profile = cpu_run
    s = spans.reduce(profile)
    requests = len(o.requests)
    assert requests >= 1
    _check_counts(s, requests)
    csr_bytes = 8 * (o.n + 1) + (4 + 8) * o.nnz  # int64 indptr, int32 indices, f64 data
    assert s["repro.session.fingerprint"]["stats"] == {"bytes": requests * csr_bytes}
    assert all(rec["idle_ns"] == 0.0 for rec in s.values())
    per = spans.per_request(s)
    assert per == {
        "fingerprint_ms": 1e-6 * s["repro.session.fingerprint"]["host_ns"] / requests,
        "step_idle_ms": 0.0,
        "jacobi_ms": 1e-6 * s["repro.restarted.jacobi"]["host_ns"] / requests,
    }


def _device_line(profile, name):
    (dev,) = [p for p in profile.planes if p.name == "/device:TPU:0"]
    (line,) = [ln for ln in dev.lines if ln.name == name]
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events]


def _host_spans(profile, name):
    line, _, _ = spans._harness_line(profile)
    return [(e.start_ns, e.start_ns + e.duration_ns) for e in line.events if e.name == name]


def test_chip_trace_counts(chip_profile):
    s = spans.reduce(chip_profile)
    _check_counts(s, 1)
    assert s["repro.session.fingerprint"]["stats"] == {"bytes": FINGERPRINT_BYTES}
    idle = spans.idle_split(chip_profile)
    assert s["repro.eigsh"]["idle_ns"] >= 0.9 * idle["total_ns"]
    assert 0 < s["repro.lanczos.step"]["idle_ns"] < s["repro.eigsh"]["idle_ns"]


def test_chip_step_idle_by_a_counting_sweep(chip_profile):
    """Idle inside steps = time with no operation running and a step open,
    by +1/-1 events over the operations and the step spans."""
    lo, hi = trace.reduce(chip_profile).window
    ops = np.clip([(s, e) for _, s, e in _device_line(chip_profile, trace.OPS_LINE)], lo, hi)
    steps = np.array(_host_spans(chip_profile, "repro.lanczos.step"))
    assert len(steps) == STEPS
    times = np.concatenate([ops[:, 0], ops[:, 1], steps[:, 0], steps[:, 1]])
    n_ops, n_steps = len(ops), len(steps)
    busy = np.concatenate([np.ones(n_ops), -np.ones(n_ops), np.zeros(2 * n_steps)])
    open_ = np.concatenate([np.zeros(2 * n_ops), np.ones(n_steps), -np.ones(n_steps)])
    order = np.argsort(times, kind="stable")
    depth, inside = np.cumsum(busy[order]), np.cumsum(open_[order])
    gaps = np.diff(times[order])
    want = np.sum(gaps[(depth[:-1] == 0) & (inside[:-1] > 0)])
    got = spans.reduce(chip_profile)["repro.lanczos.step"]["idle_ns"]
    assert want == pytest.approx(STEP_IDLE_NS, abs=1.0)
    assert got == pytest.approx(want, abs=1.0)


def test_every_spmv_runs_inside_a_step(chip_profile):
    """The chip's SpMV executions fall in the host's step spans: host and
    device events share one clock."""
    lo, hi = trace.reduce(chip_profile).window
    steps = _host_spans(chip_profile, "repro.lanczos.step")
    spmv = [
        (s, e) for name, s, e in _device_line(chip_profile, trace.MODULES_LINE)
        if trace.program_name(name) == "jit__container_spmv" and lo <= s < hi
    ]
    assert len(spmv) == STEPS
    for s, e in spmv:
        assert any(a <= s and e <= b for a, b in steps), (s, e)
