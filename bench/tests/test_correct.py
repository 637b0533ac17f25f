"""``correct`` holds for the program and fails for its control and for the
faults a cell of this kind can have, on the CPU at scale 11.

Each case drives a whole run's set-up, window and check
(``bench.harness.run``, which skips the look for a chip), with one request
in the window."""

import dataclasses
import time

import numpy as np
import pytest

from bench import harness

SCALE = 11


@pytest.fixture(scope="module", params=["kron-s20.fff", "urand-s20.fff"])
def cell(request):
    return harness.load_cell(request.param)


def _run(cell, seed=5):
    return harness.run(cell, seed, 0.0, False, t_start=time.perf_counter(), scale=SCALE)


def _broken_window(monkeypatch, target, name, value):
    """Break the timed path once set-up is done: the warm-up request runs
    the program as it is, every request of the window runs it broken."""
    window = harness.window

    def broken(*a, **kw):
        monkeypatch.setattr(target, name, value)
        return window(*a, **kw)

    monkeypatch.setattr(harness, "window", broken)


@pytest.mark.parametrize("seed", [1, 2**31 + 3])
def test_program_is_correct(cell, seed):
    o = _run(cell, seed)
    assert o.correct(), o.numbers
    assert [r.iterations for r in o.requests] == [cell.traffic["steps"]]
    assert all(r.session_reuse and r.prepare_s == 0.0 for r in o.requests)


def test_control_is_not_correct(cell):
    """The mix's control: the program's own path one precision below."""
    control = dict(cell.traffic["request"], **cell.traffic["control"])
    o = _run(dataclasses.replace(cell, traffic=dict(cell.traffic, request=control)))
    assert not o.correct(), o.numbers


def test_step_that_leaves_the_state_unchanged(cell, monkeypatch):
    from repro.kernels.engine import SpmvEngine

    _broken_window(monkeypatch, SpmvEngine, "spmv", lambda self, mat, x, accum_dtype=None: x)
    o = _run(cell)
    assert not o.correct()


def test_half_the_work_left_out(cell, monkeypatch):
    """The SpMV reads every other column only and doubles the rest."""
    from repro.kernels.engine import SpmvEngine

    spmv = SpmvEngine.spmv

    def half(self, mat, x, accum_dtype=None):
        keep = (np.arange(x.shape[0]) % 2 == 0).astype(np.float32)
        return 2 * spmv(self, mat, x * keep.astype(x.dtype), accum_dtype)

    _broken_window(monkeypatch, SpmvEngine, "spmv", half)
    o = _run(cell)
    assert not o.correct(), o.numbers


def test_answer_altered_where_produced(cell, monkeypatch):
    """The engine's last Ritz value comes back 0.1% off."""
    from repro.api import session
    from repro.core import restarted

    solve = restarted.solve_restarted

    def altered(*a, **kw):
        out = solve(*a, **kw)
        lam = np.asarray(out.eigenvalues_f64).copy()
        lam[-1] *= 1.001
        return out._replace(
            eigenvalues=out.eigenvalues.at[-1].multiply(1.001), eigenvalues_f64=lam
        )

    _broken_window(monkeypatch, session, "solve_restarted", altered)
    o = _run(cell)
    assert not o.correct(), o.numbers
