"""``bench/run.py`` refuses to measure anywhere but on the chips a cell asks
for, and then prints no result."""

import os
import shutil
import subprocess
import sys
import types

import pytest

from bench import harness, roofline

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
RUN = ["bench/run.py", "--workload", "kron-s20.fff", "--seed", "1", "--seconds", "1"]


def _run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, *args], cwd=cwd, env=env, capture_output=True, text=True, timeout=300
    )


def test_no_tpu_exits_nonzero_without_a_result():
    p = _run(ROOT, *RUN, "--trace", "0")
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_unknown_workload_exits_nonzero():
    p = _run(ROOT, "bench/run.py", "--workload", "nope", "--seed", "1", "--seconds", "1")
    assert p.returncode != 0 and p.stdout == ""


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench")
    p = _run(tmp_path, *RUN)
    assert p.returncode != 0 and p.stdout == ""


def _fake_devices(monkeypatch, kind, count=1):
    import jax

    devs = [types.SimpleNamespace(platform="tpu", device_kind=kind, id=i) for i in range(count)]
    monkeypatch.setattr(jax, "devices", lambda *a: devs)


def test_unknown_device_kind_exits_nonzero(monkeypatch, capsys):
    sys.path.insert(0, os.path.join(ROOT, "bench"))
    try:
        import run
    finally:
        sys.path.pop(0)
    _fake_devices(monkeypatch, "TPU v99 imaginary")
    assert run.main(RUN[1:]) != 0
    assert capsys.readouterr().out == ""


def test_too_few_chips(monkeypatch):
    _fake_devices(monkeypatch, "TPU v5 lite", count=1)
    with pytest.raises(harness.NoDevice):
        harness.open_devices(4)
    assert len(harness.open_devices(1)) == 1
    _fake_devices(monkeypatch, "TPU v99 imaginary")
    with pytest.raises(roofline.UnknownDevice):
        harness.open_devices(1)
