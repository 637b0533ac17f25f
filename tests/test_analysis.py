"""repro.analysis: one red fixture per rule + shipped-tree cleanliness.

Structure mirrors the subsystem's contract:

  * every rule (P001..P004, K001..K004, C001/C002, E001/E002) has a fixture
    that *fails* it — a checker that can't go red is decoration;
  * the shipped tree passes every pass with zero findings (the CI
    ``--strict`` gate, asserted here so a local pytest run sees the same
    truth);
  * the declared phase map agrees with the jaxpr-measured op counts for the
    paper rungs on all four engines, fused and unfused (the acceptance
    sweep).
"""

import os
from pathlib import Path

import pytest

import jax
import jax.numpy as jnp

from repro.analysis import RULES, Finding, is_suppressed, run_checks
from repro.analysis import concurrency, config_lint, kernel_check, precision_flow
from repro.analysis.findings import filter_suppressed, format_findings
from repro.core.precision import (
    FFF,
    POLICIES,
    assert_phase_count_parity,
    phase_op_counts,
)

REPO_ROOT = Path(__file__).resolve().parents[1]


# ----------------------------------------------------------------- findings


def test_rules_table_complete():
    assert set(RULES) == {
        "P001", "P002", "P003", "P004",
        "K001", "K002", "K003", "K004",
        "C001", "C002", "E001", "E002",
    }


def test_finding_rejects_unknown_rule():
    with pytest.raises(ValueError):
        Finding("Z999", "nope")


def test_suppression_comment():
    assert is_suppressed("x = 1  # repro: ignore[C001]", "C001")
    assert is_suppressed("x = 1  # repro: ignore[C001, E001]", "E001")
    assert not is_suppressed("x = 1  # repro: ignore[C001]", "C002")
    assert not is_suppressed("x = 1", "C001")
    fs = [Finding("C001", "m", file="f.py", line=1), Finding("C001", "m", file="f.py", line=2)]
    kept = filter_suppressed(fs, ["a = 1  # repro: ignore[C001]", "b = 2"])
    assert [f.line for f in kept] == [2]


# ------------------------------------------------------- precision red rules


def test_p001_red_undeclared_upcast():
    with jax.enable_x64(True):
        jx = jax.make_jaxpr(
            lambda x: (x.astype(jnp.float64) * 2.0).astype(jnp.float32)
        )(jax.ShapeDtypeStruct((8,), jnp.float32))
        fs = precision_flow.find_upcasts(jx, FFF.effective())
    assert [f.rule for f in fs] == ["P001"]
    assert "float64" in fs[0].message


def test_p002_red_double_rounding():
    jx = jax.make_jaxpr(
        lambda x: (x.astype(jnp.bfloat16).astype(jnp.float32) * 2.0)
    )(jax.ShapeDtypeStruct((8,), jnp.float32))
    fs = precision_flow.find_double_rounding(jx, FFF.effective())
    assert [f.rule for f in fs] == ["P002"]
    assert "bfloat16" in fs[0].message


def test_p003_red_phase_leak():
    jx = jax.make_jaxpr(
        lambda a, b: jnp.sum(a.astype(jnp.bfloat16) * b.astype(jnp.bfloat16))
    )(jax.ShapeDtypeStruct((64,), jnp.float32), jax.ShapeDtypeStruct((64,), jnp.float32))
    fs = precision_flow.find_phase_leaks(jx, FFF.effective(), "alpha_beta")
    assert any(f.rule == "P003" for f in fs)


def test_p003_green_declared_dtypes():
    jx = jax.make_jaxpr(lambda a, b: jnp.sum(a * b))(
        jax.ShapeDtypeStruct((64,), jnp.float32), jax.ShapeDtypeStruct((64,), jnp.float32)
    )
    assert precision_flow.find_phase_leaks(jx, FFF.effective(), "alpha_beta") == []


def test_p004_red_parity_divergence():
    with pytest.raises(AssertionError):
        assert_phase_count_parity(
            {"float32": 1_000}, {"float32": 1_000_000}, ratio=8.0
        )
    with pytest.raises(AssertionError):  # dtype present only in measured
        assert_phase_count_parity(
            {"float32": 1_000}, {"float32": 1_000, "float64": 1_000}, ratio=8.0
        )
    # green: within ratio
    assert_phase_count_parity({"float32": 1_000}, {"float32": 3_000}, ratio=8.0)


# -------------------------------------------------------- kernel red rules


def _dot_avals(n):
    a = jax.ShapeDtypeStruct((n,), jnp.float32)
    return (a, a)


def test_k001_red_indivisible_block():
    from repro.kernels.mixed_dot import mixed_dot_kernel_call

    fs = kernel_check.check_kernel_trace(
        lambda p, q: mixed_dot_kernel_call(p, q, block=4096, interpret=False),
        _dot_avals(8000),
        "mixed_dot",
    )
    assert [f.rule for f in fs] == ["K001"]


def test_k002_red_out_of_bounds_index_map():
    from jax.experimental import pallas as pl

    def bad_kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    def bad_call(x):
        return pl.pallas_call(
            bad_kernel,
            grid=(4,),
            in_specs=[pl.BlockSpec((8,), lambda i: (i + 1,))],  # off-by-one
            out_specs=pl.BlockSpec((8,), lambda i: (i,)),
            out_shape=jax.ShapeDtypeStruct((32,), jnp.float32),
            interpret=True,
        )(x)

    fs = kernel_check.check_kernel_trace(
        bad_call, (jax.ShapeDtypeStruct((32,), jnp.float32),), "mixed_dot"
    )
    assert any(f.rule == "K002" for f in fs)


def test_k003_red_vmem_budget():
    from repro.kernels.mixed_dot import mixed_dot_kernel_call

    fs = kernel_check.check_kernel_trace(
        lambda p, q: mixed_dot_kernel_call(p, q, block=4096, interpret=False),
        _dot_avals(8192),
        "mixed_dot",
        vmem_budget=1024,  # 1 KB: everything overflows
    )
    assert any(f.rule == "K003" for f in fs)


def test_k004_red_pinned_output_on_parallel_dim():
    from repro.kernels.mixed_dot import mixed_dot_kernel_call

    # The scalar accumulator output is pinned across the grid; declaring
    # dim 0 parallel must trip the race rule.
    fs = kernel_check.check_kernel_trace(
        lambda p, q: mixed_dot_kernel_call(p, q, block=4096, interpret=False),
        _dot_avals(8192),
        "mixed_dot",
        parallel_dims=frozenset({0}),
    )
    assert any(f.rule == "K004" for f in fs)


def test_k004_green_shipped_contracts():
    # The shipped contract table accepts every shipped kernel.
    fs = kernel_check.run()
    assert [str(f) for f in fs] == []


# --------------------------------------------------- concurrency red rules


_C001_SNIPPET = """
class Sched:
    _GUARDED_BY = {"_queue": "_cv"}

    def bad(self):
        self._queue.append(1)

    def good(self):
        with self._cv:
            self._queue.append(1)
"""


def test_c001_red_unguarded_mutation():
    fs = concurrency.check_source(_C001_SNIPPET, "sched.py")
    assert [(f.rule, f.line) for f in fs] == [("C001", 6)]


_C002_SNIPPET = """
class Sched:
    _GUARDED_BY = {}

    def inverted(self):
        with self._build_lock:
            with self._cv:
                pass

    def cross(self, sess):
        with self._cv:
            sess.eigsh_many([])
"""


def test_c002_red_lock_order_and_cross_object_call():
    fs = concurrency.check_source(_C002_SNIPPET, "sched.py")
    rules = [f.rule for f in fs]
    assert rules.count("C002") == 2


def test_c001_exemptions():
    snippet = """
class S:
    _GUARDED_BY = {"_q": "_lock"}

    def __init__(self):
        self._q = []

    def _drain_locked(self):
        self._q.clear()

    def drain(self):  # repro: holds[_lock]
        self._q.clear()

    def noted(self):
        self._q.clear()  # repro: ignore[C001]
"""
    assert concurrency.check_source(snippet, "s.py") == []


# -------------------------------------------------------- config red rules


def test_e001_red_raw_env_read():
    src = """
import os
a = os.environ.get("REPRO_SPMV_TUNE")
b = os.getenv("REPRO_FAULT")
c = os.environ["REPRO_ITER_UPDATE"]
os.environ["REPRO_SPMV_TUNE"] = "1"      # write: allowed
os.environ.setdefault("REPRO_FAULT", "") # write: allowed
d = os.environ.get("HOME")               # not a knob: allowed
"""
    fs = config_lint.find_raw_env_reads(src, "m.py")
    assert [f.rule for f in fs] == ["E001"] * 3
    assert [f.line for f in fs] == [3, 4, 5]


def test_e002_red_registry_readme_drift():
    fs = config_lint.check_readme_sync({"REPRO_A", "REPRO_B"}, "only REPRO_A and REPRO_GHOST")
    msgs = sorted(f.message for f in fs)
    assert len(fs) == 2 and all(f.rule == "E002" for f in fs)
    assert any("REPRO_B" in m for m in msgs)
    assert any("REPRO_GHOST" in m for m in msgs)


def test_env_registry_contract():
    from repro.configs import env as envcfg

    with pytest.raises(KeyError):
        envcfg.knob("REPRO_NOT_A_KNOB")
    assert envcfg.get_bool("REPRO_SPMV_TUNE") is False
    os.environ["REPRO_SPMV_TUNE"] = "on"
    try:
        assert envcfg.get_bool("REPRO_SPMV_TUNE") is True
    finally:
        del os.environ["REPRO_SPMV_TUNE"]
    assert envcfg.get_float("REPRO_ANALYSIS_VMEM_MB") == 16.0


# -------------------------------------------------- shipped-tree cleanliness


def test_shipped_tree_strict_clean_static_passes():
    """The AST/config/kernel passes are clean on the tree as shipped."""
    results = run_checks(
        ["kernels", "concurrency", "config"], repo_root=str(REPO_ROOT)
    )
    for name, findings in results.items():
        assert findings == [], f"{name}: {format_findings(findings)}"


@pytest.mark.parametrize("rung", precision_flow.RUNGS)
@pytest.mark.parametrize("engine", precision_flow.ENGINES)
@pytest.mark.parametrize("fused", [False, True])
def test_declared_phase_map_matches_measured(rung, engine, fused):
    """The acceptance sweep: measured ops_by_dtype agrees with the declared
    phase map for every paper rung on every engine, fused and unfused."""
    findings, measured = precision_flow.check_policy(
        POLICIES[rung], engine, fused=fused
    )
    assert findings == [], format_findings(findings)
    assert measured and all(v > 0 for v in measured.values())


def test_device_jacobi_ritz_accounting():
    """The reconciled model attributes the device-Jacobi sweep work (the
    divergence this PR fixed) — parity must hold with jacobi='device'."""
    findings, measured = precision_flow.check_policy(
        POLICIES["FDF"], "single", jacobi="device"
    )
    assert findings == [], format_findings(findings)
    # and the model actually grew: device ritz >> host ritz (projection only)
    host = phase_op_counts(POLICIES["FDF"], n=100, nnz=400, m=8, k=4, executed=True)
    dev = phase_op_counts(
        POLICIES["FDF"], n=100, nnz=400, m=8, k=4, executed=True, jacobi="device"
    )
    assert sum(dev.values()) > sum(host.values())


def test_session_measured_hook(tmp_path):
    """REPRO_PRECISION_MEASURE=1 surfaces jaxpr-measured counts in the
    partition audit, and they parity-match the executed-convention model."""
    from repro.api import eigsh
    from repro.sparse import generate

    os.environ["REPRO_PRECISION_MEASURE"] = "1"
    try:
        csr = generate("road", 100, 4.0, seed=1)
        res = eigsh(csr, k=3)
        prec = res.partition["spmv"]["precision"]
        measured = prec.get("ops_by_dtype_measured")
        assert measured and "error" not in measured
        assert all(isinstance(v, int) and v > 0 for v in measured.values())
        # Same float dtypes as the declared model counts.
        assert set(measured) == set(prec["ops_by_dtype"])
    finally:
        del os.environ["REPRO_PRECISION_MEASURE"]


# ---------------------------------------------------------------- CLI / CI


def test_cli_strict_clean_on_fast_passes(tmp_path, capsys):
    from repro.analysis.__main__ import main

    summary = tmp_path / "summary.md"
    rc = main(
        [
            "--check", "concurrency", "--check", "config",
            "--strict",
            "--repo-root", str(REPO_ROOT),
            "--summary-out", str(summary),
        ]
    )
    assert rc == 0
    out = capsys.readouterr().out
    assert "[concurrency] 0 finding(s)" in out
    assert "clean" in summary.read_text()


def test_cli_strict_fails_on_findings(tmp_path, capsys, monkeypatch):
    from repro.analysis.__main__ import main

    # A doctored tree: a serving module mutating a guarded field lock-free.
    bad_root = tmp_path / "tree"
    (bad_root / "src" / "repro" / "serving").mkdir(parents=True)
    (bad_root / "src" / "repro" / "serving" / "bad.py").write_text(
        _C001_SNIPPET, encoding="utf-8"
    )
    rc = main(["--check", "concurrency", "--strict", "--repo-root", str(bad_root)])
    assert rc == 1
    assert "C001" in capsys.readouterr().out


def test_cli_rejects_unknown_check():
    from repro.analysis.__main__ import main

    with pytest.raises(SystemExit):
        main(["--check", "nonsense"])
