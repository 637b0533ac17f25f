"""The ``"sell"`` SpMV's gather kernel (``kernels/sell_gather.py``).

It must give the bits of ``jnp.take(x, col)``: compared as ``uint32`` views
over edge shapes and non-finite values, through ``DeviceSELL.matvec`` on the
GAP generators' matrices and through a whole ``eigsh``.  The choice between
it and XLA's gather is made from ``x``'s dtype and size and reported in
``partition["spmv"]``; the kernel holds no ``dot_general``.  Under the
interpreter (this CPU) the kernel runs interpreted, the same code.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jex_core

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench.gen import generate as gap_generate  # noqa: E402
from repro.analysis.kernel_check import pallas_eqns  # noqa: E402
from repro.api import eigsh, session_cache_clear  # noqa: E402
from repro.kernels import sell_gather as sg  # noqa: E402
from repro.sparse import CSR  # noqa: E402
from repro.sparse.formats import to_device_sell  # noqa: E402

# Slots of one interpreted grid step: a count past it leaves a partial block.
BLOCK_SLOTS = sg.BLOCK_CHUNKS * 128


def _bits(a) -> np.ndarray:
    return np.asarray(a).view(np.uint32)


def _case(name: str, rng):
    """(x, col) of one bit-equality case."""
    n, slots = {
        "n_below_128": (100, 384),
        "n_not_multiple_of_128": (1000, 128 * 40),
        "n_4096": (4096, 128 * 300),
        "first_and_last_column": (777, 256),
        "hub_columns": (3000, 128 * 64),
        "partial_block": (500, BLOCK_SLOTS + 128 * 3),
        "below_one_block": (500, 128),
        "non_finite": (300, 128 * 8),
    }[name]
    x = rng.standard_normal(n).astype(np.float32)
    col = rng.integers(0, n, slots)
    if name == "first_and_last_column":
        col = np.where(np.arange(slots) % 2, n - 1, 0)
    if name == "hub_columns":  # most slots on three columns, as kron's hubs
        hubs = np.array([0, 1234, n - 1])
        col = np.where(rng.random(slots) < 0.9, hubs[rng.integers(0, 3, slots)], col)
    if name == "non_finite":
        special = np.array([np.inf, -np.inf, np.nan, -0.0, 1e-40, -1e-44], np.float32)
        x[: special.size] = special
        x[special.size] = np.frombuffer(np.uint32(0x7FC0_1234).tobytes(), np.float32)[0]
        col[: 2 * special.size + 2] = np.tile(np.arange(special.size + 1), 2)
    return jnp.asarray(x), jnp.asarray(col.astype(np.int32))


CASES = (
    "n_below_128",
    "n_not_multiple_of_128",
    "n_4096",
    "first_and_last_column",
    "hub_columns",
    "partial_block",
    "below_one_block",
    "non_finite",
)


@pytest.mark.parametrize("case", CASES)
def test_gather_equals_take_bit_for_bit(case):
    x, col = _case(case, np.random.default_rng(CASES.index(case)))
    got = sg.sell_gather(x, col, interpret=True)
    np.testing.assert_array_equal(_bits(got), _bits(jnp.take(x, col)))


def test_gather_under_vmap_equals_take():
    """The multi-start sweep's shape: a batch of x, one col."""
    rng = np.random.default_rng(9)
    xb = jnp.asarray(rng.standard_normal((3, 1000)), jnp.float32)
    col = jnp.asarray(rng.integers(0, 1000, 128 * 20), jnp.int32)
    got = jax.vmap(lambda v: sg.sell_gather(v, col, interpret=True))(xb)
    want = jax.vmap(lambda v: jnp.take(v, col))(xb)
    np.testing.assert_array_equal(_bits(got), _bits(want))


@pytest.mark.parametrize("interpret", [True, False], ids=["interpret", "compiled"])
def test_kernel_body_holds_no_dot(interpret):
    """A selection, never a product: no ``dot_general`` (at any precision)
    in the traced kernel body, interpreted or compiled."""
    x = jnp.zeros((5000,), jnp.float32)
    col = jnp.zeros((128 * 64,), jnp.int32)
    traced = jax.make_jaxpr(lambda a, b: sg.sell_gather(a, b, interpret=interpret))(x, col)
    (eqn,) = pallas_eqns(traced)
    assert eqn.params["name"] == "sell_gather"
    used = _primitives(eqn.params["jaxpr"])
    assert "dot_general" not in used
    assert {"get", "swap", "select_n", "reduce_max"} <= used  # loads, stores, the pick


def _primitives(jaxpr) -> set:
    """Names of the primitives in ``jaxpr`` and every jaxpr nested in it."""
    if isinstance(jaxpr, jex_core.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    out = set()
    for eqn in jaxpr.eqns:
        out.add(eqn.primitive.name)
        for p in eqn.params.values():
            for sub in p if isinstance(p, (tuple, list)) else (p,):
                if isinstance(sub, (jex_core.ClosedJaxpr, jex_core.Jaxpr)):
                    out |= _primitives(sub)
    return out


@pytest.mark.parametrize(
    "n, dtype, slots, interpret, want",
    [
        (1 << 20, jnp.float32, 128 * 1000, False, "mosaic"),  # a cell's x, 4 MiB
        (1 << 20, jnp.float32, 128 * 1000, True, "pallas_interpret"),
        (1 << 24, jnp.float32, 128 * 1000, False, "mosaic"),  # 64 MiB fits 128
        (1 << 25, jnp.float32, 128 * 1000, False, "xla"),  # 128 MiB does not
        (1 << 20, jnp.float64, 128 * 1000, False, "xla"),
        (1 << 20, jnp.bfloat16, 128 * 1000, False, "xla"),
        (1 << 20, jnp.int32, 128 * 1000, False, "xla"),
        (1 << 20, jnp.float32, 128 * 1000 + 5, False, "xla"),  # unaligned layout
        (1 << 20, jnp.float32, 0, False, "xla"),
    ],
)
def test_executor_decides_from_dtype_size_and_slots(n, dtype, slots, interpret, want):
    assert sg.executor(n, dtype, slots, interpret) == want


# ------------------------------------------------- through the SpMV and eigsh


def gap_csr(family: str, scale: int, seed: int = 1) -> CSR:
    cfg = {
        "generator": family, "scale": scale, "edge_factor": 16, "graph_seed": 20,
        "kronecker_abc": [0.57, 0.19, 0.19],
    }
    g = gap_generate(cfg, seed)
    data = np.random.default_rng(seed).uniform(0.5, 1.5, g.nnz)
    return CSR(g.indptr, g.indices, data, (g.n, g.n))


def no_vmem(monkeypatch):
    """No VMEM to hold ``x``: every gather falls back to ``jnp.take``.  The
    choice is made when a program is traced, so compiled programs go too."""
    monkeypatch.setattr(sg, "vmem_capacity_bytes", lambda: 0)
    jax.clear_caches()


@pytest.mark.parametrize("acc", [jnp.float32, jnp.float64], ids=["acc_f32", "acc_f64"])
@pytest.mark.parametrize("family, scale", [("kron", 9), ("kron", 11), ("urand", 10), ("urand", 11)])
def test_sell_matvec_with_the_kernel_equals_the_xla_gather(family, scale, acc, monkeypatch):
    mat = to_device_sell(gap_csr(family, scale), dtype=jnp.float32)
    assert mat.col.shape[0] % 128 == 0 and mat.slots <= mat.col.shape[0] < mat.slots + 128
    x = jnp.asarray(np.random.default_rng(scale).standard_normal(mat.n_rows), jnp.float32)
    assert mat.gather_executor(x.dtype, interpret=True) == "pallas_interpret"
    got = mat.matvec(x, accum_dtype=acc, interpret=True)
    no_vmem(monkeypatch)
    assert mat.gather_executor(x.dtype, interpret=True) == "xla"
    want = mat.matvec(x, accum_dtype=acc, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _solve(csr, policy, backend):
    session_cache_clear()
    kw = dict(k=4, subspace=16, num_iters=16, seed=3, policy=policy, backend=backend)
    res = eigsh(csr, format="sell", **kw)
    session_cache_clear()
    return res


@pytest.mark.parametrize("family", ["kron", "urand"])
def test_eigsh_over_sell_is_bit_identical_with_and_without_the_kernel(family, monkeypatch):
    """Op by op: XLA:CPU drops the ``optimization_barrier`` that keeps each
    class's products apart from its sum, and then compiles the fused sum
    differently for another producer of the same bits (jitted, the same
    ``jnp.take`` SpMV differs from its op-by-op run in a third of the rows).
    A TPU keeps the barrier; the chip check compares the jitted programs."""
    csr = gap_csr(family, 8)
    with jax.disable_jit():
        res = _solve(csr, "FFF", "restarted")
        no_vmem(monkeypatch)
        ref = _solve(csr, "FFF", "restarted")
    assert res.partition["spmv"]["sell"]["gather"] == "pallas_interpret"
    assert ref.partition["spmv"]["sell"]["gather"] == "xla"
    for a, b in [(res.eigenvalues, ref.eigenvalues), (res.eigenvectors, ref.eigenvectors)]:
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize(
    "policy, over_budget, want",
    [
        ("FFF", False, "pallas_interpret"),  # float32 x that fits: the kernel
        ("FDF", False, "pallas_interpret"),  # float32 storage, f64 sums
        ("DDD", False, "xla"),  # float64 x
        ("FFF", True, "xla"),  # x over the VMEM budget
    ],
)
def test_partition_reports_what_gathered_x(policy, over_budget, want, monkeypatch):
    if over_budget:
        monkeypatch.setattr(sg, "VMEM_MARGIN_BYTES", sg.vmem_capacity_bytes())
        jax.clear_caches()
    res = _solve(gap_csr("urand", 9), policy, "restarted")
    spmv = res.partition["spmv"]
    assert spmv["sell"]["gather"] == want
    assert spmv["kernels"]["spmv"] == want
