"""TPU compiles, without a chip: the kernels and the sweep the chip path runs.

Each test compiles for one chip of a described TPU v5e topology
(``jax.experimental.topologies``), so Mosaic — the TPU kernel compiler —
refuses here what it would refuse on the chip: misaligned blocks, scalar
stores to VMEM, 64-bit index maps, more VMEM than a kernel may use.  Sizes
are the paper's Wikipedia matrix (WK, Table I: n = 3,566,907).  The
topology is described inside a module fixture, never at import, so every
test worker collects the same tests and only the one that runs this file
loads the TPU compiler.

The last tests trace (no compile) a TPU-mode engine — ``interpret=False`` —
through every format and update mode, and check that it dispatches only the
kernels Mosaic accepts (``lanczos_update``, ``mixed_dot``, and
``sell_gather`` for the ``"sell"`` layout): the ELL / BSR SpMV kernels
gather from a VMEM-resident ``x`` and run only under the interpreter.
"""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.analysis.kernel_check import pallas_eqns
from repro.core.lanczos import _lanczos_loop, ops_for_operator
from repro.core.operators import SparseOperator, make_operator
from repro.core.precision import FFF
from repro.kernels import ops as kops
from repro.kernels.engine import FORMATS, ITER_UPDATE_MODES, make_engine
from repro.sparse import generate
from repro.sparse.formats import (
    DeviceHybrid,
    DeviceSELL,
    sell_classes,
    sell_stored_slots,
    to_device_ell,
)

WK_N = 3_566_907
# The generated WK matrix (29,079,360 non-zeros) in its hybrid layout: the
# ELL part at the 95th-percentile row length (6), aligned to 8 slots, and a
# COO tail of the rows' overflow, 14,723,974 entries past the cap of 6
# (``benchmarks/probes/spmv_wk.py`` on WK; the aligned layout spills fewer).
WK_HYB_WIDTH = 8
WK_TAIL_NNZ = 14_723_974
# What the chip path may dispatch as a Pallas kernel.
MOSAIC_KERNELS = {"lanczos_update", "mixed_dot", "sell_gather"}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *avals):
    compiled = jax.jit(fn).lower(*avals).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the Mosaic kernel is in
    return compiled


def test_lanczos_update_compiles_at_wk_width(one_chip):
    vec = jax.ShapeDtypeStruct((WK_N,), jnp.float32, sharding=one_chip)
    s = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    _compile(
        lambda w, v, vp, a, b: kops.lanczos_update(
            w, v, vp, a, b, accum_dtype=jnp.float32, interpret=False
        ),
        vec, vec, vec, s, s,
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("compensated", [False, True])
def test_mixed_dot_compiles_at_wk_width(one_chip, dtype, compensated):
    vec = jax.ShapeDtypeStruct((WK_N,), dtype, sharding=one_chip)
    _compile(
        lambda a, b: kops.mixed_dot(
            a, b, accum_dtype=jnp.float32, compensated=compensated, interpret=False
        ),
        vec, vec,
    )


def test_lanczos_sweep_compiles_at_wk_width(one_chip):
    """One jitted single-engine Lanczos sweep over the hybrid layout the
    chip path builds for WK (SpMV as XLA gathers), with the fused Mosaic
    update (FFF)."""
    engine = make_engine(
        generate("web", 4096, 12.6, seed=0), "hybrid", interpret=False
    )
    assert engine.iteration_plan.update == "fused"
    pol = FFF.effective()
    rows_pad = -(-WK_N // engine.tiles.block_r) * engine.tiles.block_r

    def sweep(ell_val, ell_col, tail_row, tail_col, tail_val, v1):
        mat = DeviceHybrid(ell_val, ell_col, tail_row, tail_col, tail_val, WK_N, WK_N)
        op = SparseOperator(mat, impl="engine", engine=engine)
        return _lanczos_loop(v1, ops_for_operator(op, pol), 16, pol, "half")

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = _compile(
        sweep,
        sds((rows_pad, WK_HYB_WIDTH), jnp.float32),
        sds((rows_pad, WK_HYB_WIDTH), jnp.int32),
        sds((WK_TAIL_NNZ,), jnp.int32),
        sds((WK_TAIL_NNZ,), jnp.int32),
        sds((WK_TAIL_NNZ,), jnp.float32),
        sds((WK_N,), jnp.float32),
    )
    mem = compiled.memory_analysis()
    if mem is not None:  # the basis (16 x n f32) and the layout fit one chip
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16 * 2**30


@pytest.mark.parametrize("config", ["gap-kron-s20", "gap-urand-s20"])
def test_sell_spmv_compiles_at_cell_scale(one_chip, config):
    """The compiled SpMV over the bucketed layout of a benchmark cell's
    matrix (2^20 rows, ~33M slots): one ``sell_gather`` Mosaic kernel over
    every slot in place of XLA's gather, and temporaries of about two
    copies of the products (a compiler rewrite that pads a class to 128
    lanes shows as gigabytes)."""
    import json
    import sys

    from repro.kernels.engine import _container_spmv

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from bench.gen import generate as gap_generate

    with open(os.path.join(root, "bench", "configs", f"{config}.json")) as f:
        g = gap_generate(json.load(f), 1)
    n = g.n
    pieces, _, classes = sell_classes(np.diff(g.indptr))
    meta, off = [], 0
    for width, rows in classes:
        meta.append((width, rows, off))
        off += width * rows

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    stored = sell_stored_slots(off)
    mat = DeviceSELL(
        sds((stored,), jnp.int32), sds((stored,), jnp.float32), sds((pieces.size,), jnp.int32),
        tuple(meta), n, n, g.nnz,
    )
    engine = make_engine(generate("web", 512, 6.0, seed=3), "sell", interpret=False)
    compiled = _container_spmv.lower(
        engine, mat, sds((n,), jnp.float32), jnp.dtype(jnp.float32)
    ).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 and "sell_gather" in text
    assert text.count(" gather(") == 0
    mem = compiled.memory_analysis()
    if mem is not None:
        assert mem.temp_size_in_bytes < 3 * 4 * off


@pytest.mark.parametrize("n", [WK_N, 1 << 24], ids=["wk", "scale24"])
def test_sell_gather_compiles_where_it_is_chosen(one_chip, n):
    """The gather kernel at WK's x (14.3 MB) and at the largest x the
    VMEM test admits on a v5e (scale 24, 64 MiB): Mosaic takes both."""
    from repro.kernels import sell_gather as sg

    slots = 128 * 4096
    assert sg.executor(n, jnp.float32, slots, interpret=False) == "mosaic"
    _compile(
        lambda x, c: sg.sell_gather(x, c, interpret=False),
        jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip),
    )


def test_sell_sums_compile_alike_with_either_gather(one_chip, monkeypatch):
    """Compiled for the chip, the ``"sell"`` SpMV with the gather kernel and
    with XLA's gather differ only up to the products: every slice, reshape,
    class sum and the scatter-add have the same shapes and layouts, so the
    kernel's bits (equal to ``jnp.take``'s) give the same sums."""
    import re
    from collections import Counter

    from repro.kernels import sell_gather as sg
    from repro.kernels.engine import _container_spmv
    from repro.sparse.formats import to_device_sell

    csr = generate("web", 4096, 12.0, seed=5, values="normalized")
    mat = to_device_sell(csr, dtype=jnp.float32)
    engine = make_engine(csr, "sell", interpret=False)

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    def after_products():
        text = _container_spmv.lower(
            engine, jax.tree_util.tree_map(sds, mat), sds(jnp.zeros(csr.n, jnp.float32)),
            jnp.dtype(jnp.float32),
        ).compile().as_text()
        ops = []
        whole = f"[{mat.col.shape[0]}]"  # the gather's and the products' arrays
        for line in text.splitlines():
            if not re.search(r" (reduce|reshape|concatenate)\(|slice=|scatter", line):
                continue
            if whole not in line.split(" = ", 1)[-1].split("(")[0]:
                line = re.sub(r", metadata=\{.*", "", line)
                ops.append(re.sub(r"%[\w.\-]+", "%", line.strip()))
        return text, Counter(ops)

    kernel_text, kernel = after_products()
    monkeypatch.setattr(sg, "vmem_capacity_bytes", lambda: 0)
    jax.clear_caches()
    xla_text, xla = after_products()
    assert "sell_gather" in kernel_text and "sell_gather" not in xla_text
    assert any(" reduce(" in op for op in kernel) and any("scatter" in op for op in kernel)
    assert kernel == xla


# ------------------------------------------- what a TPU-mode engine dispatches


def _kernels_in(fn, *args):
    """(name, interpret) of every pallas_call in the trace of ``fn``."""
    return {
        (e.params["name"], bool(e.params["interpret"]))
        for e in pallas_eqns(jax.make_jaxpr(fn)(*args))
    }


@pytest.mark.parametrize("update", ITER_UPDATE_MODES)
@pytest.mark.parametrize("fmt", FORMATS)
def test_tpu_engine_dispatches_only_mosaic_kernels(fmt, update, monkeypatch):
    monkeypatch.setenv("REPRO_ITER_UPDATE", update)  # pin, even fused_spmv
    csr = generate("web", 512, 6.0, seed=3, values="normalized")
    engine = make_engine(csr, fmt, interpret=False)
    op = make_operator(csr, engine=engine)
    ops = ops_for_operator(op, FFF.effective())
    v1 = jnp.ones((csr.n,), jnp.float32)
    found = _kernels_in(lambda v: _lanczos_loop(v, ops, 8, FFF.effective(), "half").alpha, v1)
    assert {name for name, _ in found} <= MOSAIC_KERNELS, found
    assert not any(interp for _, interp in found), found
    if update != "unfused":  # the compiled update kernel is really used
        assert ("lanczos_update", False) in found
    # the "sell" SpMV gathers its float32 x with the compiled kernel
    assert (("sell_gather", False) in found) == (fmt == "sell")


def test_tpu_engine_spmv_layouts_run_as_xla():
    """Every raw-array SpMV entry of a TPU-mode engine traces to no kernel,
    while the same engine in interpret mode does reach its kernel."""
    csr = generate("web", 512, 6.0, seed=3, values="normalized")
    ell = to_device_ell(csr, row_tile=32)
    x = jnp.ones((csr.n,), jnp.float32)
    tpu = make_engine(csr, "ell", interpret=False)
    cpu = make_engine(csr, "ell", interpret=True)
    assert _kernels_in(lambda v: tpu.ell_matvec(ell.val, ell.col, v), x) == set()
    assert _kernels_in(lambda v: cpu.ell_matvec(ell.val, ell.col, v), x) == {("spmv_ell", True)}
    rows, width = ell.val.shape
    scale = jnp.ones((rows, 1), jnp.float32)
    base = jnp.zeros((rows, 1), jnp.int32)
    dcol = jnp.zeros((rows, width), jnp.int16)
    packed = functools.partial(tpu.packed_ell_matvec, ell.val.astype(jnp.bfloat16), scale, base, dcol)
    assert _kernels_in(packed, x) == set()
    bsr = make_engine(csr, "bsr", interpret=False)
    bmat = make_operator(csr, engine=bsr).mat
    assert _kernels_in(lambda v: bsr.bsr_matvec(bmat.val, bmat.bcol, v), x) == set()
    np.testing.assert_allclose(
        np.asarray(tpu.ell_matvec(ell.val, ell.col, x))[: csr.n],
        csr.to_scipy() @ np.ones(csr.n),
        rtol=1e-5,
    )
