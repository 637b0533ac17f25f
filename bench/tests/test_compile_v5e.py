"""The cells' device programs compile for a described TPU v5e chip at their
scale-20 shapes: the SpMV over each configuration's auto-selected layout,
and the restarted engine's ``orth``.  Nothing runs; this finds what the
chip's compiler would refuse, and gives each program's memory, before any
chip time is spent.  The scale-20 graphs take a few seconds and ~2 GB of
host memory to generate."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.gen import generate


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _layout(csr, fmt_engine, sharding):
    """Abstract device layout of ``csr`` as ``make_operator`` builds it."""
    from repro.sparse.formats import DeviceCOO, DeviceELL

    n, nnz = csr.n, csr.nnz
    if fmt_engine.format == "coo":
        return DeviceCOO(
            _sds((nnz,), jnp.int32, sharding), _sds((nnz,), jnp.int32, sharding),
            _sds((nnz,), jnp.float32, sharding), n, n,
        )
    assert fmt_engine.format == "ell", fmt_engine.format
    rows = -(-n // fmt_engine.tiles.block_r) * fmt_engine.tiles.block_r
    width = -(-int(np.diff(csr.indptr).max()) // 128) * 128
    return DeviceELL(
        _sds((rows, width), jnp.float32, sharding), _sds((rows, width), jnp.int32, sharding), n, n
    )


@pytest.mark.parametrize("workload,fmt", [("kron-s20.fff", "coo"), ("urand-s20.fff", "ell")])
def test_spmv_and_orth_compile_for_v5e(workload, fmt, one_chip):
    from repro.core.precision import FFF
    from repro.core.restarted import restart_kernels
    from repro.kernels.engine import _container_spmv, make_engine
    from repro.sparse import CSR

    cell = harness.load_cell(workload)
    g = generate(cell.config, 1)
    csr = CSR(g.indptr, g.indices, g.data, (g.n, g.n))
    engine = make_engine(csr, "auto", interpret=False, storage_dtype=jnp.float32)
    assert engine.format == fmt
    mat = _layout(csr, engine, one_chip)
    x = _sds((g.n,), jnp.float32, one_chip)
    spmv = _container_spmv.lower(engine, mat, x, jnp.dtype(jnp.float32)).compile()
    m = cell.traffic["request"]["subspace"]
    with jax.default_matmul_precision("highest"):
        _, orth = restart_kernels(FFF)
        orth_c = orth.lower(x, _sds((m, g.n), jnp.float32, one_chip),
                            _sds((m,), jnp.float32, one_chip)).compile()
    report = {}
    for name, c in (("spmv", spmv), ("orth", orth_c)):
        mem = c.memory_analysis()
        report[name] = {k: getattr(mem, k) for k in (
            "argument_size_in_bytes", "output_size_in_bytes", "temp_size_in_bytes",
            "generated_code_size_in_bytes")}
    print(json.dumps({"workload": workload, "format": fmt, "nnz": g.nnz, **report}))
    assert report["spmv"]["temp_size_in_bytes"] < 16e9
