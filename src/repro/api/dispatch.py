"""Backend selection for the ``eigsh`` frontend.

The paper's design goal is a *transparent* solver: the caller states the
problem, the solver decides placement (§III).  ``select_backend`` encodes
that decision as an explicit, testable function of the input:

  1. ``"restarted"``   — a convergence tolerance was requested: fixed-m
     Lanczos cannot promise a residual, thick-restart can, so an explicit
     ``tol`` always wins (use ``backend="distributed"`` explicitly to keep
     the multi-device path; ``tol`` then only defines the converged flags).
  2. ``"distributed"`` — an explicit sparse matrix and >1 visible device:
     the paper's nnz-balanced multi-GPU partition (its headline mode).
  3. ``"chunked"``     — an explicit sparse matrix too large to keep
     device-resident: the paper's out-of-core unified-memory mode.  Where
     the device reports its memory (``memory_stats()["bytes_limit"]``: TPU,
     GPU), triggered when the in-core working set exceeds half of it: the
     SpMV layout the solve would build, padding included, plus its Krylov
     basis (:func:`device_working_set`).  The other half is headroom for
     the SpMV's temporaries and the compiled programs.  The CPU reports no
     such figure, so there host RAM stands in for it: above
     ``CHUNKED_NNZ_THRESHOLD`` non-zeros (~25M nnz ≈ 300 MB) or a working
     set (12 B per non-zero) over half the free host RAM.
  4. ``"single"``      — everything else: the paper's single-device pipeline.

Explicit ``backend=`` requests skip the policy but are validated (the
distributed and chunked paths need an explicit sparse matrix).
"""

from __future__ import annotations

import os
from typing import Callable, Optional

import numpy as np

from ..configs import env as envcfg

__all__ = [
    "BACKENDS",
    "CHUNKED_NNZ_THRESHOLD",
    "select_backend",
    "host_available_bytes",
    "device_memory_bytes",
    "device_working_set",
]

BACKENDS = ("single", "distributed", "restarted", "chunked")

# nnz above which an in-core COO copy (val f32 + row/col i32 = 12 B/nnz) is
# deemed too large to keep device-resident; overridable for experiments.
CHUNKED_NNZ_THRESHOLD = envcfg.get_int("REPRO_EIGSH_CHUNK_NNZ")

_MATRIX_BACKENDS = ("distributed", "chunked")


def host_available_bytes() -> Optional[int]:
    """Free host memory, or None when the platform doesn't expose it."""
    try:
        return os.sysconf("SC_PAGE_SIZE") * os.sysconf("SC_AVPHYS_PAGES")
    except (ValueError, OSError, AttributeError):
        return None


def device_memory_bytes() -> Optional[int]:
    """Memory of the default device where its backend reports one (TPU,
    GPU), else None (the CPU backend reports no ``memory_stats``)."""
    import jax

    stats = jax.devices()[0].memory_stats()
    return int(stats["bytes_limit"]) if stats and "bytes_limit" in stats else None


def device_working_set(stats, fmt: str, storage_dtype, basis_vectors: int) -> int:
    """Device bytes an in-core solve holds: the SpMV layout ``fmt`` at its
    padded size (``SpmvStats.layout_bytes``) and the ``(basis_vectors, n)``
    Krylov basis, both in the storage dtype."""
    vb = np.dtype(storage_dtype).itemsize
    return stats.layout_bytes(fmt, vb) + int(basis_vectors) * stats.n_rows * vb


def select_backend(
    requested: str,
    *,
    has_matrix: bool,
    nnz: int = 0,
    tol: Optional[float] = None,
    device_count: int = 1,
    free_bytes: Optional[int] = None,
    mesh_given: bool = False,
    disk_bytes: Optional[int] = None,
    device_bytes: Optional[int] = None,
    working_set: Optional[Callable[[], int]] = None,
) -> str:
    """Resolve ``backend="auto"`` (or validate an explicit request).

    Args:
      requested: "auto" or one of BACKENDS.
      has_matrix: input coerced to an explicit host CSR (disk-backed DiskCSR
        mappings count: they can be re-partitioned/chunked from disk).
      nnz: non-zeros of that CSR (0 for matrix-free inputs).
      tol: requested convergence tolerance (None = fixed-iteration mode).
      device_count: visible (or mesh-provided) device count.
      free_bytes: host-memory budget; defaults to the live reading.
      mesh_given: the caller passed an explicit ``jax.sharding.Mesh`` — under
        "auto" that is an explicit request for the distributed path and must
        not be silently dropped (e.g. when ``tol`` would pick restarted).
      disk_bytes: on-disk payload size of a disk-backed (DiskCSR) input, or
        None for in-RAM inputs.  Under "auto", a disk matrix whose payload
        exceeds half the free host memory MUST stream: every other backend
        would materialize it.
      device_bytes: device memory to judge residency against; defaults to
        the live :func:`device_memory_bytes` (None on the CPU, where the
        host-memory rule applies instead).
      working_set: returns the in-core solve's device bytes
        (:func:`device_working_set`); called only where the device reports
        its memory.  Defaults to 12 B per non-zero (COO at f32 values).
    """
    if requested != "auto":
        if requested not in BACKENDS:
            raise ValueError(f"unknown backend {requested!r}; expected one of {BACKENDS}")
        if requested in _MATRIX_BACKENDS and not has_matrix:
            raise ValueError(
                f"backend={requested!r} needs a host-side sparse matrix (repro "
                "CSR or scipy sparse) so it can be re-partitioned/chunked; "
                "device containers (DeviceCOO/DeviceELL) and matrix-free "
                "operators can't be — pass the host CSR instead"
            )
        return requested

    # Host-memory pressure rule for disk-backed inputs: a mapping bigger than
    # the budget cannot be materialized by ANY other backend, so it overrides
    # even tol/device-count preferences (the chunked engine honors tol=None
    # fixed-m semantics; restarted-on-disk would page-thrash or OOM).
    if disk_bytes is not None and has_matrix:
        free = free_bytes if free_bytes is not None else host_available_bytes()
        if free is None or disk_bytes > free // 2:
            return "chunked"

    if mesh_given:
        if not has_matrix:
            raise ValueError(
                "mesh= requests the distributed backend, which needs a host-side "
                "sparse matrix (repro CSR or scipy sparse) so it can be "
                "re-partitioned; device containers (DeviceCOO/DeviceELL) and "
                "matrix-free operators can't be — pass the host CSR instead"
            )
        return "distributed"

    # A requested tolerance is a convergence *requirement*: only the restarted
    # engine iterates until it holds, so it wins even over multiple devices.
    # (Pass backend="distributed" explicitly to keep the fixed-m multi-device
    # path; tol then only defines the converged flags.)
    if tol is not None:
        return "restarted"
    if has_matrix and device_count > 1:
        return "distributed"
    if has_matrix:
        device = device_bytes if device_bytes is not None else device_memory_bytes()
        if device is not None:
            need = working_set() if working_set is not None else nnz * 12
            return "chunked" if need > device // 2 else "single"
        if nnz >= CHUNKED_NNZ_THRESHOLD:
            return "chunked"
        free = free_bytes if free_bytes is not None else host_available_bytes()
        if free is not None and nnz * 12 > free // 2:
            return "chunked"
    return "single"
