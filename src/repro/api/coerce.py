"""Input coercion for the ``eigsh`` frontend.

Accepted problem descriptions, mirroring scipy/CoLA's dispatching frontends:

  * dense arrays (NumPy / JAX), square symmetric;
  * our host-side :class:`repro.sparse.CSR`;
  * any scipy sparse matrix/array (converted to CSR once, host-side);
  * device sparse containers (:class:`DeviceCOO` / :class:`DeviceELL`);
  * our :class:`LinearOperator` subclasses (incl. :class:`HvpOperator`);
  * scipy ``LinearOperator``s and bare matvec callables (``n=`` required
    for callables without a ``.shape``).

Coercion returns *both* an operator (when the input is already actionable)
and the host CSR (when the input is an explicit sparse matrix) — the CSR is
what makes the distributed and chunked backends possible, so it is kept
whenever the input provides it.
"""

from __future__ import annotations

import hashlib
import os
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import env as envcfg
from ..core.operators import (
    CallableOperator,
    DenseOperator,
    LinearOperator,
    SparseOperator,
)
from ..sparse.diskcsr import DiskCSR, diskcsr_fingerprint, is_diskcsr, open_diskcsr
from ..sparse.formats import CSR, DeviceCOO, DeviceELL
from ..tracing import span

__all__ = ["CoercedInput", "coerce_input", "matrix_fingerprint"]


class CoercedInput(NamedTuple):
    operator: Optional[LinearOperator]  # None when only a host CSR was given
    csr: Optional[CSR]  # None for matrix-free / device-resident inputs
    n: int
    # Content digest of the problem data (CSR arrays or dense bytes), the
    # matrix half of the session-cache key (api/session.py); None for
    # matrix-free / device-resident inputs, which cannot be fingerprinted.
    fingerprint: Optional[str] = None


# Content digest: every byte of the buffers is hashed in fixed chunks, each
# chunk by its own blake2b (on a thread pool: hashlib drops the GIL over
# large buffers), and the digest is a blake2b over a header (version, kind,
# shape, each array's dtype and length) and the chunk digests in order.
# The chunking is fixed, so one matrix has one digest whatever the number of
# threads.  The version prefix keeps digests of the older single-pass hash
# (bare hex) from ever matching one of these.
_FP_VERSION = "v2"
_FP_CHUNK_BYTES = 16 << 20
_FP_SERIAL_CHUNKS = 4  # fewer chunks than this are hashed on the calling thread

_fp_pool: Optional[ThreadPoolExecutor] = None
_fp_pool_pid: Optional[int] = None
_fp_pool_lock = threading.Lock()


def _usable_cpus() -> int:
    try:
        return len(os.sched_getaffinity(0))
    except AttributeError:  # not on Linux
        return os.cpu_count() or 1


def _fingerprint_pool() -> ThreadPoolExecutor:
    """The module's hashing pool, made on first use (and again in a forked
    child, where the parent's worker threads do not exist)."""
    global _fp_pool, _fp_pool_pid
    with _fp_pool_lock:
        if _fp_pool is None or _fp_pool_pid != os.getpid():
            _fp_pool = ThreadPoolExecutor(
                max_workers=_usable_cpus(), thread_name_prefix="repro-fingerprint"
            )
            _fp_pool_pid = os.getpid()
        return _fp_pool


def _chunk_count(nbytes: int) -> int:
    return -(-int(nbytes) // _FP_CHUNK_BYTES)


def _chunk_digest(chunk) -> bytes:
    return hashlib.blake2b(chunk, person=b"repro.fp.chunk").digest()


def _tree_digest(kind: str, shape, arrays) -> str:
    """Versioned digest of ``arrays`` (host NumPy) read as raw bytes, with no
    copy of a contiguous array."""
    header = [f"repro-fp-{_FP_VERSION}", kind, repr(tuple(int(d) for d in shape))]
    chunks = []
    for arr in arrays:
        arr = np.ascontiguousarray(arr)
        header.append(f"{arr.dtype}:{arr.size}")
        raw = arr.reshape(-1).view(np.uint8)
        chunks.extend(raw[i : i + _FP_CHUNK_BYTES] for i in range(0, raw.size, _FP_CHUNK_BYTES))
    if len(chunks) < _FP_SERIAL_CHUNKS or _usable_cpus() == 1:
        digests = [_chunk_digest(c) for c in chunks]
    else:
        digests = list(_fingerprint_pool().map(_chunk_digest, chunks))
    h = hashlib.blake2b(digest_size=16, person=b"repro.fp.root")
    h.update("|".join(header).encode())
    for d in digests:
        h.update(d)
    return f"{_FP_VERSION}-{h.hexdigest()}"


def matrix_fingerprint(a) -> Optional[str]:
    """Content digest of an explicit matrix (CSR or dense), ``"v2-<hex>"``.

    Hashes every byte of the raw buffers (indptr / indices / data + shape for
    CSR; the array bytes + dtype + shape for dense), so mutating a matrix in
    place, or changing a dtype, yields a different digest — the session
    cache treats it as a new problem — while a byte-identical re-submission
    hits, whatever array objects hold it.  A chunked blake2b tree, hashed on
    a thread pool without copying the buffers (see ``_tree_digest``): about
    0.1 s for the 385 MB of a 2^20-row, 31M-nnz float64 CSR on 8 cores.
    """
    # Disk-backed inputs get the *sampled* fingerprint: hashing the full
    # payload of an out-of-core matrix would read the whole file back in.
    if isinstance(a, DiskCSR):
        return diskcsr_fingerprint(a.path)
    if isinstance(a, (str, os.PathLike)) and is_diskcsr(a):
        return diskcsr_fingerprint(a)
    if isinstance(a, CSR):
        return _tree_digest("csr", a.shape, (a.indptr, a.indices, a.data))
    if isinstance(a, (np.ndarray, jax.Array)):
        arr = np.asarray(a)
        return _tree_digest("dense", arr.shape, (arr,))
    return None


def traced_fingerprint(a) -> Optional[str]:
    """:func:`matrix_fingerprint` inside a ``repro.session.fingerprint`` span
    whose ``bytes`` stat counts the buffers hashed in full and ``chunks`` the
    chunks they were cut into (both 0 for the sampled digest of a
    disk-backed matrix)."""
    if isinstance(a, (DiskCSR, str, os.PathLike)):
        sizes = ()
    elif isinstance(a, CSR):
        sizes = [np.asarray(x).nbytes for x in (a.indptr, a.indices, a.data)]
    else:
        sizes = [int(getattr(a, "nbytes", 0))]
    with span(
        "repro.session.fingerprint",
        bytes=sum(sizes),
        chunks=sum(_chunk_count(s) for s in sizes),
    ):
        return matrix_fingerprint(a)


def _validate_values(data, storage_dtype, what: str) -> None:
    """Fail fast on inputs no solve can survive: NaN/Inf entries, or a value
    range the requested storage dtype cannot represent finitely.

    Catching this at ``prepare()``/submit time turns a confusing mid-solve
    ``NumericalBreakdown`` (or silently-Inf bf16 cast) into a named
    ``ValueError`` at the call that introduced the bad data.  O(nnz) host
    scan, paid once per session build — never per solve.
    ``REPRO_VALIDATE_INPUT=0`` is the kill switch.
    """
    if not envcfg.get_bool("REPRO_VALIDATE_INPUT"):
        return
    arr = np.asarray(data)
    if not np.issubdtype(arr.dtype, np.floating):
        return
    finite = np.isfinite(arr)
    if not finite.all():
        bad = int(arr.size - np.count_nonzero(finite))
        raise ValueError(
            f"input matrix contains {bad} non-finite value(s) in its {what}; "
            "eigsh requires finite input — mask or clean the data before "
            "prepare()/submit (set REPRO_VALIDATE_INPUT=0 to bypass)"
        )
    try:
        limit = float(jnp.finfo(storage_dtype).max)
    except (TypeError, ValueError):
        return
    peak = float(np.max(np.abs(arr))) if arr.size else 0.0
    if peak > limit:
        raise ValueError(
            f"input matrix peak magnitude {peak:.3e} overflows the requested "
            f"storage dtype {jnp.dtype(storage_dtype).name} "
            f"(finite max {limit:.3e}): this dtype combination is not "
            "finite-safe — rescale the matrix or pick a wider storage policy "
            "(set REPRO_VALIDATE_INPUT=0 to bypass)"
        )


def _csr_from_scipy(a) -> CSR:
    m = a.tocsr()
    m.sort_indices()
    if m.shape[0] != m.shape[1]:
        raise ValueError(f"eigsh needs a square matrix, got shape {m.shape}")
    return CSR(
        indptr=np.asarray(m.indptr, dtype=np.int64),
        indices=np.asarray(m.indices, dtype=np.int32),
        data=np.asarray(m.data, dtype=np.float64),
        shape=(m.shape[0], m.shape[1]),
    )


def coerce_input(
    a,
    *,
    n: Optional[int] = None,
    storage_dtype=jnp.float32,
    fingerprint: Optional[str] = None,
    want_fingerprint: bool = False,
) -> CoercedInput:
    """Normalize any accepted input into (operator, csr, n). See module doc.

    Fingerprinting is opt-in: pass ``fingerprint=`` when the digest is
    already computed (the session cache probes CSR/dense inputs before
    coercing), or ``want_fingerprint=True`` to have it computed here (the
    scipy path, whose digest is of the converted CSR).  The default skips
    the O(bytes) hash — direct ``prepare()`` sessions and cache-disabled
    calls never pay for a digest they will not use.
    """
    if isinstance(a, LinearOperator):
        return CoercedInput(operator=a, csr=None, n=int(a.n))

    def _fp(x):
        if fingerprint is not None:
            return fingerprint
        return traced_fingerprint(x) if want_fingerprint else None

    if isinstance(a, CSR):
        _validate_values(a.data, storage_dtype, "CSR data")
        return CoercedInput(operator=None, csr=a, n=a.n, fingerprint=_fp(a))

    # Disk-native path: a diskcsr directory (str/PathLike) or an already-open
    # DiskCSR.  The mapping duck-types CSR's cheap surface, so it flows into
    # chunk planning unchanged — value validation is deliberately skipped
    # here: a full finite-scan would fault in the entire on-disk payload,
    # the exact thing the out-of-core path exists to avoid (the chunked
    # solve surfaces non-finite data as a NumericalBreakdown instead).
    if isinstance(a, (str, os.PathLike)):
        a = open_diskcsr(a)  # raises FileNotFoundError with a hint otherwise
    if isinstance(a, DiskCSR):
        return CoercedInput(operator=None, csr=a, n=a.n, fingerprint=_fp(a))

    if isinstance(a, (DeviceCOO, DeviceELL)):
        impl = "coo" if isinstance(a, DeviceCOO) else "ell"
        return CoercedInput(
            operator=SparseOperator(a, impl=impl), csr=None, n=int(a.n_rows)
        )

    # scipy sparse (spmatrix or the newer sparray) — duck-typed so scipy
    # stays an optional import.
    if hasattr(a, "tocsr") and hasattr(a, "shape"):
        csr = _csr_from_scipy(a)
        _validate_values(csr.data, storage_dtype, "sparse data")
        return CoercedInput(operator=None, csr=csr, n=csr.n, fingerprint=_fp(csr))

    if isinstance(a, (np.ndarray, jax.Array)):
        if a.ndim != 2 or a.shape[0] != a.shape[1]:
            raise ValueError(f"eigsh needs a square 2-D array, got shape {a.shape}")
        _validate_values(a, storage_dtype, "entries")
        return CoercedInput(
            operator=DenseOperator(jnp.asarray(a, dtype=storage_dtype)),
            csr=None,
            n=int(a.shape[0]),
            fingerprint=_fp(a),
        )

    # scipy.sparse.linalg.LinearOperator look-alikes: .matvec + .shape.
    if hasattr(a, "matvec") and hasattr(a, "shape"):
        dim = int(a.shape[0])
        if a.shape[0] != a.shape[1]:
            raise ValueError(f"eigsh needs a square operator, got shape {a.shape}")
        mv = a.matvec
        return CoercedInput(
            operator=CallableOperator(fn=lambda x: jnp.asarray(mv(np.asarray(x))), n=dim),
            csr=None,
            n=dim,
        )

    if callable(a):
        if n is None:
            raise ValueError(
                "eigsh(matvec_callable, ...) needs the problem size: pass n=<dim>"
            )
        return CoercedInput(operator=CallableOperator(fn=a, n=int(n)), csr=None, n=int(n))

    raise TypeError(
        f"eigsh does not understand input of type {type(a).__name__}: expected a "
        "dense array, CSR, scipy sparse matrix, LinearOperator, or matvec callable"
    )
