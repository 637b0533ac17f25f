"""Disk-native CSR: an on-disk directory format + ``np.memmap``-backed reader.

The out-of-core engine (``core/operators.ChunkedOperator``) targets matrices
larger than host RAM, which means the matrix must never be required to exist
as in-memory arrays.  This module persists a CSR as a directory of plain
``.npy`` files plus a JSON header:

    <path>/
      header.json   {"format": "repro-diskcsr", "version": 1, "shape": [n, n],
                     "nnz": ..., "indptr_dtype": ..., "indices_dtype": ...,
                     "data_dtype": ...}
      indptr.npy    (n+1,) int64
      indices.npy   (nnz,) int32
      data.npy      (nnz,) value dtype (f64/f32/bf16 — caller's choice)

``open_diskcsr`` maps the arrays with ``np.load(mmap_mode="r")``: slicing a
row window reads only those pages from disk, so the reader's host residency
is bounded by what callers actually touch (the chunked operator touches one
staging window at a time).  ``DiskCSR`` duck-types the cheap parts of
``sparse.formats.CSR`` (``n``/``nnz``/``row_nnz``/``indptr``/``indices``/
``data``) so chunk planning code runs unchanged; ``to_csr()`` materializes —
callers must gate it on size.

``diskcsr_fingerprint`` is the content key for the session cache and
``SessionStore``: hashing every byte of the payload (what
``matrix_fingerprint`` does for in-RAM CSR, in parallel chunks) would read
the whole file back, so the disk fingerprint digests the header plus
*strided sample blocks* of each array file — O(1) I/O regardless of matrix
size, still invalidating on header change, size change, or content change
inside any sampled block (the block stride covers the file ends and evenly
spaced interior windows).
"""

from __future__ import annotations

import hashlib
import json
import os
from typing import Optional, Union

import numpy as np

from .formats import CSR

__all__ = [
    "DiskCSR",
    "save_diskcsr",
    "open_diskcsr",
    "is_diskcsr",
    "diskcsr_fingerprint",
]

_HEADER = "header.json"
_FORMAT = "repro-diskcsr"
_VERSION = 1
_ARRAYS = ("indptr", "indices", "data")
# Chunk size (elements) for the streaming writer: bounds the writer's own
# peak host bytes when persisting an already-materialized CSR.
_COPY_ELEMS = 1 << 22


class DiskCSR:
    """``np.memmap``-backed CSR view over a ``save_diskcsr`` directory.

    The three arrays are read-only memory maps: touching a slice faults in
    only the pages it covers.  Symmetric-square by repo convention (same as
    :class:`~repro.sparse.formats.CSR`).
    """

    def __init__(self, path: str):
        self.path = os.path.abspath(str(path))
        header_path = os.path.join(self.path, _HEADER)
        with open(header_path, "r") as f:
            header = json.load(f)
        if header.get("format") != _FORMAT:
            raise ValueError(f"{header_path}: not a {_FORMAT} header")
        if int(header.get("version", 0)) > _VERSION:
            raise ValueError(
                f"{header_path}: version {header['version']} is newer than "
                f"this reader ({_VERSION})"
            )
        self.header = header
        self.shape = tuple(int(s) for s in header["shape"])
        self.indptr = np.load(os.path.join(self.path, "indptr.npy"), mmap_mode="r")
        self.indices = np.load(os.path.join(self.path, "indices.npy"), mmap_mode="r")
        self.data = np.load(os.path.join(self.path, "data.npy"), mmap_mode="r")
        if self.indptr.shape[0] != self.shape[0] + 1:
            raise ValueError(
                f"{self.path}: indptr length {self.indptr.shape[0]} != n+1 "
                f"for shape {self.shape}"
            )
        if int(header["nnz"]) != self.indices.shape[0]:
            raise ValueError(
                f"{self.path}: header nnz {header['nnz']} != indices length "
                f"{self.indices.shape[0]}"
            )

    @property
    def n(self) -> int:
        return self.shape[0]

    @property
    def nnz(self) -> int:
        return int(self.indices.shape[0])

    def row_nnz(self) -> np.ndarray:
        # O(n) — row counts, not nnz; fine to materialize even for huge nnz.
        return np.diff(self.indptr)

    def nbytes_on_disk(self) -> int:
        """Total bytes of the three array payloads (the staging-pressure
        estimate ``backend="auto"`` compares against free host memory)."""
        return int(
            self.indptr.nbytes + self.indices.nbytes + np.asarray(self.data.shape).prod()
            * self.data.dtype.itemsize
        )

    def to_csr(self) -> CSR:
        """Materialize into an in-RAM :class:`CSR`.  Loads everything —
        callers must gate this on matrix size (verification paths do)."""
        return CSR(
            indptr=np.asarray(self.indptr, dtype=np.int64),
            indices=np.asarray(self.indices, dtype=np.int32),
            data=np.asarray(self.data, dtype=np.float64),
            shape=self.shape,
        )

    def __repr__(self) -> str:
        return (
            f"DiskCSR(path={self.path!r}, shape={self.shape}, nnz={self.nnz}, "
            f"data_dtype={self.data.dtype})"
        )


def save_diskcsr(path: str, csr: CSR, data_dtype=None) -> str:
    """Persist a CSR as a diskcsr directory; returns the directory path.

    ``data_dtype`` narrows the on-disk value dtype (default: keep the source
    dtype).  Arrays are written through ``np.lib.format.open_memmap`` in
    bounded windows, so persisting never doubles the source's host footprint.
    """
    path = os.path.abspath(str(path))
    os.makedirs(path, exist_ok=True)
    ddt = np.dtype(data_dtype) if data_dtype is not None else csr.data.dtype
    arrays = {
        "indptr": (np.asarray(csr.indptr), np.dtype(np.int64)),
        "indices": (np.asarray(csr.indices), np.dtype(np.int32)),
        "data": (np.asarray(csr.data), ddt),
    }
    for name, (src, dtype) in arrays.items():
        out = np.lib.format.open_memmap(
            os.path.join(path, f"{name}.npy"), mode="w+", dtype=dtype, shape=src.shape
        )
        for lo in range(0, src.shape[0], _COPY_ELEMS):
            hi = min(lo + _COPY_ELEMS, src.shape[0])
            out[lo:hi] = src[lo:hi].astype(dtype, copy=False)
        out.flush()
        del out
    header = {
        "format": _FORMAT,
        "version": _VERSION,
        "shape": [int(s) for s in csr.shape],
        "nnz": int(csr.nnz),
        "indptr_dtype": "int64",
        "indices_dtype": "int32",
        "data_dtype": ddt.name,
    }
    tmp = os.path.join(path, _HEADER + ".tmp")
    with open(tmp, "w") as f:
        json.dump(header, f, indent=1, sort_keys=True)
    os.replace(tmp, os.path.join(path, _HEADER))  # header last: commit point
    return path


def is_diskcsr(path) -> bool:
    """True when ``path`` looks like a diskcsr directory (committed header)."""
    try:
        p = os.fspath(path)
    except TypeError:
        return False
    return os.path.isdir(p) and os.path.isfile(os.path.join(p, _HEADER))


def open_diskcsr(path: Union[str, os.PathLike]) -> DiskCSR:
    p = os.fspath(path)
    if not is_diskcsr(p):
        raise FileNotFoundError(
            f"{p!r} is not a repro diskcsr directory (missing {_HEADER}; "
            "write one with repro.sparse.save_diskcsr)"
        )
    return DiskCSR(p)


def _sample_file(h, fpath: str, blocks: int, block_bytes: int) -> None:
    """Feed strided sample windows of a file into a running hash: the first
    and last blocks always, plus evenly spaced interior blocks — O(blocks)
    reads however large the file is."""
    size = os.path.getsize(fpath)
    h.update(np.int64(size).tobytes())
    with open(fpath, "rb") as f:
        if size <= blocks * block_bytes:
            h.update(f.read())  # small file: exact
            return
        stride = (size - block_bytes) // max(1, blocks - 1)
        for b in range(blocks):
            off = min(b * stride, size - block_bytes)
            f.seek(off)
            h.update(np.int64(off).tobytes())
            h.update(f.read(block_bytes))


def diskcsr_fingerprint(
    path: Union[str, os.PathLike],
    blocks: Optional[int] = None,
    block_bytes: int = 1 << 16,
) -> str:
    """Sampled content fingerprint of a diskcsr directory.

    Digest = header bytes + per-array (file size + strided 64 KiB sample
    blocks).  Cost is O(blocks) I/O — feasible for disk-resident matrices
    where hashing every byte, as ``matrix_fingerprint`` does, is not.  Any
    header or size change invalidates; content-only changes invalidate when
    they touch a sampled window (the documented contract of a *sampled* key
    — callers that rewrite data in place should bump the header or re-save).
    """
    if blocks is None:
        from ..configs import env as envcfg

        blocks = envcfg.get_int("REPRO_DISKCSR_FP_BLOCKS")
    p = os.fspath(path)
    h = hashlib.blake2b(digest_size=16)
    h.update(b"repro-diskcsr-fp-v1")
    with open(os.path.join(p, _HEADER), "rb") as f:
        h.update(f.read())
    for name in _ARRAYS:
        h.update(name.encode())
        _sample_file(h, os.path.join(p, f"{name}.npy"), int(blocks), block_bytes)
    return h.hexdigest()
