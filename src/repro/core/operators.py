"""Linear operators consumed by the eigensolver.

The paper's solver is matrix-driven (sparse SpMV), but the Lanczos phase only
needs ``y = A @ x``; we expose that as a small operator protocol so the same
solver runs on:

  * explicit sparse matrices (COO segment-sum path, or the Pallas ELL/BSR
    kernels — the paper's case);
  * chunk-streamed matrices whose triplets live in **host** memory and are
    staged to the device chunk-by-chunk (the paper's out-of-core unified
    memory mode, DESIGN.md §3.4);
  * matrix-free Hessian/GGN-vector products of a model loss — the framework
    integration (spectral monitoring of training, DESIGN.md §5).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Optional

import jax
import jax.flatten_util
import jax.numpy as jnp
import numpy as np

from ..kernels.engine import SpmvEngine
from ..sparse.formats import (
    CSR,
    DeviceCOO,
    DeviceELL,
    count_conversions,
    row_sums,
    to_device_bsr,
    to_device_coo,
    to_device_ell,
    to_device_hybrid,
)
from ..testing import faults as _faults
from .precision import PrecisionPolicy

__all__ = [
    "LinearOperator",
    "DenseOperator",
    "SparseOperator",
    "ChunkedOperator",
    "CallableOperator",
    "HvpOperator",
    "chunk_row_bounds",
    "make_operator",
]


def chunk_row_bounds(indptr: np.ndarray, n: int, chunk_nnz: int) -> list:
    """Row-contiguous chunk bounds holding <= ``chunk_nnz`` non-zeros each
    (single rows larger than the budget get a chunk of their own).  Shared
    by :class:`ChunkedOperator` and the frontend's staging-footprint
    estimate so both reason about the same chunking."""
    starts = [0]
    while starts[-1] < n:
        r0 = starts[-1]
        r1 = int(np.searchsorted(indptr, indptr[r0] + chunk_nnz, side="right")) - 1
        starts.append(min(n, max(r1, r0 + 1)))
    return list(zip(starts[:-1], starts[1:]))


def chunk_rows_pad(rows: int, block_r: int, storage_dtype, row_multiple: int = 1) -> int:
    """Padded row count of one staged ELL chunk: rows round up to the chunk's
    own row tile — the kernel's ``block_r`` capped at the next power of two
    of the row count (floored at the TPU sublane minimum: 8 for 4-byte
    dtypes, 16 for bf16/f16, 32 for fp8), so a chunk with FEW rows (e.g. a
    hub row chunked alone) never allocates the full global row tile times
    its huge width.  ``ell_matvec`` adapts its row tile down to whatever
    divides this.  ``row_multiple`` additionally aligns the padded count
    (the chunk-resident sharded path needs rows divisible by the mesh)."""
    itemsize = jnp.dtype(storage_dtype).itemsize
    min_r = {1: 32, 2: 16}.get(itemsize, 8)
    np2 = 1 << max(0, max(rows, min_r) - 1).bit_length()  # next pow2 >= rows
    tile = max(min_r, min(block_r, np2)) * max(1, int(row_multiple))
    return -(-rows // tile) * tile


class LinearOperator:
    """Protocol: symmetric square operator with policy-aware matvec."""

    n: int

    def matvec(self, x: jax.Array, accum_dtype=None) -> jax.Array:
        raise NotImplementedError

    def bound_matvec(self, policy: PrecisionPolicy) -> Callable:
        # The SpMV accumulator runs in its own phase dtype (defaults to the
        # policy's compute dtype); the Lanczos loop rounds the product back
        # to the carried compute dtype at the phase boundary.
        acc = policy.phase_dtype("spmv")

        def mv(x):
            return self.matvec(x, accum_dtype=acc)

        return mv


@dataclasses.dataclass
class DenseOperator(LinearOperator):
    a: jax.Array

    @property
    def n(self) -> int:
        return self.a.shape[0]

    def matvec(self, x, accum_dtype=None):
        acc = accum_dtype or x.dtype
        return self.a.astype(acc) @ x.astype(acc)


@dataclasses.dataclass
class SparseOperator(LinearOperator):
    """Explicit sparse matrix; ``impl`` (or an :class:`SpmvEngine`) picks the
    SpMV execution path.  With an engine attached, the container format and
    tile parameters come from the engine (`kernels/engine.py`)."""

    mat: object  # DeviceCOO | DeviceELL | DeviceBSR
    impl: str = "coo"  # "coo" | "ell" | "ell_kernel" | "bsr_kernel" | "engine"
    engine: Optional[SpmvEngine] = None

    @property
    def n(self) -> int:
        if isinstance(self.mat, tuple):  # blocked-ELL: (val, bcol, n_rows)
            return int(self.mat[2])
        return self.mat.n_rows

    @property
    def spmv_format(self) -> str:
        if self.engine is not None:
            return self.engine.format
        return {"ell_kernel": "ell", "bsr_kernel": "bsr"}.get(self.impl, self.impl)

    def matvec(self, x, accum_dtype=None):
        if self.engine is not None:
            return self.engine.spmv(self.mat, x, accum_dtype=accum_dtype)
        if self.impl in ("coo", "ell"):
            return self.mat.matvec(x, accum_dtype=accum_dtype)
        if self.impl == "ell_kernel":
            from ..kernels import ops as kops

            return kops.spmv_ell(self.mat, x, accum_dtype=accum_dtype)
        if self.impl == "bsr_kernel":
            from ..kernels import ops as kops

            return kops.spmv_bsr(self.mat, x, accum_dtype=accum_dtype)  # mat = (val,bcol,n)
        raise ValueError(f"unknown SpMV impl {self.impl!r}")


class ChunkedOperator(LinearOperator):
    """Out-of-core SpMV: matrix data stays on the host (in-RAM CSR **or** an
    ``np.memmap``-backed :class:`~repro.sparse.diskcsr.DiskCSR`); each matvec
    streams fixed-size chunks to the device and accumulates partial products.

    This reproduces the paper's unified-memory out-of-core mode: at any moment
    at most ``stage_depth + 1`` chunks are device-resident.  On a real TPU the
    staging is host-DRAM -> HBM DMA; here the same code path exercises the
    chunking and double-buffering logic.

    **Host residency contract.**  Chunk buffers are built *lazily per staged
    window* from the source CSR/mapping and dropped as soon as the chunk's
    transfer is issued, so peak host residency is the source matrix (disk
    pages for a ``DiskCSR``) plus ``stage_depth + 1`` chunk windows — never a
    second full pinned copy of the matrix.  ``own_data=True`` opts into the
    legacy eager pre-pin (conversion paid once, fastest repeat sweeps) and in
    exchange the operator *drops its source-CSR reference* after pinning: the
    caller hands the arrays over, and host residency ends at one copy again.

    **Compressed staging.**  ``staging="bf16" | "fp8"`` stages ELL chunk
    values quantized to the narrow dtype with per-row-block scales and
    delta-encoded int16/int32 columns, decompressed inside the Pallas kernel
    (``kernels/spmv_ell_packed.py``) — 2-4x the effective staging bandwidth.
    ``staging="auto"`` packs when the storage dtype is already narrow
    (bf16/f16 policies) and ships plain buffers otherwise.  Byte / bandwidth
    / compression counters accumulate in ``self.staging`` (surfaced by
    ``eigsh`` in ``EigenResult.partition["spmv"]["staging"]``).

    **Sharded chunk residency.**  With a ``mesh``, each staged ELL chunk is
    placed row-sharded across the mesh and its partial SpMV runs *inside*
    ``shard_map`` — out-of-core and multi-device compose instead of
    excluding each other (the PR 3 open item).

    With an ELL-format :class:`SpmvEngine` attached, chunks are row ranges
    staged as per-chunk-width ELL tiles (a hub row inflates only its own
    chunk's padding, not every chunk's) and the partial SpMV runs the Pallas
    kernel; otherwise the COO ``segment_sum`` reference path streams
    nnz-sized slices (plain staging only).
    """

    # The Lanczos loop must stay a host loop for this operator: tracing the
    # chunk stream would bake every chunk into one executable as constants,
    # defeating the bounded-residency staging (see lanczos_tridiag(jit=...)).
    prefers_jit = False

    STAGING_MODES = ("f32", "bf16", "fp8", "auto")

    def __init__(
        self,
        csr,
        chunk_nnz: int = 1 << 20,
        dtype=jnp.float32,
        engine: Optional[SpmvEngine] = None,
        stage_depth: int = 1,
        own_data: bool = False,
        staging: str = "f32",
        mesh=None,
        axis: str = "data",
    ):
        self.n = csr.n
        self._dtype = dtype
        self.engine = engine
        self.stage_depth = max(0, int(stage_depth))
        self.spmv_format = engine.format if engine is not None else "coo"
        if self.spmv_format in ("bsr", "hybrid"):
            raise ValueError(
                "ChunkedOperator stages chunks as COO or ELL; per-chunk "
                f"{self.spmv_format.upper()} is not supported (pick format='ell' or 'coo')"
            )
        if staging not in self.STAGING_MODES:
            raise ValueError(
                f"unknown staging mode {staging!r}; expected one of {self.STAGING_MODES}"
            )
        if staging == "auto":
            # Pack when the storage dtype is already narrow: the quantization
            # the policy accepted is the quantization the staging ships.
            itemsize = jnp.dtype(dtype).itemsize
            staging = "bf16" if itemsize == 2 else ("fp8" if itemsize == 1 else "f32")
        if staging != "f32" and self.spmv_format != "ell":
            staging = "f32"  # packed staging is an ELL-kernel path
        self.staging_mode = staging
        self.mesh = mesh
        self._axis = axis
        self._mesh_size = int(np.prod(mesh.devices.shape)) if mesh is not None else 1
        from ..sparse.diskcsr import DiskCSR  # local: sparse imports stay light

        self.disk_backed = isinstance(csr, DiskCSR)
        self.source_path = csr.path if self.disk_backed else None
        self.staging = {
            "conversions": 0,
            "transfers": 0,
            "max_resident": 0,
            "bytes_staged": 0,
            "bytes_plain": 0,
            "stage_s": 0.0,
            "mode": self.staging_mode,
        }
        self._csr = csr
        self._row_nnz = np.asarray(csr.row_nnz())  # O(n), not O(nnz)
        if self.spmv_format == "ell":
            self._init_ell_meta(csr, chunk_nnz, dtype, engine)
        else:
            self._init_coo_meta(csr, chunk_nnz)
        self._built = np.zeros(self.num_chunks, dtype=bool)
        self._pinned = None
        # Mid-step checkpoint bindings (see ``set_step_hook``/``set_resume``):
        # the Lanczos host loop installs these so the ONE streamed matvec per
        # step can persist/restore its chunk cursor without the loop having
        # to thread extra arguments through the generic Ops.matvec closure.
        self._step_hook = None
        self._resume = None
        if own_data and not self.disk_backed:
            # Eager pre-pin (the legacy fast path), then release the source:
            # the caller opted into handing the arrays over, so only ONE host
            # copy (the pinned chunks) survives construction.
            self._pinned = [self._build_chunk(j) for j in range(self.num_chunks)]
            self._csr = None
            self._row_nnz = None

    # ------------------------------ chunk planning ------------------------------

    def _init_coo_meta(self, csr, chunk_nnz: int):
        nnz = csr.nnz
        self._coo_chunk_nnz = int(chunk_nnz)
        self._coo_bounds = [
            (lo, min(lo + chunk_nnz, nnz)) for lo in range(0, max(nnz, 1), chunk_nnz)
        ]
        self.num_chunks = len(self._coo_bounds)

        # One jitted partial-SpMV per instance, keyed on the (static) accum
        # dtype: defining it inside matvec would retrace on every call.
        @partial(jax.jit, static_argnames=("acc",))
        def _partial_spmv(row, col, val, x, y, *, acc):
            prod = val.astype(acc) * jnp.take(x, col).astype(acc)
            return y + row_sums(prod, row, self.n)

        self._partial_spmv = _partial_spmv

    def _init_ell_meta(self, csr, chunk_nnz: int, dtype, engine: SpmvEngine):
        indptr, n = csr.indptr, csr.n
        bounds = chunk_row_bounds(indptr, n, chunk_nnz)
        # TPU sublane minima follow the *staged* value dtype (fp8 tiles need
        # 32 sublanes); the sharded path additionally needs rows divisible by
        # the mesh extent.
        staged_dtype = {"bf16": jnp.bfloat16, "fp8": "float8_e4m3fn"}.get(
            self.staging_mode, dtype
        )
        self._bounds = []
        self._widths = []
        self._rows_pads = []
        self._r0s = []
        n_out_pad = 0
        self.padded_slots = 0
        for r0, r1 in bounds:
            local_nnz = self._row_nnz[r0:r1]
            # Per-chunk width (128-lane aligned) AND per-chunk row padding:
            # a hub row pays for its own chunk only — neither its width nor
            # the global row tile inflates any other chunk, and a few-row
            # hub chunk never allocates block_r x hub_width zeros.
            width = int(max(1, local_nnz.max() if local_nnz.size else 1))
            width = -(-width // 128) * 128
            rows_pad = chunk_rows_pad(
                r1 - r0, engine.tiles.block_r, staged_dtype, row_multiple=self._mesh_size
            )
            self._bounds.append((r0, r1))
            self._widths.append(width)
            self._rows_pads.append(rows_pad)
            self._r0s.append(r0)
            n_out_pad = max(n_out_pad, r0 + rows_pad)
            self.padded_slots += rows_pad * width
        self.num_chunks = len(self._bounds)
        self._n_out_pad = n_out_pad

        # Jitted per-chunk kernel SpMV; static over the engine (hashable) so a
        # different accum dtype retraces once per distinct chunk width, not
        # per chunk per call.
        @partial(jax.jit, static_argnames=("eng",))
        def _partial_ell(val, col, x, y, r0, *, eng):
            yk = eng.ell_matvec(val, col, x).astype(y.dtype)
            seg = jax.lax.dynamic_slice(y, (r0,), (yk.shape[0],))
            return jax.lax.dynamic_update_slice(y, seg + yk, (r0,))

        @partial(jax.jit, static_argnames=("eng",))
        def _partial_ell_packed(val, scale, base, dcol, x, y, r0, *, eng):
            yk = eng.packed_ell_matvec(val, scale, base, dcol, x).astype(y.dtype)
            seg = jax.lax.dynamic_slice(y, (r0,), (yk.shape[0],))
            return jax.lax.dynamic_update_slice(y, seg + yk, (r0,))

        self._partial_ell = _partial_ell
        self._partial_ell_packed = _partial_ell_packed

    # ------------------------------ chunk building ------------------------------

    def _build_chunk(self, j: int):
        """Materialize chunk ``j``'s host staging buffers from the source
        CSR/mapping.  Called lazily per staged window (the headline host-
        memory fix: buffers exist only while their window is staged) or once
        per chunk from the eager ``own_data`` pre-pin."""
        arrs = (
            self._build_ell_chunk(j)
            if self.spmv_format == "ell"
            else self._build_coo_chunk(j)
        )
        if not self._built[j]:
            # Conversion census ticks once per chunk per operator lifetime:
            # rebuilding the same window on a later sweep is staging traffic
            # (counted in bytes_staged), not a new layout conversion.
            self._built[j] = True
            self.staging["conversions"] += 1
            count_conversions(1)
        return arrs

    def _build_coo_chunk(self, j: int):
        lo, hi = self._coo_bounds[j]
        indptr = self._csr.indptr
        np_dtype = np.dtype(jnp.dtype(self._dtype))  # bf16 host buffers via ml_dtypes
        # Rows overlapping [lo, hi): repeat each row id by its nnz inside the
        # window — O(window), never the O(nnz) full row array.
        r_lo = int(np.searchsorted(indptr, lo, side="right")) - 1
        r_hi = int(np.searchsorted(indptr, hi, side="left"))
        counts = np.minimum(indptr[r_lo + 1 : r_hi + 1], hi) - np.maximum(
            indptr[r_lo:r_hi], lo
        )
        row = np.repeat(np.arange(r_lo, r_hi, dtype=np.int32), counts)
        pad = self._coo_chunk_nnz - (hi - lo)
        return (
            np.pad(row, (0, pad)),
            np.pad(np.asarray(self._csr.indices[lo:hi]), (0, pad)),
            np.pad(np.asarray(self._csr.data[lo:hi], dtype=np.float64), (0, pad)).astype(
                np_dtype
            ),
        )

    def _build_ell_chunk(self, j: int):
        r0, r1 = self._bounds[j]
        indptr = self._csr.indptr
        lo, hi = int(indptr[r0]), int(indptr[r1])
        local_nnz = self._row_nnz[r0:r1]
        width, rows_pad = self._widths[j], self._rows_pads[j]
        rix = np.repeat(np.arange(r1 - r0), local_nnz)
        pos = np.arange(hi - lo) - np.repeat(np.asarray(indptr[r0:r1]) - lo, local_nnz)
        col = np.zeros((rows_pad, width), dtype=np.int32)
        col[rix, pos] = self._csr.indices[lo:hi]
        if self.staging_mode == "f32":
            np_dtype = np.dtype(jnp.dtype(self._dtype))
            val = np.zeros((rows_pad, width), dtype=np_dtype)
            val[rix, pos] = np.asarray(self._csr.data[lo:hi], dtype=np.float64).astype(
                np_dtype
            )
            return (val, col)
        from ..kernels.spmv_ell_packed import pack_ell_chunk

        val = np.zeros((rows_pad, width), dtype=np.float32)
        val[rix, pos] = self._csr.data[lo:hi]
        return pack_ell_chunk(val, col, self.staging_mode)

    def _plain_chunk_bytes(self, j: int) -> int:
        """Bytes plain (uncompressed) staging would ship for chunk ``j`` —
        the numerator of the compression ratio."""
        if self.spmv_format == "ell":
            slots = self._rows_pads[j] * self._widths[j]
            return slots * (jnp.dtype(self._dtype).itemsize + 4)  # val + int32 col
        return self._coo_chunk_nnz * (8 + jnp.dtype(self._dtype).itemsize)

    # ------------------------------- staging loop -------------------------------

    def _device_put_chunk(self, arrs):
        if self.mesh is None or self.spmv_format != "ell":
            return tuple(jax.device_put(a) for a in arrs)
        from jax.sharding import NamedSharding, PartitionSpec

        # Chunk-resident sharding: rows of the staged window split across the
        # mesh (rows_pad is padded to a mesh multiple), columns replicated.
        sh = NamedSharding(self.mesh, PartitionSpec(self._axis, None))
        return tuple(jax.device_put(a, sh) for a in arrs)

    def _stream(self, consume, start: int = 0):
        """Double-buffered chunk stream: build + stage (device_put) up to
        ``stage_depth`` chunks ahead of the one being consumed; host buffers
        are dropped once their transfer is issued and device references as
        soon as the chunk's partial SpMV is dispatched, so at most
        ``stage_depth + 1`` chunks are resident on either side.  ``start``
        skips already-consumed chunks (mid-step checkpoint resume)."""
        import time as _time

        staged = {}

        def stage(j):
            if j < self.num_chunks and j not in staged:
                _faults.check_chunk_io(j)
                t0 = _time.perf_counter()
                arrs = self._pinned[j] if self._pinned is not None else self._build_chunk(j)
                staged[j] = self._device_put_chunk(arrs)
                self.staging["stage_s"] += _time.perf_counter() - t0
                self.staging["transfers"] += 1
                self.staging["bytes_staged"] += sum(int(a.nbytes) for a in arrs)
                self.staging["bytes_plain"] += self._plain_chunk_bytes(j)

        for i in range(start, self.num_chunks):
            stage(i)
            for j in range(i + 1, min(i + 1 + self.stage_depth, self.num_chunks)):
                stage(j)  # issued while chunk i's compute is in flight
            self.staging["max_resident"] = max(self.staging["max_resident"], len(staged))
            consume(i, staged.pop(i))

    def staging_stats(self) -> dict:
        """Staging counters + derived bandwidth/compression metrics (what
        ``partition["spmv"]["staging"]`` reports)."""
        out = dict(self.staging)
        staged = out["bytes_staged"]
        out["effective_bandwidth_gbps"] = (
            out["bytes_plain"] / out["stage_s"] / 1e9 if out["stage_s"] > 0 else 0.0
        )
        out["compression_ratio"] = out["bytes_plain"] / staged if staged else 1.0
        return out

    # --------------------------------- matvec -----------------------------------

    def _throttle(self, i: int, y) -> None:
        """Bound the async dispatch queue to the staging window.  The host
        loop builds and dispatches chunks far faster than the device drains
        them; without a periodic sync the executor's queue pins EVERY
        dispatched chunk's buffers at once and the ``stage_depth + 1``
        residency contract only holds for the host-side windows.  Blocking
        on the running accumulator once per window retires the chunks behind
        it while the window ahead still overlaps build/transfer/compute."""
        if (i + 1) % (self.stage_depth + 1) == 0:
            jax.block_until_ready(y)

    def set_step_hook(self, hook):
        """Install ``hook(chunk_index, partial_accumulator)`` to observe the
        running accumulator of the *next* matvec after each consumed chunk
        (the chunk-cursor checkpoint writer).  One-per-step: the caller
        reinstalls before each step."""
        self._step_hook = hook

    def set_resume(self, start_chunk: int, partial_y):
        """Arm the next matvec to skip chunks ``< start_chunk`` and seed its
        accumulator from ``partial_y`` (chunk-cursor checkpoint restore).
        Consumed by exactly one matvec call."""
        self._resume = (int(start_chunk), partial_y)

    def matvec(self, x, accum_dtype=None, *, start_chunk: int = 0, partial_y=None,
               on_chunk=None):
        """Streamed SpMV.  ``start_chunk``/``partial_y`` resume a partially
        accumulated product from a mid-step checkpoint (chunks are consumed
        in a fixed order, so resuming from the saved partial is bit-identical
        to an uninterrupted sweep); ``on_chunk(i, y)`` observes the running
        accumulator after each chunk (the checkpoint writer hook)."""
        if start_chunk == 0 and partial_y is None and self._resume is not None:
            start_chunk, partial_y = self._resume
            self._resume = None
        if on_chunk is None:
            on_chunk = self._step_hook
        acc = jnp.dtype(accum_dtype or self._dtype)
        if self.spmv_format == "ell":
            import dataclasses as _dc

            eng = self.engine
            if jnp.dtype(eng.accum_dtype) != acc:
                eng = _dc.replace(eng, accum_dtype=acc)
            if partial_y is not None:
                y = [jnp.asarray(partial_y, acc)]
            else:
                y = [jnp.zeros((self._n_out_pad,), acc)]

            packed = self.staging_mode != "f32"

            def consume(i, arrs):
                r0 = jnp.asarray(self._r0s[i], jnp.int32)
                if packed:
                    val, scale, base, dcol = arrs
                    y[0] = self._sharded_or_local_packed(
                        val, scale, base, dcol, x, y[0], r0, eng
                    )
                else:
                    val, col = arrs
                    y[0] = self._sharded_or_local_plain(val, col, x, y[0], r0, eng)
                self._throttle(i, y[0])
                if on_chunk is not None:
                    on_chunk(i, y[0])

            self._stream(consume, start=start_chunk)
            return y[0][: self.n]
        y = [
            jnp.asarray(partial_y, acc)
            if partial_y is not None
            else jnp.zeros((self.n,), acc)
        ]

        def consume(i, arrs):
            row, col, val = arrs
            y[0] = self._partial_spmv(row, col, val, x, y[0], acc=acc)
            self._throttle(i, y[0])
            if on_chunk is not None:
                on_chunk(i, y[0])

        self._stream(consume, start=start_chunk)
        return y[0]

    # ------------------------- sharded partial dispatch -------------------------

    def _shard_fn(self, eng, packed: bool):
        """shard_map-wrapped per-chunk partial SpMV: the kernel runs on each
        device's row slice of the staged chunk, with ``x`` replicated — the
        composition of out-of-core staging and the paper's multi-device
        partition.  Cached per (engine, kind) since shard_map closures are
        rebuilt otherwise."""
        key = (eng, packed)
        cache = getattr(self, "_shard_fns", None)
        if cache is None:
            cache = self._shard_fns = {}
        if key not in cache:
            from jax.sharding import PartitionSpec as P

            # lazy: avoids an import cycle
            from .distributed import _SHARD_MAP_KW, _shard_map

            ax = self._axis
            if packed:

                def local(val, scale, base, dcol, x):
                    return eng.packed_ell_matvec(val, scale, base, dcol, x)

                in_specs = (P(ax, None),) * 4 + (P(),)
            else:

                def local(val, col, x):
                    return eng.ell_matvec(val, col, x)

                in_specs = (P(ax, None), P(ax, None), P())
            cache[key] = jax.jit(
                _shard_map(
                    local, mesh=self.mesh, in_specs=in_specs, out_specs=P(ax),
                    **_SHARD_MAP_KW,
                )
            )
        return cache[key]

    def _sharded_or_local_plain(self, val, col, x, y, r0, eng):
        if self.mesh is None:
            return self._partial_ell(val, col, x, y, r0, eng=eng)
        yk = self._shard_fn(eng, packed=False)(val, col, x).astype(y.dtype)
        # Gather the row-sharded partial onto the accumulator's placement: a
        # mesh with explicit axes refuses to mix the two shardings.
        yk = jax.device_put(yk, y.sharding)
        seg = jax.lax.dynamic_slice(y, (r0,), (yk.shape[0],))
        return jax.lax.dynamic_update_slice(y, seg + yk, (r0,))

    def _sharded_or_local_packed(self, val, scale, base, dcol, x, y, r0, eng):
        if self.mesh is None:
            return self._partial_ell_packed(val, scale, base, dcol, x, y, r0, eng=eng)
        yk = self._shard_fn(eng, packed=True)(val, scale, base, dcol, x).astype(y.dtype)
        # Gather the row-sharded partial onto the accumulator's placement: a
        # mesh with explicit axes refuses to mix the two shardings.
        yk = jax.device_put(yk, y.sharding)
        seg = jax.lax.dynamic_slice(y, (r0,), (yk.shape[0],))
        return jax.lax.dynamic_update_slice(y, seg + yk, (r0,))


@dataclasses.dataclass
class CallableOperator(LinearOperator):
    """Wrap a bare symmetric matvec callable ``fn(x) -> A @ x``.

    This is how the ``eigsh`` frontend accepts matrix-free problems (scipy's
    ``LinearOperator`` or any function): the callable is treated as a black
    box, so the mixed-precision policy governs only the surrounding Lanczos
    arithmetic, not the matvec interior.

    The Lanczos loop runs under ``jit``, so a callable that computes in
    NumPy (e.g. a scipy ``LinearOperator``) cannot be traced.  We probe
    traceability once with ``jax.eval_shape``: traceable callables are
    inlined into the compiled loop; host callables are bridged with
    ``jax.pure_callback`` (one device<->host round-trip per matvec — the
    same placement cost scipy's ARPACK wrapper pays).
    """

    fn: Callable[[jax.Array], jax.Array]
    n: int

    def __post_init__(self):
        try:
            out = jax.eval_shape(self.fn, jax.ShapeDtypeStruct((self.n,), jnp.float32))
        except Exception:
            self._traceable = False
        else:
            if out.shape != (self.n,):
                raise ValueError(
                    f"matvec callable returned shape {out.shape}, expected ({self.n},)"
                )
            self._traceable = True

    def matvec(self, x, accum_dtype=None):
        if self._traceable:
            y = jnp.asarray(self.fn(x))
        else:
            spec = jax.ShapeDtypeStruct((self.n,), x.dtype)
            y = jax.pure_callback(
                lambda xv: np.asarray(self.fn(xv), dtype=xv.dtype), spec, x
            )
        return y.astype(accum_dtype) if accum_dtype is not None else y


class HvpOperator(LinearOperator):
    """Matrix-free Hessian-vector product of ``loss(params)`` (framework
    integration of the paper's solver; see training/spectral.py)."""

    def __init__(self, loss_fn: Callable, params, ggn: bool = False):
        self._loss = loss_fn
        self._params = params
        flat, unravel = jax.flatten_util.ravel_pytree(params)
        self._flat0 = flat
        self._unravel = unravel
        self.n = flat.shape[0]

        def hvp(v):
            # reverse-over-reverse: H v = d/dp <grad(loss)(p), v>.  (Forward-
            # over-reverse is cheaper but jvp does not compose with the
            # custom_vjp embedding lookup in the model zoo.)
            def gv(flat_p):
                g = jax.flatten_util.ravel_pytree(jax.grad(loss_fn)(unravel(flat_p)))[0]
                return jnp.vdot(g, v)

            return jax.grad(gv)(flat)

        self._hvp = jax.jit(hvp)

    def matvec(self, x, accum_dtype=None):
        y = self._hvp(x.astype(self._flat0.dtype))
        return y.astype(accum_dtype) if accum_dtype else y


def make_operator(
    csr: CSR,
    impl: str = "coo",
    dtype=jnp.float32,
    engine: Optional[SpmvEngine] = None,
) -> LinearOperator:
    """Build a solver operator for an explicit sparse matrix.

    With an :class:`SpmvEngine`, the engine's chosen format drives the device
    container and the kernel tile parameters (``impl`` is ignored); otherwise
    ``impl`` picks the legacy fixed path.
    """
    if engine is not None:
        if engine.format == "ell":
            mat = to_device_ell(
                csr, dtype=dtype, row_tile=engine.tiles.block_r, slot_tile=128
            )
        elif engine.format == "bsr":
            mat = to_device_bsr(csr, block_size=engine.tiles.block_size, dtype=dtype)
        elif engine.format == "hybrid":
            # Reuse the cap the selection statistics were computed with, so
            # the built layout matches the overhead the selector accepted.
            cap = max(s.hyb_width for s in engine.stats) if engine.stats else None
            mat = to_device_hybrid(
                csr, dtype=dtype, width_cap=cap, row_tile=engine.tiles.block_r
            )
        else:
            mat = to_device_coo(csr, dtype=dtype)
        return SparseOperator(mat, impl="engine", engine=engine)
    if impl == "coo":
        return SparseOperator(to_device_coo(csr, dtype=dtype), impl="coo")
    if impl in ("ell", "ell_kernel"):
        return SparseOperator(to_device_ell(csr, dtype=dtype), impl=impl)
    if impl == "bsr_kernel":
        from ..kernels.spmv_bsr import blocked_ell_from_csr

        return SparseOperator(blocked_ell_from_csr(csr, dtype=dtype), impl=impl)
    if impl == "chunked":
        return ChunkedOperator(csr, dtype=dtype)
    raise ValueError(f"unknown operator impl {impl!r}")
