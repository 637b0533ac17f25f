"""Sparse matrix containers used by the eigensolver.

Host-side construction is NumPy (CSR); device-side compute formats are:

* ``DeviceCOO``  — (row, col, val) triplets, the pure-jnp ``segment_sum`` SpMV
  reference path; also the per-shard format of the distributed solver.
* ``DeviceELL``  — row-tiled ELLPACK (uniform width, padded), a gather and a
  row sum (DESIGN.md §4); ``pack_ell_chunk`` compresses host ELL chunks for
  out-of-core staging.
* ``DeviceBSR``  — blocked-ELL (uniform block-slots per block-row, padded):
  a gather of ``x`` blocks and small dense products.
* ``DeviceHybrid`` — hub-row split: ELL capped at a quantile of the row
  lengths plus a COO overflow tail (``segment_sum``), so power-law matrices
  keep bounded padding.
* ``DeviceSELL`` — row-length-bucketed ELL: rows cut into pieces of at most
  ``ROW_BLOCK`` entries, sorted by length into width classes, each padded
  only to its own width and stored slot-major in flat 1-D arrays; the SpMV
  is one gather (the ``sell_gather`` Mosaic kernel where ``x`` fits VMEM),
  dense per-class sums and one scatter of one sum per piece (the compiled
  layout, see ``kernels/engine.py``).

All device containers are registered pytrees so they can cross ``jit`` /
``shard_map`` boundaries.  The ``shard_to_*`` converters build *shard-local*
layouts (uniform shapes across shards, columns remapped to the
padded-global coordinates of ``core/partition.py``) so the distributed
engine's hot loop runs the ELL / BSR / hybrid bodies instead of
``segment_sum``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

__all__ = [
    "CSR",
    "DeviceCOO",
    "DeviceELL",
    "DeviceBSR",
    "DeviceHybrid",
    "DeviceSELL",
    "csr_from_coo",
    "to_device_coo",
    "to_device_ell",
    "to_device_bsr",
    "blocked_ell_from_csr",
    "PACKED_VALUE_DTYPES",
    "SCALE_BLOCK_ROWS",
    "pack_ell_chunk",
    "to_device_hybrid",
    "to_device_sell",
    "sell_classes",
    "sell_stored_slots",
    "row_sums",
    "ell_padding_stats",
    "blocked_ell_from_triplets",
    "padded_col_map",
    "shard_to_ell",
    "shard_to_blocked_ell",
    "shard_to_hybrid",
    "conversion_count",
    "count_conversions",
]

# Process-wide census of host->device format conversions (one tick per
# converted layout: a device container, a shard set, a pinned chunk).  The
# plan/execute split (api/session.py) is *verified* against this counter —
# a cache-hit solve must leave it untouched — so every conversion entry
# point below ticks it.
_CONVERSIONS = {"count": 0}


def conversion_count() -> int:
    """Total format conversions performed by this process so far."""
    return _CONVERSIONS["count"]


def count_conversions(n: int = 1) -> None:
    _CONVERSIONS["count"] += int(n)


@dataclasses.dataclass
class CSR:
    """Host-side CSR (NumPy). Always square, symmetric matrices here."""

    indptr: np.ndarray  # (n+1,) int64
    indices: np.ndarray  # (nnz,) int32
    data: np.ndarray  # (nnz,) float64
    shape: Tuple[int, int]

    @property
    def n(self) -> int:
        return self.shape[0]

    @property
    def nnz(self) -> int:
        return int(self.indices.shape[0])

    def row_nnz(self) -> np.ndarray:
        return np.diff(self.indptr)

    def to_scipy(self):
        import scipy.sparse as sp

        return sp.csr_matrix((self.data, self.indices, self.indptr), shape=self.shape)

    def toarray(self) -> np.ndarray:
        return self.to_scipy().toarray()


def csr_from_coo(
    rows: np.ndarray, cols: np.ndarray, vals: np.ndarray, n: int, sum_dups: bool = True
) -> CSR:
    """Build CSR from COO triplets (NumPy), summing duplicates."""
    import scipy.sparse as sp

    m = sp.coo_matrix((vals, (rows, cols)), shape=(n, n))
    if sum_dups:
        m.sum_duplicates()
    m = m.tocsr()
    m.sort_indices()
    return CSR(
        indptr=m.indptr.astype(np.int64),
        indices=m.indices.astype(np.int32),
        data=m.data.astype(np.float64),
        shape=(n, n),
    )


# row_sums reduces blocks of this many consecutive COO entries as a tree.
ROW_BLOCK = 1024


def row_sums(prod: jax.Array, rows: jax.Array, n_rows: int) -> jax.Array:
    """``segment_sum(prod, rows, n_rows)`` that stays accurate on long rows.

    A scatter-add accumulates the entries of one output row one after
    another: in f32 the 3.57M-entry hub row of the Wikipedia matrix comes
    out ~1e-3 off (on the CPU, and in a four-chip TPU solve).  Here each
    block of ``ROW_BLOCK`` consecutive entries that lies inside one row is
    summed as a tree and scattered as one partial; the other entries are
    scattered as before.  Correct for any order of ``rows``; in the
    row-sorted layouts built here a long row fills single-row blocks, so it
    accumulates ~len / ROW_BLOCK partials plus two blocks of entries.
    """
    p = prod.shape[0]
    if p <= ROW_BLOCK:
        return jax.ops.segment_sum(prod, rows, num_segments=n_rows)
    pad = (-p) % ROW_BLOCK
    pb = jnp.pad(prod, (0, pad)).reshape(-1, ROW_BLOCK)
    rb = jnp.pad(rows, (0, pad), mode="edge").reshape(-1, ROW_BLOCK)
    one_row = jnp.all(rb == rb[:, :1], axis=1)
    loose = jnp.where(one_row[:, None], jnp.zeros_like(pb), pb).reshape(-1)
    y = jax.ops.segment_sum(loose, rb.reshape(-1), num_segments=n_rows)
    partial = jnp.where(one_row, pb.sum(axis=1), jnp.zeros((), pb.dtype))
    return y + jax.ops.segment_sum(partial, rb[:, 0], num_segments=n_rows)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DeviceCOO:
    """Device COO triplets; SpMV = segment_sum(val * x[col], row)."""

    row: jax.Array  # (nnz,) int32, sorted by row
    col: jax.Array  # (nnz,) int32
    val: jax.Array  # (nnz,) storage dtype
    n_rows: int  # static
    n_cols: int  # static

    def tree_flatten(self):
        return (self.row, self.col, self.val), (self.n_rows, self.n_cols)

    @classmethod
    def tree_unflatten(cls, aux, children):
        row, col, val = children
        return cls(row, col, val, *aux)

    @property
    def nnz(self) -> int:
        return int(self.val.shape[0])

    def matvec(self, x: jax.Array, accum_dtype=None) -> jax.Array:
        """SpMV with accumulation in ``accum_dtype`` (mixed-precision knob)."""
        acc = accum_dtype or self.val.dtype
        prod = self.val.astype(acc) * jnp.take(x, self.col).astype(acc)
        return row_sums(prod, self.row, self.n_rows)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DeviceELL:
    """Uniform-width ELLPACK, row-major, padded.

    ``val[r, s]`` / ``col[r, s]``: s-th stored entry of row r.  Padding slots
    have ``val == 0`` and ``col == 0`` (they contribute 0).  Rows are padded to
    a multiple of ``row_tile`` and the width to a multiple of ``slot_tile``
    (the engine's :class:`~repro.kernels.engine.TileConfig`).
    """

    val: jax.Array  # (rows_padded, width) storage dtype
    col: jax.Array  # (rows_padded, width) int32
    n_rows: int  # logical rows (static)
    n_cols: int  # static

    def tree_flatten(self):
        return (self.val, self.col), (self.n_rows, self.n_cols)

    @classmethod
    def tree_unflatten(cls, aux, children):
        val, col = children
        return cls(val, col, *aux)

    @property
    def width(self) -> int:
        return int(self.val.shape[1])

    def matvec(self, x: jax.Array, accum_dtype=None) -> jax.Array:
        acc = accum_dtype or self.val.dtype
        gathered = jnp.take(x, self.col).astype(acc)  # (rows_padded, width)
        y = (self.val.astype(acc) * gathered).sum(axis=1)
        return y[: self.n_rows]


def _row_positions(csr: CSR) -> Tuple[np.ndarray, np.ndarray]:
    """(row index, position-within-row) of every stored nnz, in CSR order —
    the scatter coordinates every padded-layout conversion below shares."""
    row_nnz = csr.row_nnz()
    rix = np.repeat(np.arange(csr.n, dtype=np.int64), row_nnz)
    pos = np.arange(csr.nnz) - np.repeat(csr.indptr[:-1], row_nnz)
    return rix, pos


def to_device_coo(csr: CSR, dtype=jnp.float32) -> DeviceCOO:
    n = csr.n
    count_conversions()
    row = np.repeat(np.arange(n, dtype=np.int32), csr.row_nnz())
    return DeviceCOO(
        row=jnp.asarray(row),
        col=jnp.asarray(csr.indices, dtype=jnp.int32),
        val=jnp.asarray(csr.data, dtype=dtype),
        n_rows=n,
        n_cols=n,
    )


def to_device_ell(
    csr: CSR, dtype=jnp.float32, row_tile: int = 8, slot_tile: int = 128
) -> DeviceELL:
    """Convert CSR to uniform-width padded ELL."""
    n = csr.n
    count_conversions()
    nnz_per_row = csr.row_nnz()
    width = int(max(1, nnz_per_row.max()))
    width = -(-width // slot_tile) * slot_tile
    rows_pad = -(-n // row_tile) * row_tile

    val = np.zeros((rows_pad, width), dtype=np.float64)
    col = np.zeros((rows_pad, width), dtype=np.int32)
    rix, pos = _row_positions(csr)  # vectorized fill coordinates
    val[rix, pos] = csr.data
    col[rix, pos] = csr.indices
    return DeviceELL(
        val=jnp.asarray(val, dtype=dtype),
        col=jnp.asarray(col),
        n_rows=n,
        n_cols=n,
    )


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DeviceHybrid:
    """Hub-row split: capped-width ELL + COO overflow tail.

    Every row stores its first ``width`` entries in the uniform ELL arrays
    (``val == 0`` / ``col == 0`` on padding slots); entries past the cap —
    the hub rows' overflow — live as COO triplets.  SpMV is the ELL body over
    the bounded part plus one ``segment_sum`` over the tail, so
    the padding cost is ``n * width_cap`` instead of ``n * max_row_nnz``.
    Tail arrays are zero-padded (row 0, col 0, val 0 contributes nothing).
    """

    ell_val: jax.Array  # (rows_padded, width_cap) storage dtype
    ell_col: jax.Array  # (rows_padded, width_cap) int32
    tail_row: jax.Array  # (tail_padded,) int32 — output row of each overflow nnz
    tail_col: jax.Array  # (tail_padded,) int32
    tail_val: jax.Array  # (tail_padded,) storage dtype
    n_rows: int  # logical rows (static)
    n_cols: int  # static

    def tree_flatten(self):
        children = (self.ell_val, self.ell_col, self.tail_row, self.tail_col, self.tail_val)
        return children, (self.n_rows, self.n_cols)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    @property
    def width(self) -> int:
        return int(self.ell_val.shape[1])

    @property
    def tail_slots(self) -> int:
        return int(self.tail_val.shape[0])

    def matvec(self, x: jax.Array, accum_dtype=None) -> jax.Array:
        """jnp reference SpMV (the engine's body is ``kernels/engine.py``)."""
        acc = accum_dtype or self.ell_val.dtype
        gathered = jnp.take(x, self.ell_col).astype(acc)
        y = (self.ell_val.astype(acc) * gathered).sum(axis=1)[: self.n_rows]
        prod = self.tail_val.astype(acc) * jnp.take(x, self.tail_col).astype(acc)
        return y + row_sums(prod, self.tail_row, self.n_rows)


def to_device_hybrid(
    csr: CSR,
    dtype=jnp.float32,
    width_cap: Optional[int] = None,
    quantile: Optional[float] = None,
    row_tile: int = 8,
    slot_tile: int = 8,
    tail_align: int = 8,
) -> DeviceHybrid:
    """Convert CSR to the hub-split hybrid layout (capped ELL + COO tail).

    ``width_cap`` pins the ELL width (the engine passes the cap its selection
    statistics used); by default it is the ``quantile`` of the row lengths
    (``kernels.engine.hybrid_width_cap`` — env-tunable via
    ``REPRO_SPMV_HYBRID_Q``).  ``slot_tile`` aligns the capped width (kept
    small by default: a 128-lane pad would reinflate exactly the padding the
    split exists to avoid).
    """
    from ..kernels.engine import hybrid_width_cap  # lazy: sparse sits below kernels

    n = csr.n
    count_conversions()
    row_nnz = csr.row_nnz()
    cap = hybrid_width_cap(row_nnz, quantile) if width_cap is None else int(width_cap)
    cap = max(1, min(cap, int(row_nnz.max()) if row_nnz.size else 1))
    width = -(-cap // slot_tile) * slot_tile
    rows_pad = -(-n // row_tile) * row_tile

    rix, pos = _row_positions(csr)
    keep = pos < width  # padded cap: the aligned slots might as well hold nnz
    val = np.zeros((rows_pad, width), dtype=np.float64)
    col = np.zeros((rows_pad, width), dtype=np.int32)
    val[rix[keep], pos[keep]] = csr.data[keep]
    col[rix[keep], pos[keep]] = csr.indices[keep]

    spill = ~keep
    tail_n = int(spill.sum())
    tail_pad = -(-max(tail_n, 1) // tail_align) * tail_align
    trow = np.zeros((tail_pad,), dtype=np.int32)
    tcol = np.zeros((tail_pad,), dtype=np.int32)
    tval = np.zeros((tail_pad,), dtype=np.float64)
    trow[:tail_n] = rix[spill]
    tcol[:tail_n] = csr.indices[spill]
    tval[:tail_n] = csr.data[spill]
    return DeviceHybrid(
        ell_val=jnp.asarray(val, dtype=dtype),
        ell_col=jnp.asarray(col),
        tail_row=jnp.asarray(trow),
        tail_col=jnp.asarray(tcol),
        tail_val=jnp.asarray(tval, dtype=dtype),
        n_rows=n,
        n_cols=n,
    )


# Width classes of the bucketed layout: every piece length up to SELL_EXACT
# is a class of its own; above it each class bound is at most SELL_GROWTH
# times the one below, so no piece is padded by more than 12.5%.
SELL_EXACT = 16
SELL_GROWTH = 1.125
# The flat slot arrays are padded to a multiple of this (the gather kernel's
# lane-dense rows of 128 slots); the classes' slots come first.
SELL_SLOT_ALIGN = 128


def sell_stored_slots(slots: int) -> int:
    """Length of the flat arrays holding ``slots`` class slots."""
    return -(-int(slots) // SELL_SLOT_ALIGN) * SELL_SLOT_ALIGN


def sell_classes(
    row_nnz: np.ndarray,
) -> Tuple[np.ndarray, np.ndarray, Tuple[Tuple[int, int], ...]]:
    """Pieces and width classes of the bucketed layout.

    Every row is cut into pieces of at most ``ROW_BLOCK`` consecutive
    entries; empty rows have none.  Returns ``(row, first, classes)``:
    ``row`` and ``first`` give, for each piece sorted by length (stable),
    its row and the position of its first entry within the row, and
    ``classes`` one ``(width, pieces)`` pair per non-empty class, in that
    order; a class's width is its longest piece.
    """
    lens = np.asarray(row_nnz, dtype=np.int64)
    cuts = -(-lens // ROW_BLOCK)
    row = np.repeat(np.arange(lens.size, dtype=np.int64), cuts)
    first = (np.arange(row.size) - np.repeat(np.cumsum(cuts) - cuts, cuts)) * ROW_BLOCK
    plen = np.minimum(lens[row] - first, ROW_BLOCK)
    order = np.argsort(plen, kind="stable")
    row, first, plen = row[order], first[order], plen[order]
    if not row.size:
        return row, first, ()
    top = int(plen[-1])
    bounds = list(range(1, min(top, SELL_EXACT) + 1))
    while bounds[-1] < top:
        bounds.append(max(bounds[-1] + 1, int(bounds[-1] * SELL_GROWTH)))
    cls = np.searchsorted(np.asarray(bounds), plen)
    stops = np.append(np.flatnonzero(np.diff(cls)) + 1, plen.size)
    starts = np.append(0, stops[:-1])
    classes = tuple((int(plen[b - 1]), int(b - a)) for a, b in zip(starts, stops))
    return row, first, classes


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DeviceSELL:
    """Row-length-bucketed ELL: width classes, each padded to its own width.

    Rows are cut into pieces of at most ``ROW_BLOCK`` entries
    (:func:`sell_classes`).  Class ``c`` holds ``rows`` pieces, each padded
    to ``width`` slots and stored slot-major from ``offset`` on: slot ``s``
    of its piece ``p`` at ``offset + s * rows + p`` of the flat ``col`` /
    ``val``, so a class sums across its pieces, 128 to a vector register.
    Padding slots have ``val == 0`` and ``col == 0``; they include the tail
    that pads the flat arrays to a multiple of ``SELL_SLOT_ALIGN`` after the
    last class.  ``order`` holds each piece's row; a row longer than
    ``ROW_BLOCK`` has several.  Every array
    the SpMV reads is 1-D: a ``(rows, width)`` array would be laid out 128
    lanes wide on a TPU and bring the padding back.  The cut bounds every
    class's width by ``ROW_BLOCK``: with whole hub rows as classes (up to
    3.57M slots wide) the TPU compiler did not finish the Wikipedia
    matrix's SpMV in ten minutes.
    """

    col: jax.Array  # (sell_stored_slots(slots),) int32
    val: jax.Array  # (sell_stored_slots(slots),) storage dtype
    order: jax.Array  # (pieces,) int32: the row each stored piece belongs to
    classes: Tuple[Tuple[int, int, int], ...]  # static (width, pieces, offset)
    n_rows: int  # static
    n_cols: int  # static
    nnz: int  # static: stored entries, padding excluded

    def tree_flatten(self):
        return (self.col, self.val, self.order), (self.classes, self.n_rows, self.n_cols, self.nnz)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    @property
    def slots(self) -> int:
        """The classes' slots (the aligned tail is not counted)."""
        return sum(w * r for w, r, _ in self.classes)

    def summary(self) -> dict:
        """Classes, slots and padding of the built layout."""
        return {
            "classes": len(self.classes),
            "slots": self.slots,
            "slots_per_nnz": self.slots / max(1, self.nnz),
        }

    def gather_executor(self, x_dtype, interpret: Optional[bool] = None) -> str:
        """What gathers ``x[col]`` for an ``x`` of ``x_dtype``: ``"mosaic"``
        or ``"pallas_interpret"`` (the ``sell_gather`` kernel, where ``x``
        is 32-bit float and fits VMEM) or ``"xla"`` (``jnp.take``).
        ``interpret`` None means the default backend's mode."""
        from ..kernels import sell_gather  # lazy: sparse sits below kernels
        from ..kernels.ops import default_interpret

        interp = default_interpret() if interpret is None else interpret
        return sell_gather.executor(self.n_cols, x_dtype, int(self.col.shape[0]), interp)

    def matvec(
        self, x: jax.Array, accum_dtype=None, interpret: Optional[bool] = None
    ) -> jax.Array:
        """One gather over all slots, a dense sum per class, and one
        scatter-add of the pieces' sums into their rows: a row longer than
        ``ROW_BLOCK`` adds up its pieces' partials, as ``row_sums`` does.
        The gather is the ``sell_gather`` kernel or ``jnp.take``, as
        :meth:`gather_executor` decides: the same bits either way."""
        acc = accum_dtype or self.val.dtype
        y = jnp.zeros((self.n_rows,), acc)
        if not self.classes:
            return y
        how = self.gather_executor(x.dtype, interpret)
        if how == "xla":
            xg = jnp.take(x, self.col)
        else:
            from ..kernels.sell_gather import sell_gather

            xg = sell_gather(x, self.col, interpret=how == "pallas_interpret")
        prod = self.val.astype(acc) * xg.astype(acc)
        # The barrier keeps each class's reshape on its own slice: left free,
        # the TPU compiler rewrote a two-piece class's slice-and-reshape as a
        # reshape of all the products to (slots / 2, 2), 64 lanes of padding
        # to each value (8.4 GB on the kron-s20 matrix).
        sums = [
            jax.lax.optimization_barrier(prod[off : off + width * rows])
            .reshape(width, rows)
            .sum(axis=0)
            for width, rows, off in self.classes
        ]
        return y.at[self.order].add(jnp.concatenate(sums))


def to_device_sell(csr: CSR, dtype=jnp.float32) -> DeviceSELL:
    """Convert CSR to the row-length-bucketed layout, class by class (host
    temporaries stay within one class; values go straight to ``dtype``)."""
    count_conversions()
    row_nnz = csr.row_nnz()
    row, first, classes = sell_classes(row_nnz)
    slots = sell_stored_slots(sum(w * r for w, r in classes))
    col = np.zeros((slots,), dtype=np.int32)
    val = np.zeros((slots,), dtype=jnp.dtype(dtype))
    meta = []
    off = done = 0
    for width, rows in classes:
        piece = slice(done, done + rows)
        s = np.arange(width, dtype=np.int64)[:, None]
        mask = s < (row_nnz[row[piece]] - first[piece])[None, :]  # (width, rows)
        src = (csr.indptr[row[piece]] + first[piece])[None, :] + s
        dst = off + np.flatnonzero(mask)
        col[dst] = csr.indices[src[mask]]
        val[dst] = csr.data[src[mask]]
        meta.append((width, rows, off))
        off += width * rows
        done += rows
    return DeviceSELL(
        col=jnp.asarray(col),
        val=jnp.asarray(val, dtype=dtype),
        order=jnp.asarray(row.astype(np.int32)),
        classes=tuple(meta),
        n_rows=csr.n,
        n_cols=csr.n,
        nnz=csr.nnz,
    )


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DeviceBSR:
    """Blocked-ELL ("BSR-style"): dense (BS, BS) blocks at sparse block
    coordinates, uniform slot count per block-row, zero-padded.

    ``val[i, s]`` is the s-th stored block of block-row i; ``bcol[i, s]`` its
    block-column (0 on padding slots — the zero block makes padding inert).
    ``SpmvEngine.bsr_matvec`` runs it (one gather of blocks of ``x``).
    """

    val: jax.Array  # (n_block_rows, slots, BS, BS) storage dtype
    bcol: jax.Array  # (n_block_rows, slots) int32
    n_rows: int  # logical rows (static)
    n_cols: int  # static

    def tree_flatten(self):
        return (self.val, self.bcol), (self.n_rows, self.n_cols)

    @classmethod
    def tree_unflatten(cls, aux, children):
        val, bcol = children
        return cls(val, bcol, *aux)

    @property
    def block_size(self) -> int:
        return int(self.val.shape[2])

    @property
    def slots(self) -> int:
        return int(self.val.shape[1])

    def matvec(self, x: jax.Array, accum_dtype=None) -> jax.Array:
        """jnp reference SpMV (the engine's body is ``kernels/engine.py``)."""
        acc = accum_dtype or self.val.dtype
        nbr, slots, bs, _ = self.val.shape
        if x.shape[0] % bs:
            x = jnp.pad(x, (0, bs - x.shape[0] % bs))
        gathered = jnp.take(x.reshape(-1, bs), self.bcol, axis=0)  # (nbr, slots, bs)
        y = jnp.einsum("rsij,rsj->ri", self.val.astype(acc), gathered.astype(acc))
        return y.reshape(nbr * bs)[: self.n_rows]


def ell_padding_stats(row_nnz: np.ndarray) -> dict:
    """Padding cost of an ELL layout over rows with the given nnz counts:
    ``overhead`` = stored slots / nnz (1.0 = perfectly uniform rows)."""
    nnz = int(row_nnz.sum())
    width = int(row_nnz.max()) if row_nnz.size else 0
    return {
        "width": width,
        "mean_row_nnz": nnz / max(1, row_nnz.size),
        "overhead": (width * int(row_nnz.size)) / max(1, nnz),
    }


def blocked_ell_from_triplets(
    rows: np.ndarray,
    cols: np.ndarray,
    vals: np.ndarray,
    n_rows: int,
    n_cols: int,
    block_size: int = 8,
    slots: Optional[int] = None,
    dtype=jnp.float32,
) -> DeviceBSR:
    """Build a blocked-ELL layout from COO triplets (host, vectorized).

    ``slots`` forces a uniform slot count (>= the required maximum) so shards
    of a distributed solve share one shape; None sizes it to this matrix.
    """
    bs = block_size
    nbr = max(1, -(-n_rows // bs))
    nbc = max(1, -(-n_cols // bs))
    br = rows.astype(np.int64) // bs
    bc = cols.astype(np.int64) // bs
    keys = np.unique(br * nbc + bc)  # sorted: groups contiguous per block-row
    kbr = keys // nbc
    counts = np.bincount(kbr, minlength=nbr)
    needed = int(counts.max()) if keys.size else 1
    if slots is None:
        slots = max(1, needed)
    elif slots < needed:
        raise ValueError(f"slots={slots} < required {needed}")

    val = np.zeros((nbr, slots, bs, bs), dtype=np.float64)
    bcol = np.zeros((nbr, slots), dtype=np.int32)
    if keys.size:
        # Slot index of each stored block = its rank within its block-row.
        first = np.searchsorted(kbr, np.arange(nbr), side="left")
        slot_of_key = np.arange(keys.size) - first[kbr]
        bcol[kbr, slot_of_key] = (keys % nbc).astype(np.int32)
        # Scatter nnz into their block slot (CSR inputs are deduplicated).
        kidx = np.searchsorted(keys, br * nbc + bc)
        val[br, slot_of_key[kidx], rows % bs, cols % bs] = vals
    return DeviceBSR(
        val=jnp.asarray(val, dtype=dtype),
        bcol=jnp.asarray(bcol),
        n_rows=n_rows,
        n_cols=n_cols,
    )


def to_device_bsr(csr: CSR, block_size: int = 8, dtype=jnp.float32) -> DeviceBSR:
    """Convert CSR to the blocked-ELL/BSR layout."""
    count_conversions()
    rows = np.repeat(np.arange(csr.n, dtype=np.int64), csr.row_nnz())
    return blocked_ell_from_triplets(
        rows, csr.indices, csr.data, csr.n, csr.n, block_size=block_size, dtype=dtype
    )


def blocked_ell_from_csr(csr: CSR, block_size: int = 8, dtype=jnp.float32):
    """CSR -> ``(val, bcol, n_rows)``: the :class:`DeviceBSR` arrays as a tuple."""
    bsr = to_device_bsr(csr, block_size=block_size, dtype=dtype)
    return bsr.val, bsr.bcol, bsr.n_rows


# Compressed out-of-core staging: staging-mode name -> narrow storage dtype
# of the packed ELL values (``SpmvEngine.packed_ell_matvec`` runs them).
PACKED_VALUE_DTYPES = {
    "bf16": np.dtype(ml_dtypes.bfloat16),
    "fp8": np.dtype(ml_dtypes.float8_e4m3fn),
}
# Rows sharing one quantization scale (the "per-row-block" granularity).
SCALE_BLOCK_ROWS = 8


def pack_ell_chunk(val: np.ndarray, col: np.ndarray, mode: str):
    """Quantize + delta-encode one host-side ELL chunk.

    Returns host arrays ``(val_packed, scale, base, dcol)``: values in the
    mode's narrow dtype, ``scale`` (rows, 1) f32 computed over row blocks of
    ``SCALE_BLOCK_ROWS`` rows (max-abs mapped to the dtype's finite range
    for fp8; bf16 shares f32's exponent range so its scale is 1) and
    expanded per row, ``base`` (rows, 1) int32 first stored column of each
    row, and ``dcol`` the column deltas (``dcol[r, 0] == 0``), narrowed to
    int16 when every delta fits, else int32.  The columns are recovered as
    ``base + cumsum(dcol, axis=1)``.
    """
    vdt = PACKED_VALUE_DTYPES.get(mode)
    if vdt is None:
        raise ValueError(
            f"unknown packed staging mode {mode!r}; expected {tuple(PACKED_VALUE_DTYPES)}"
        )
    rows, width = val.shape
    if rows % SCALE_BLOCK_ROWS:
        raise ValueError(
            f"packed chunk rows {rows} must be a multiple of {SCALE_BLOCK_ROWS}"
        )
    v64 = np.asarray(val, dtype=np.float64)
    if mode == "fp8":
        absmax = np.abs(v64).reshape(rows // SCALE_BLOCK_ROWS, -1).max(axis=1)
        fmax = float(ml_dtypes.finfo(vdt).max)
        block_scale = np.where(absmax > 0, absmax / fmax, 1.0)
    else:
        block_scale = np.ones(rows // SCALE_BLOCK_ROWS, dtype=np.float64)
    scale = np.repeat(block_scale, SCALE_BLOCK_ROWS).astype(np.float32).reshape(rows, 1)
    val_packed = (v64 / scale).astype(vdt)
    base = np.ascontiguousarray(col[:, :1], dtype=np.int32)
    dcol32 = np.diff(col.astype(np.int64), axis=1, prepend=base.astype(np.int64))
    idt = np.int16 if np.abs(dcol32).max(initial=0) < (1 << 15) else np.int32
    return val_packed, scale, base, dcol32.astype(idt)


def padded_col_map(splits: np.ndarray, n_pad: int, n: int) -> np.ndarray:
    """Global column -> padded-global coordinate ``shard * n_pad + local``.

    The single definition of the distributed coordinate scheme: the COO path
    (``core.partition.partition_matrix``) and the kernel-format conversions
    below must index the all-gathered vector identically.
    """
    owner = np.searchsorted(splits, np.arange(n), side="right") - 1
    return (owner * n_pad + (np.arange(n) - splits[owner])).astype(np.int64)


def shard_to_ell(
    csr: CSR,
    splits: np.ndarray,
    n_pad: int,
    dtype=jnp.float32,
    row_tile: int = 8,
    slot_tile: int = 128,
) -> Tuple[jax.Array, jax.Array, dict]:
    """Row-shard a CSR into stacked uniform ELL arrays for ``shard_map``.

    Returns ``(val, col)`` of shape (G, rows_pad, width) — one identical-shape
    ELL block per shard, columns remapped to the padded-global coordinate
    system of ``core/partition.py`` (``g = shard * n_pad + local_row``) so the
    all-gathered replicated vector is indexed directly — plus a stats dict
    with the realized padding overhead.
    """
    g = len(splits) - 1
    n = csr.n
    row_nnz = csr.row_nnz()
    width = int(max(1, row_nnz.max()))
    width = -(-width // slot_tile) * slot_tile
    rows_pad = -(-n_pad // row_tile) * row_tile
    count_conversions(g)

    col_map = padded_col_map(splits, n_pad, n)
    rix, pos = _row_positions(csr)
    owner = np.searchsorted(splits, rix, side="right") - 1
    local_r = rix - splits[owner]

    val = np.zeros((g, rows_pad, width), dtype=np.float64)
    col = np.zeros((g, rows_pad, width), dtype=np.int32)
    val[owner, local_r, pos] = csr.data
    col[owner, local_r, pos] = col_map[csr.indices]
    stats = ell_padding_stats(row_nnz)
    stats["rows_pad"] = rows_pad
    stats["width_padded"] = width
    return jnp.asarray(val, dtype=dtype), jnp.asarray(col), stats


def shard_to_blocked_ell(
    csr: CSR,
    splits: np.ndarray,
    n_pad: int,
    block_size: int = 8,
    dtype=jnp.float32,
) -> Tuple[jax.Array, jax.Array, dict]:
    """Row-shard a CSR into stacked blocked-ELL arrays for ``shard_map``.

    Returns ``(val, bcol)`` of shapes (G, nbr, slots, BS, BS) / (G, nbr,
    slots) with a uniform slot count (the max over shards), block columns in
    the *flat padded-global* index space of the all-gathered vector.  Requires
    ``n_pad % block_size == 0`` (use ``partition_matrix(..., row_align=BS)``)
    so shard-local block rows stay aligned with the replicated vector.
    """
    if n_pad % block_size:
        raise ValueError(f"n_pad={n_pad} must be a multiple of block_size={block_size}")
    g = len(splits) - 1
    n = csr.n
    count_conversions(g)
    col_map = padded_col_map(splits, n_pad, n)
    row_nnz = csr.row_nnz()
    rix = np.repeat(np.arange(n, dtype=np.int64), row_nnz)

    shard_trip = []
    slots = 1
    for s in range(g):
        lo, hi = int(csr.indptr[splits[s]]), int(csr.indptr[splits[s + 1]])
        rows_l = rix[lo:hi] - splits[s]
        cols_g = col_map[csr.indices[lo:hi]]
        shard_trip.append((rows_l, cols_g, csr.data[lo:hi]))
        if rows_l.size:
            bkeys = (rows_l // block_size) * (g * n_pad // block_size) + cols_g // block_size
            counts = np.bincount(np.unique(bkeys) // (g * n_pad // block_size))
            slots = max(slots, int(counts.max()))

    vals, bcols = [], []
    for rows_l, cols_g, data in shard_trip:
        bsr = blocked_ell_from_triplets(
            rows_l, cols_g, data, n_pad, g * n_pad, block_size=block_size,
            slots=slots, dtype=dtype,
        )
        vals.append(bsr.val)
        bcols.append(bsr.bcol)
    stats = {"slots": slots, "block_size": block_size, "n_block_rows": n_pad // block_size}
    return jnp.stack(vals), jnp.stack(bcols), stats


def shard_to_hybrid(
    csr: CSR,
    splits: np.ndarray,
    n_pad: int,
    dtype=jnp.float32,
    width_cap: Optional[int] = None,
    quantile: Optional[float] = None,
    row_tile: int = 8,
    slot_tile: int = 8,
    tail_align: int = 8,
) -> Tuple[Tuple[jax.Array, ...], dict]:
    """Row-shard a CSR into stacked hybrid (capped ELL + COO tail) arrays.

    Returns ``(val, col, tail_row, tail_col, tail_val)`` with shapes
    (G, rows_pad, width_cap) / (G, tail_pad): one identical-shape hybrid
    block per shard (shard_map needs uniform shapes, so the width cap is
    *global* — the quantile of the full matrix's row lengths — and every
    shard's tail is padded to the largest shard tail).  Columns are remapped
    to the padded-global coordinates of ``core/partition.py``; tail rows are
    shard-local output rows.  Plus a stats dict with the realized split.
    """
    from ..kernels.engine import hybrid_width_cap  # lazy: sparse sits below kernels

    g = len(splits) - 1
    n = csr.n
    count_conversions(g)
    row_nnz = csr.row_nnz()
    cap = hybrid_width_cap(row_nnz, quantile) if width_cap is None else int(width_cap)
    cap = max(1, min(cap, int(row_nnz.max()) if row_nnz.size else 1))
    width = -(-cap // slot_tile) * slot_tile
    rows_pad = -(-n_pad // row_tile) * row_tile

    col_map = padded_col_map(splits, n_pad, n)
    rix, pos = _row_positions(csr)
    owner = np.searchsorted(splits, rix, side="right") - 1
    local_r = rix - splits[owner]
    keep = pos < width

    val = np.zeros((g, rows_pad, width), dtype=np.float64)
    col = np.zeros((g, rows_pad, width), dtype=np.int32)
    val[owner[keep], local_r[keep], pos[keep]] = csr.data[keep]
    col[owner[keep], local_r[keep], pos[keep]] = col_map[csr.indices[keep]]

    spill = ~keep
    tail_counts = np.bincount(owner[spill], minlength=g)
    tail_pad = -(-max(int(tail_counts.max()) if g else 0, 1) // tail_align) * tail_align
    trow = np.zeros((g, tail_pad), dtype=np.int32)
    tcol = np.zeros((g, tail_pad), dtype=np.int32)
    tval = np.zeros((g, tail_pad), dtype=np.float64)
    for s in range(g):
        sel = spill & (owner == s)
        k = int(sel.sum())
        trow[s, :k] = local_r[sel]
        tcol[s, :k] = col_map[csr.indices[sel]]
        tval[s, :k] = csr.data[sel]
    tail_nnz = int(spill.sum())
    stats = {
        "width_cap": width,
        "rows_pad": rows_pad,
        "tail_nnz": tail_nnz,
        "tail_pad": tail_pad,
        "hybrid_overhead": (g * rows_pad * width + tail_nnz) / max(1, csr.nnz),
        "tail_frac": tail_nnz / max(1, csr.nnz),
    }
    mats = (
        jnp.asarray(val, dtype=dtype),
        jnp.asarray(col),
        jnp.asarray(trow),
        jnp.asarray(tcol),
        jnp.asarray(tval, dtype=dtype),
    )
    return mats, stats
