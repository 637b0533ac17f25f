"""The SpMV layer: one body per layout, chosen from the matrix.

Which *layout* wins is a property of the matrix, not the solver, so
:class:`SpmvEngine` packages the decision — format, accumulation dtype and
the layout's padding (:class:`TileConfig`) — behind one object that every
solver engine (``solve_fixed``, ``solve_sharded``, ``ChunkedOperator``, the
restarted engine) runs.  Each format has one body, the same under the Pallas
interpreter (CPU) and compiled (TPU):

  * ``coo``    — one gather of ``x`` and a scatter-add of the products
    (``row_sums``);
  * ``ell``    — padded ELLPACK, a gather and a row sum (``spmv_ell_ref``);
  * ``bsr``    — blocked ELL, a gather of ``x`` blocks and an ``einsum``;
  * ``hybrid`` — ELL capped at a quantile of the row lengths plus a COO tail
    for the hub rows' overflow;
  * ``sell``   — the row-length-bucketed ELL of ``sparse.formats.DeviceSELL``
    (at most 1.125 slots per non-zero): one gather, dense per-class sums, one
    scatter of a sum per row piece.  Its gather is the ``sell_gather`` Mosaic
    kernel (``x`` resident in VMEM, bit for bit ``jnp.take``) where ``x`` is
    float32 and fits VMEM, ``jnp.take`` otherwise.

All but ``sell``'s gather are XLA.  The only Pallas kernels of the package are
``sell_gather`` and the vector kernels ``lanczos_update`` and ``mixed_dot``.

Auto selection (:func:`choose_format`) runs on cheap O(nnz) statistics of the
host CSR (:class:`SpmvStats`): BSR where stored blocks are dense enough
(``block_fill``), then ``sell`` where the backend allows it and execution is
compiled, then ELL while its padding is bounded (``ell_overhead``), the hybrid
split while its tail stays a minority, and COO otherwise.
``partition["spmv"]["kernels"]`` reports what ran each phase
(:func:`phase_executors`).
"""

from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import env as envcfg

__all__ = [
    "FORMATS",
    "ITER_UPDATE_MODES",
    "TileConfig",
    "SpmvStats",
    "SpmvEngine",
    "matrix_stats",
    "shard_stats",
    "choose_format",
    "select_tiles",
    "table_update_mode",
    "phase_executors",
    "make_engine",
]

FORMATS = ("coo", "ell", "bsr", "hybrid", "sell")

# ELL accepted while padded slots <= ELL_MAX_OVERHEAD * nnz.
ELL_MAX_OVERHEAD = 3.0
# BSR accepted while block_fill >= BSR_FILL_FACTOR / block_size.
BSR_FILL_FACTOR = 4.0
DEFAULT_BLOCK_SIZE = 8
# Hybrid ELL+COO: cap the ELL width at this quantile of the row lengths...
HYBRID_QUANTILE = 0.95
# ...and accept while the spilled tail stays a minority of the nnz (the ELL
# part must do the bulk of the work for the split to beat plain COO).
HYBRID_MAX_TAIL = 0.6


def _env_float(name: str, default: float) -> float:
    return envcfg.get_float(name, default, lenient=True)


def ell_overhead_bound() -> float:
    """The effective ELL padding bound (env-overridable) — the single parse
    every consumer of ``REPRO_SPMV_ELL_OVERHEAD`` shares."""
    return _env_float("REPRO_SPMV_ELL_OVERHEAD", ELL_MAX_OVERHEAD)


@dataclasses.dataclass(frozen=True)
class TileConfig:
    """The layout's padding: ELL and hybrid layouts pad rows to ``block_r``
    and ELL widths to ``block_w``; ``block_size`` is the dense block edge of
    the blocked-ELL/BSR layout."""

    block_r: int = 8
    block_w: int = 128
    block_size: int = DEFAULT_BLOCK_SIZE


# How the Lanczos three-term update runs: ``unfused`` as jnp expressions,
# ``fused`` through the ``lanczos_update`` kernel (update and norm in one pass).
ITER_UPDATE_MODES = ("unfused", "fused")


# Static tile table: (max_rows, max_width) upper bounds -> (block_r, block_w).
# Entries are scanned in order and the first row that fits is used.  bf16/f16
# rows double block_r to honor the TPU (16, 128) sublane minimum for 16-bit
# dtypes.
_TILE_TABLE: Tuple[Tuple[int, int, int, int], ...] = (
    # max_rows, max_width, block_r, block_w
    (1 << 10, 1 << 8, 8, 128),
    (1 << 10, 1 << 30, 8, 256),
    (1 << 14, 1 << 8, 16, 128),
    (1 << 14, 1 << 30, 16, 256),
    (1 << 30, 1 << 8, 32, 128),
    (1 << 30, 1 << 30, 32, 512),
)


def select_tiles(
    n_rows: int,
    width: int,
    dtype=jnp.float32,
    block_size: int = DEFAULT_BLOCK_SIZE,
) -> TileConfig:
    """The layout padding for a (rows, width) shape from the static table."""
    block_r, block_w = _TILE_TABLE[-1][2:]
    for max_rows, max_width, br, bw in _TILE_TABLE:
        if n_rows <= max_rows and width <= max_width:
            block_r, block_w = br, bw
            break
    if jnp.dtype(dtype).itemsize == 2:  # bf16/f16 sublane minimum is 16
        block_r = max(block_r, 16)
    return TileConfig(block_r=block_r, block_w=block_w, block_size=block_size)


def phase_executors(
    fmt: str, interpret: bool, update: str, compute_dtype, gather: Optional[str] = None
) -> dict:
    """What runs each per-iteration phase: ``"mosaic"`` (a compiled Pallas
    kernel), ``"pallas_interpret"`` or ``"xla"``.  ``update`` is the
    effective update mode; f64 compute keeps the jnp update.  The SpMV is
    XLA for every format but ``"sell"``, which reports ``gather``, what
    gathered its ``x`` (``DeviceSELL.gather_executor``; ``"xla"`` when not
    given)."""
    pallas = "pallas_interpret" if interpret else "mosaic"
    spmv = (gather or "xla") if fmt == "sell" else "xla"
    fused = update != "unfused" and jnp.dtype(compute_dtype) != jnp.dtype(jnp.float64)
    return {"spmv": spmv, "update": pallas if fused else "xla"}


def table_update_mode(interpret: bool) -> str:
    """The update mode for the execution mode.  Interpret mode (CPU
    validation) pays ~ms of interpreter overhead per Pallas grid step, so
    the fused kernel loses there (measured ~9x slower than XLA's unfused
    expressions); compiled, it is the memory-bound pass the fusion targets.
    """
    return "unfused" if interpret else "fused"


@dataclasses.dataclass(frozen=True)
class SpmvStats:
    """Cheap per-matrix (or per-shard) layout statistics driving selection."""

    n_rows: int
    nnz: int
    max_row_nnz: int
    mean_row_nnz: float
    ell_overhead: float  # padded ELL slots / nnz (1.0 = no padding)
    block_size: int
    n_blocks: int  # touched BS x BS blocks
    block_fill: float  # nnz / (n_blocks * BS^2)
    # Hybrid ELL+COO split: ELL width capped at the HYBRID_QUANTILE of row
    # lengths, hub overflow spilled to a COO tail.
    hyb_width: int = 0  # the capped ELL width
    hyb_tail_nnz: int = 0  # nnz spilled past the cap
    hyb_overhead: float = 0.0  # (capped ELL slots + tail) / nnz
    hyb_tail_frac: float = 0.0  # tail nnz / nnz
    block_row_max: int = 0  # touched blocks in the fullest block-row
    # Row-length-bucketed ELL (sparse.formats.sell_classes): padded slots,
    # stored row pieces (one per non-empty row of up to ROW_BLOCK entries)
    # and width classes.
    sell_slots: int = 0
    sell_pieces: int = 0
    sell_classes: int = 0

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)

    def layout_bytes(self, fmt: str, value_bytes: int) -> int:
        """Device bytes of the layout ``fmt`` builds, padding included: a
        value and an int32 column per slot, plus an int32 row per COO
        triplet.  ELL pads every row to the longest (128-lane aligned),
        hybrid to its capped width (8-slot aligned) plus the tail (counted at
        the unaligned cap: an upper bound), BSR every block-row to the
        fullest one; sell each row piece to its class width and the slots
        to a multiple of 128, plus an int32 row index per piece."""
        vb = int(value_bytes)
        if fmt == "sell":
            from ..sparse.formats import sell_stored_slots  # lazy: sparse sits below kernels

            return sell_stored_slots(self.sell_slots) * (vb + 4) + 4 * self.sell_pieces
        if fmt == "ell":
            width = -(-max(1, self.max_row_nnz) // 128) * 128
            return width * self.n_rows * (vb + 4)
        if fmt == "hybrid":
            width = -(-max(1, self.hyb_width) // 8) * 8
            return width * self.n_rows * (vb + 4) + self.hyb_tail_nnz * (vb + 8)
        if fmt == "bsr":
            bs = self.block_size
            slots = -(-self.n_rows // bs) * self.block_row_max
            return slots * (bs * bs * vb + 4)
        return self.nnz * (vb + 8)


def hybrid_quantile() -> float:
    return _env_float("REPRO_SPMV_HYBRID_Q", HYBRID_QUANTILE)


def hybrid_width_cap(row_nnz: np.ndarray, quantile: Optional[float] = None) -> int:
    """The hybrid split's ELL width: the given quantile of the row lengths
    (hub rows above it spill their overflow into the COO tail)."""
    if not row_nnz.size or not int(row_nnz.max()):
        return 0
    q = hybrid_quantile() if quantile is None else quantile
    cap = int(np.ceil(np.quantile(row_nnz, min(max(q, 0.0), 1.0))))
    return max(1, min(cap, int(row_nnz.max())))


def _stats_from_triplets(
    row_nnz: np.ndarray,
    rows: Optional[np.ndarray],
    cols: Optional[np.ndarray],
    n_rows: int,
    block_size: int,
    width: Optional[int] = None,
    hyb_width: Optional[int] = None,
) -> SpmvStats:
    """``rows``/``cols`` may be None to skip the (sort-heavy) block census —
    used when the format is forced and block density is never consulted.
    ``width`` overrides the ELL width used for the overhead estimate (shards
    of a distributed solve all pay the *global* max row width, since
    shard_map forces one shared ELL shape); ``hyb_width`` likewise overrides
    the hybrid cap (shards share one capped width too)."""
    nnz = int(row_nnz.sum())
    max_row = int(row_nnz.max()) if row_nnz.size else 0
    mean_row = nnz / max(1, n_rows)
    overhead = (max(max_row, width or 0) * n_rows) / max(1, nnz)
    bs = block_size
    if nnz and rows is not None:
        nbc = -(-int(cols.max() + 1) // bs)
        keys = np.unique((rows // bs).astype(np.int64) * nbc + cols // bs)
        n_blocks = int(keys.size)
        block_row_max = int(np.bincount(keys // nbc).max())
    else:
        n_blocks = block_row_max = 0
    # No census (skipped or empty matrix) must read as "no block structure",
    # never as infinite fill — otherwise auto-selection would pick BSR.
    fill = nnz / (n_blocks * bs * bs) if n_blocks else 0.0
    cap = hybrid_width_cap(row_nnz) if hyb_width is None else int(hyb_width)
    tail = int(np.maximum(row_nnz - cap, 0).sum()) if (nnz and cap) else 0
    from ..sparse.formats import sell_classes  # lazy: sparse sits below kernels

    pieces, _, classes = sell_classes(row_nnz)
    return SpmvStats(
        n_rows=n_rows,
        nnz=nnz,
        max_row_nnz=max_row,
        mean_row_nnz=mean_row,
        ell_overhead=overhead,
        block_size=bs,
        n_blocks=n_blocks,
        block_fill=fill,
        hyb_width=cap,
        hyb_tail_nnz=tail,
        hyb_overhead=(cap * n_rows + tail) / max(1, nnz),
        hyb_tail_frac=tail / max(1, nnz),
        block_row_max=block_row_max,
        sell_slots=sum(w * r for w, r in classes),
        sell_pieces=int(pieces.size),
        sell_classes=len(classes),
    )


def matrix_stats(
    csr, block_size: int = DEFAULT_BLOCK_SIZE, with_blocks: bool = True
) -> SpmvStats:
    """O(nnz) layout statistics of a host CSR (the block census is the only
    super-linear part; skip it with ``with_blocks=False``)."""
    row_nnz = csr.row_nnz()
    if with_blocks:
        rows = np.repeat(np.arange(csr.n, dtype=np.int64), row_nnz)
        return _stats_from_triplets(row_nnz, rows, csr.indices, csr.n, block_size)
    return _stats_from_triplets(row_nnz, None, None, csr.n, block_size)


def shard_stats(
    csr,
    splits: np.ndarray,
    block_size: int = DEFAULT_BLOCK_SIZE,
    with_blocks: bool = True,
) -> Tuple[SpmvStats, ...]:
    """Per-shard statistics for a row-partitioned CSR (splits from
    ``core.partition.nnz_balanced_splits``).

    Block density is measured in the *remapped padded-global* column
    coordinates the distributed BSR layout actually uses
    (``sparse.formats.shard_to_blocked_ell``: columns become
    ``owner * n_pad + local`` with ``n_pad`` block-aligned), and each shard's
    ``ell_overhead`` is charged at the *global* max row width (shard_map
    forces one shared ELL shape — ``shard_to_ell`` pads every shard to it),
    so the selector judges the layout it would build, not a local optimum.
    """
    out = []
    row_nnz = csr.row_nnz()
    global_width = int(row_nnz.max()) if row_nnz.size else 0
    global_cap = hybrid_width_cap(row_nnz)  # hybrid too shares one shape
    # Every shard is padded to the SAME row count (n_pad ~ max shard rows) and
    # the same width, so each shard's overhead is charged at that uniform
    # shape — a shard with few dense rows still allocates max_rows x width.
    max_rows = int((splits[1:] - splits[:-1]).max()) if len(splits) > 1 else csr.n
    max_rows = max(1, max_rows)
    cols_pg = None
    if with_blocks:
        n_pad_bsr = -(-max_rows // block_size) * block_size
        owner = np.searchsorted(splits, csr.indices, side="right") - 1
        cols_pg = owner * n_pad_bsr + (csr.indices - splits[owner])
    for s in range(len(splits) - 1):
        r0, r1 = int(splits[s]), int(splits[s + 1])
        lo, hi = int(csr.indptr[r0]), int(csr.indptr[r1])
        local_nnz = row_nnz[r0:r1]
        if with_blocks:
            rows = np.repeat(np.arange(r1 - r0, dtype=np.int64), local_nnz)
            cols = cols_pg[lo:hi]
        else:
            rows = cols = None
        out.append(
            _stats_from_triplets(
                local_nnz,
                rows,
                cols,
                max_rows,
                block_size,
                width=global_width,
                hyb_width=global_cap,
            )
        )
    return tuple(out)


def choose_format(
    stats,
    allowed: Sequence[str] = FORMATS,
    *,
    ell_max_overhead: Optional[float] = None,
    bsr_fill_factor: Optional[float] = None,
    compiled: bool = False,
) -> str:
    """Pick a SpMV format from layout statistics (see module docstring).

    ``stats`` is one :class:`SpmvStats` or a sequence of per-shard stats; with
    several shards the choice must hold for *every* shard (shard_map runs one
    program on all of them), so the worst shard decides.

    ``allowed`` restricts the candidates: the distributed engine passes
    ``("ell", "bsr", "hybrid")`` because its hot loop takes no COO by
    default (COO remains an explicit opt-out there), the chunked engine passes ``("coo", "ell")``
    because per-chunk BSR staging is not implemented.

    ``compiled`` says the SpMV is compiled for the chip.  Then ``"sell"``,
    where allowed, is taken after the BSR test: it gathers the fewest slots
    of the gather layouts and scatters one sum per row piece, not one product
    per non-zero.  Under the interpreter it is not picked, because the
    interpreted ``sell_gather`` costs seconds a call at test sizes; the
    choice there is among the other formats.
    """
    if isinstance(stats, SpmvStats):
        stats = (stats,)
    ell_max = ell_max_overhead if ell_max_overhead is not None else ell_overhead_bound()
    bsr_factor = (
        bsr_fill_factor
        if bsr_fill_factor is not None
        else _env_float("REPRO_SPMV_BSR_FILL", BSR_FILL_FACTOR)
    )
    tail_max = _env_float("REPRO_SPMV_HYBRID_TAIL", HYBRID_MAX_TAIL)
    bsr_ok = "bsr" in allowed and all(
        s.block_fill >= bsr_factor / s.block_size for s in stats
    )
    if bsr_ok:
        return "bsr"
    if compiled and "sell" in allowed:
        return "sell"
    ell_ok = "ell" in allowed and all(s.ell_overhead <= ell_max for s in stats)
    if ell_ok:
        return "ell"
    # Hub-row split: the quantile-capped ELL part must respect the same
    # padding bound plain ELL failed (a *memory* bound: per shard), and the
    # spilled tail must stay a minority of the nnz (a *throughput* ratio:
    # judged on the aggregate — nnz-balanced splits concentrate hubs into
    # few-row shards whose local tail share is skewed by construction).
    # Otherwise segment_sum is doing the work anyway and plain COO is the
    # honest choice.
    tail_frac = sum(s.hyb_tail_nnz for s in stats) / max(1, sum(s.nnz for s in stats))
    hyb_ok = (
        "hybrid" in allowed
        and tail_frac <= tail_max
        and all(s.hyb_overhead <= ell_max for s in stats)
    )
    if hyb_ok:
        return "hybrid"
    if "coo" in allowed:
        return "coo"
    for fmt in ("hybrid", "ell"):
        if fmt not in allowed:
            continue
        # COO-free paths (distributed): ELL/hybrid are always *correct*;
        # the bounds above only optimize padding, so fall back rather than
        # fail — but loudly: padded ELL costs O(n * max_row_nnz) memory,
        # which on hub-dominated (power-law) matrices can dwarf the O(nnz)
        # COO path (the hybrid split bounds that, hence it is preferred).
        worst = max(
            (s.hyb_overhead if fmt == "hybrid" else s.ell_overhead) for s in stats
        )
        warnings.warn(
            f"SpMV auto-selection is restricted to kernel formats here and "
            f"fell back to {fmt.upper()} despite a {worst:.0f}x padding "
            f"overhead (bound: {ell_max:.1f}x); for hub-dominated matrices "
            f"consider format='coo' (segment-sum reference path) or a larger "
            f"REPRO_SPMV_ELL_OVERHEAD",
            stacklevel=2,
        )
        return fmt
    raise ValueError(f"no admissible SpMV format among {tuple(allowed)}")


def _default_interpret() -> bool:
    from .ops import default_interpret  # lazy: keeps package init order simple

    return default_interpret()


@dataclasses.dataclass(frozen=True)
class SpmvEngine:
    """One SpMV execution configuration: format + accum dtype + padding.

    Frozen and hashable so it can ride through ``jax.jit`` static arguments.
    ``interpret`` selects the Pallas interpreter (CPU containers) vs compiled
    execution (real TPU) for the kernels the SpMV and the update call.
    """

    format: str = "auto"
    accum_dtype: Any = jnp.float32
    tiles: TileConfig = TileConfig()
    interpret: bool = True
    requested: str = "auto"
    stats: Optional[Tuple[SpmvStats, ...]] = None

    def __post_init__(self):
        if self.format not in FORMATS:
            raise ValueError(f"unknown SpMV format {self.format!r}; expected {FORMATS}")

    # --- raw-array SpMV bodies (used inside shard_map / jit) ---------------

    def ell_matvec(self, val: jax.Array, col: jax.Array, x: jax.Array) -> jax.Array:
        """y = ELL(val, col) @ x -> (rows_padded,) in the accum dtype."""
        from .ref import spmv_ell_ref

        return spmv_ell_ref(val, col, x, accum_dtype=jnp.dtype(self.accum_dtype))

    def packed_ell_matvec(
        self,
        val: jax.Array,
        scale: jax.Array,
        base: jax.Array,
        dcol: jax.Array,
        x: jax.Array,
    ) -> jax.Array:
        """y = dequant(val, scale) @ x over delta-encoded columns (compressed
        out-of-core staging; ``sparse.formats.pack_ell_chunk``).  Returns
        (rows_padded,) in the accum dtype."""
        acc = jnp.dtype(self.accum_dtype)
        vals = val.astype(acc) * scale.astype(acc)
        cols = base + jnp.cumsum(dcol.astype(jnp.int32), axis=1)
        return jnp.sum(vals * jnp.take(x, cols).astype(acc), axis=1)

    def bsr_matvec(self, val: jax.Array, bcol: jax.Array, x: jax.Array) -> jax.Array:
        """y = BSR(val, bcol) @ x -> (nbr * BS,) in the accum dtype."""
        acc = jnp.dtype(self.accum_dtype)
        nbr, slots, bs, _ = val.shape
        if x.shape[0] % bs:
            x = jnp.pad(x, (0, bs - x.shape[0] % bs))
        # Same einsum as DeviceBSR.matvec, without the [:n_rows] slice
        # (callers hold the logical row count).
        gathered = jnp.take(x.reshape(-1, bs), bcol, axis=0)  # (nbr, slots, bs)
        y = jnp.einsum("rsij,rsj->ri", val.astype(acc), gathered.astype(acc))
        return y.reshape(nbr * bs)

    def hybrid_matvec(
        self,
        val: jax.Array,
        col: jax.Array,
        tail_row: jax.Array,
        tail_col: jax.Array,
        tail_val: jax.Array,
        x: jax.Array,
        n_rows: int,
    ) -> jax.Array:
        """Hub-split SpMV: capped-width ELL + COO tail (``row_sums``).

        ``tail_row`` indexes the output rows; padding slots (val 0, row 0)
        contribute nothing.  Returns (n_rows,) in the accum dtype.
        """
        from ..sparse.formats import row_sums

        acc = jnp.dtype(self.accum_dtype)
        y = self.ell_matvec(val, col, x)[:n_rows]
        prod = tail_val.astype(acc) * jnp.take(x, tail_col).astype(acc)
        return y + row_sums(prod, tail_row, n_rows)

    # --- container-level dispatch (single-device operators) ----------------

    def spmv(self, mat, x: jax.Array, accum_dtype=None) -> jax.Array:
        """SpMV on a device container (DeviceCOO/ELL/BSR/Hybrid/SELL).

        One compiled program, the container passed as an argument: run op by
        op from a host loop (the restarted engine), the SpMV would materialise
        every intermediate — ~3 GB of f64 temporaries per call on WK.
        """
        return _container_spmv(self, mat, x, jnp.dtype(accum_dtype or self.accum_dtype))

    def describe(self) -> dict:
        """Loggable summary (what ``EigenResult.partition`` records)."""
        return {
            "format": self.format,
            "requested": self.requested,
            "accum_dtype": str(jnp.dtype(self.accum_dtype)),
            "block_r": self.tiles.block_r,
            "block_w": self.tiles.block_w,
            "block_size": self.tiles.block_size,
            "interpret": self.interpret,
        }


@functools.partial(jax.jit, static_argnums=(0, 3))
def _container_spmv(engine: SpmvEngine, mat, x: jax.Array, acc) -> jax.Array:
    from ..sparse.formats import DeviceBSR, DeviceCOO, DeviceELL, DeviceHybrid, DeviceSELL

    if isinstance(mat, DeviceSELL):
        return mat.matvec(x, accum_dtype=acc, interpret=engine.interpret)
    if isinstance(mat, DeviceCOO):
        return mat.matvec(x, accum_dtype=acc)
    eng = engine if acc == engine.accum_dtype else dataclasses.replace(engine, accum_dtype=acc)
    if isinstance(mat, DeviceELL):
        return eng.ell_matvec(mat.val, mat.col, x)[: mat.n_rows]
    if isinstance(mat, DeviceBSR):
        return eng.bsr_matvec(mat.val, mat.bcol, x)[: mat.n_rows]
    if isinstance(mat, DeviceHybrid):
        return eng.hybrid_matvec(
            mat.ell_val, mat.ell_col, mat.tail_row, mat.tail_col, mat.tail_val,
            x, mat.n_rows,
        )
    raise TypeError(f"SpmvEngine.spmv: unsupported container {type(mat).__name__}")


def make_engine(
    csr=None,
    format: str = "auto",
    *,
    stats=None,
    accum_dtype: Any = jnp.float32,
    allowed: Sequence[str] = FORMATS,
    block_size: int = DEFAULT_BLOCK_SIZE,
    interpret: Optional[bool] = None,
    tiles: Optional[TileConfig] = None,
    storage_dtype: Any = None,
    ell_max_overhead: Optional[float] = None,
    bsr_fill_factor: Optional[float] = None,
) -> SpmvEngine:
    """Build a :class:`SpmvEngine` for a matrix (or precomputed shard stats).

    ``format="auto"`` runs :func:`choose_format` on the statistics, with
    ``compiled`` set from the execution mode (``"sell"`` only compiled: its
    interpreted gather kernel costs seconds a call); an explicit format is
    validated against ``allowed`` and used as-is.  ``tiles`` (the layout's
    padding) defaults to the static table for the layout's shape.
    """
    requested = format
    if stats is None:
        if csr is None:
            raise ValueError("make_engine needs a csr or precomputed stats")
        # The block census (an O(nnz log nnz) sort) only matters when BSR is
        # actually in play; forced COO/ELL solves skip it.
        with_blocks = format == "auto" and "bsr" in allowed
        stats = (matrix_stats(csr, block_size=block_size, with_blocks=with_blocks),)
    elif isinstance(stats, SpmvStats):
        stats = (stats,)
    else:
        stats = tuple(stats)

    interp = _default_interpret() if interpret is None else interpret
    if format == "auto":
        fmt = choose_format(
            stats,
            allowed,
            ell_max_overhead=ell_max_overhead,
            bsr_fill_factor=bsr_fill_factor,
            compiled=not interp,
        )
    else:
        if format not in FORMATS:
            raise ValueError(f"unknown SpMV format {format!r}; expected {FORMATS} or 'auto'")
        if format not in allowed:
            raise ValueError(
                f"format={format!r} is not supported by this backend (allowed: {tuple(allowed)})"
            )
        fmt = format

    if tiles is None:
        # The width the built layout will have, not the raw row statistic:
        # hybrid caps it (8-slot aligned, to_device_hybrid), plain ELL pads
        # to 128 lanes (to_device_ell/shard_to_ell); sell and COO report the
        # longest row.
        if fmt == "hybrid":
            width = -(-max(1, max(s.hyb_width for s in stats)) // 8) * 8
        elif fmt == "ell":
            width = -(-max(1, max(s.max_row_nnz for s in stats)) // 128) * 128
        else:
            width = max(s.max_row_nnz for s in stats)
        # The storage dtype governs the TPU sublane minimum of the value tiles.
        tiles = select_tiles(
            max(s.n_rows for s in stats),
            width,
            dtype=storage_dtype or accum_dtype,
            block_size=block_size,
        )
    return SpmvEngine(
        format=fmt,
        accum_dtype=accum_dtype,
        tiles=tiles,
        interpret=interp,
        requested=requested,
        stats=stats,
    )
