"""Pallas TPU kernel: packed-ELL SpMV — compressed out-of-core staging.

The out-of-core engine's bottleneck is staging bandwidth (host DRAM -> HBM
DMA on TPU; see ``core/operators.ChunkedOperator``).  This kernel multiplies
the *effective* bandwidth by shipping each staged chunk compressed and
decompressing on-chip:

  * ``val``   — (rows, width) chunk values in a narrow storage dtype
    (bf16 or fp8 e4m3), quantized per row block;
  * ``scale`` — (rows, 1) f32 dequantization scale (one scale per row block
    of the packing, expanded to per-row at pack time so the kernel tile
    math stays trivial);
  * ``base``  — (rows, 1) int32 first stored column of each row;
  * ``dcol``  — (rows, width) int16/int32 *delta-encoded* column indices:
    ``dcol[r, 0] == 0`` and ``dcol[r, s] == col[r, s] - col[r, s-1]``.
    Sorted CSR rows make the deltas small, so int16 usually suffices —
    half the index bytes of the plain ELL layout.

In-kernel decompression recovers ``col = base + cumsum(dcol, axis=1)`` and
``v = val * scale``; the row-wise cumsum requires the whole width in one
tile, so the grid is one-dimensional over row blocks (chunk widths are
per-chunk and modest — the staging layer builds per-chunk-width tiles, see
``ChunkedOperator._build_chunk``).  The single grid dimension is parallel
over independent row blocks; there is no cross-step accumulator.

Packing itself (quantize + delta-encode) is host-side NumPy in the staging
path — the kernel is the *decompress + SpMV* half that runs per staged
chunk.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
from jax.experimental import pallas as pl

__all__ = [
    "PACKED_VALUE_DTYPES",
    "pack_ell_chunk",
    "spmv_ell_packed_kernel_call",
]

# staging-mode name -> narrow storage dtype of the packed values
PACKED_VALUE_DTYPES = {
    "bf16": np.dtype(ml_dtypes.bfloat16),
    "fp8": np.dtype(ml_dtypes.float8_e4m3fn),
}
# Rows sharing one quantization scale (the "per-row-block" granularity).
SCALE_BLOCK_ROWS = 8


def pack_ell_chunk(val: np.ndarray, col: np.ndarray, mode: str):
    """Quantize + delta-encode one host-side ELL chunk.

    Returns ``(val_packed, scale, base, dcol)`` host arrays matching the
    kernel's operand layout.  ``scale`` is computed over row blocks of
    ``SCALE_BLOCK_ROWS`` rows (max-abs mapped to the dtype's finite range
    for fp8; bf16 shares f32's exponent range so its scale is 1) and
    expanded to per-row ``(rows, 1)``.  ``dcol`` narrows to int16 when every
    delta fits, else stays int32.
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


def _kernel(x_ref, val_ref, scale_ref, base_ref, dcol_ref, y_ref, *, accum_dtype):
    x = x_ref[...]  # full vector, VMEM-resident (same contract as spmv_ell)
    # Decompress: dequantize values, cumsum the column deltas back to
    # absolute indices.  The whole row width is in this tile (1-D grid).
    vals = val_ref[...].astype(accum_dtype) * scale_ref[...].astype(accum_dtype)
    cols = base_ref[...] + jnp.cumsum(dcol_ref[...].astype(jnp.int32), axis=1)
    gathered = jnp.take(x, cols.reshape(-1), axis=0).reshape(cols.shape).astype(accum_dtype)
    y_ref[...] = jnp.sum(vals * gathered, axis=1)  # (BR,)


@functools.partial(
    jax.jit, static_argnames=("block_r", "accum_dtype", "interpret")
)
def spmv_ell_packed_kernel_call(
    val: jax.Array,
    scale: jax.Array,
    base: jax.Array,
    dcol: jax.Array,
    x: jax.Array,
    *,
    block_r: int = 8,
    accum_dtype=jnp.float32,
    interpret: bool = True,
) -> jax.Array:
    """y = dequant(val, scale) @ x at columns ``base + cumsum(dcol)``.

    Accumulates in ``accum_dtype``; returns (rows,).  The grid tiles rows
    only — the delta cumsum needs the full width per tile.
    """
    rows, width = val.shape
    if rows % block_r:
        raise ValueError(
            f"packed ELL shape {val.shape} rows not divisible by block_r={block_r}"
        )
    n = x.shape[0]
    grid = (rows // block_r,)
    return pl.pallas_call(
        functools.partial(_kernel, accum_dtype=accum_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((n,), lambda i: (0,)),  # x: full vector each step
            pl.BlockSpec((block_r, width), lambda i: (i, 0)),
            pl.BlockSpec((block_r, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_r, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_r, width), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_r,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((rows,), accum_dtype),
        interpret=interpret,
        name="spmv_ell_packed",
    )(x, val, scale, base, dcol)
