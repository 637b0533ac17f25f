"""Pallas TPU kernel: ELLPACK SpMV — the paper's hot-spot (DESIGN.md §4).

Layout (see ``sparse/formats.py::DeviceELL``): ``val``/``col`` are
(rows_padded, width) with zero padding; ``x`` is the SpMV input vector
(replicated per shard in the distributed solver — the paper's §III-A).

Tiling: grid = (rows/BLOCK_R, width/BLOCK_W).  Each step holds in VMEM:
  * a (BLOCK_R, BLOCK_W) value tile and its column-index tile,
  * the full ``x`` vector (the gather source must be on-chip: TPU has no
    efficient random HBM gather — this is the central hardware adaptation
    from the paper's GPU design, which gathers through the L2/unified
    memory. VMEM residency caps a single shard at ~3M f32 columns; larger
    matrices are row+column partitioned across devices first, which is
    exactly the paper's multi-device partition scheme),
  * a (BLOCK_R,) f32 output accumulator tile.

The width dimension of the grid is sequential on TPU, so the kernel
accumulates partial row sums into the output tile across width steps
(`pl.when(j == 0)` initializes).  Accumulation dtype is a parameter — the
paper's mixed-precision "compute" knob.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["spmv_ell_kernel_call"]


def _kernel(x_ref, val_ref, col_ref, y_ref, *, accum_dtype):
    j = pl.program_id(1)
    x = x_ref[...]  # full vector, VMEM-resident
    cols = col_ref[...]  # (BR, BW) int32
    vals = val_ref[...].astype(accum_dtype)
    gathered = jnp.take(x, cols.reshape(-1), axis=0).reshape(cols.shape).astype(accum_dtype)
    part = jnp.sum(vals * gathered, axis=1)  # (BR,)

    @pl.when(j == 0)
    def _init():
        y_ref[...] = part

    @pl.when(j != 0)
    def _acc():
        y_ref[...] = y_ref[...] + part


@functools.partial(
    jax.jit, static_argnames=("block_r", "block_w", "accum_dtype", "interpret")
)
def spmv_ell_kernel_call(
    val: jax.Array,
    col: jax.Array,
    x: jax.Array,
    *,
    block_r: int = 8,
    block_w: int = 512,
    accum_dtype=jnp.float32,
    interpret: bool = True,
) -> jax.Array:
    """y = ELL(val, col) @ x, accumulated in ``accum_dtype``. Returns (rows,)."""
    rows, width = val.shape
    block_w = min(block_w, width)
    if rows % block_r or width % block_w:
        raise ValueError(f"ELL shape {val.shape} not divisible by ({block_r},{block_w})")
    n = x.shape[0]
    grid = (rows // block_r, width // block_w)
    return pl.pallas_call(
        functools.partial(_kernel, accum_dtype=accum_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((n,), lambda i, j: (0,)),  # x: full vector each step
            pl.BlockSpec((block_r, block_w), lambda i, j: (i, j)),
            pl.BlockSpec((block_r, block_w), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((block_r,), lambda i, j: (i,)),
        out_shape=jax.ShapeDtypeStruct((rows,), accum_dtype),
        interpret=interpret,
        name="spmv_ell",
    )(x, val, col)
