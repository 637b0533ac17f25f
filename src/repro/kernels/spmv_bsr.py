"""Pallas TPU kernel: blocked-ELL (BSR-style) SpMV — the MXU-native variant.

The ELL kernel (spmv_ell.py) gathers scalars with the VPU; when the matrix
has block structure (FEM meshes, banded graphs, the paper's venturiLevel3),
storing dense (BS x BS) blocks at sparse block coordinates turns SpMV into a
stream of small dense matmuls on the MXU.  Layout ("blocked ELL": uniform
block-slots per block-row, zero-padded):

  val:  (n_block_rows, slots, BS, BS)
  bcol: (n_block_rows, slots) int32  — block-column index (0 for padding,
                                        val zeros make padding inert)
  x:    (n_cols,) — VMEM-resident like the ELL kernel

Grid = (n_block_rows, slots); the slot axis is sequential on TPU, so the
(BS,) output tile accumulates across slots.  The block gather is a dynamic
slice of x at bcol*BS — contiguous, no scalar scatter/gather at all, which
is the entire point of the format on TPU.

Crossover vs ELL is density-dependent: a block is worth storing when more
than ~1/BS of it is non-zero (see benchmarks/kernels_bench.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["spmv_bsr_kernel_call", "blocked_ell_from_csr"]


def _kernel(x_ref, val_ref, bcol_ref, y_ref, *, accum_dtype, block_size):
    j = pl.program_id(1)
    bcol = bcol_ref[0, 0]
    xs = jax.lax.dynamic_slice(x_ref[...], (bcol * block_size,), (block_size,))
    blk = val_ref[0, 0].astype(accum_dtype)  # (BS, BS)
    part = blk @ xs.astype(accum_dtype)  # MXU matvec

    @pl.when(j == 0)
    def _init():
        y_ref[0, :] = part

    @pl.when(j != 0)
    def _acc():
        y_ref[0, :] = y_ref[0, :] + part


@functools.partial(jax.jit, static_argnames=("accum_dtype", "interpret"))
def spmv_bsr_kernel_call(
    val: jax.Array,  # (nbr, slots, BS, BS)
    bcol: jax.Array,  # (nbr, slots) int32
    x: jax.Array,  # (n_cols,)
    *,
    accum_dtype=jnp.float32,
    interpret: bool = True,
) -> jax.Array:
    nbr, slots, bs, _ = val.shape
    n = x.shape[0]
    return pl.pallas_call(
        functools.partial(_kernel, accum_dtype=accum_dtype, block_size=bs),
        grid=(nbr, slots),
        in_specs=[
            pl.BlockSpec((n,), lambda i, j: (0,)),
            pl.BlockSpec((1, 1, bs, bs), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((1, bs), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nbr, bs), accum_dtype),
        interpret=interpret,
        name="spmv_bsr",
    )(x, val, bcol).reshape(nbr * bs)


def blocked_ell_from_csr(csr, block_size: int = 8, dtype=jnp.float32):
    """Host conversion: CSR -> (val, bcol, n_rows). Zero-pads to uniform slots.

    Thin tuple-returning shim over the vectorized container conversion in
    ``sparse/formats.py`` (kept for callers predating :class:`DeviceBSR`).
    """
    from ..sparse.formats import to_device_bsr

    bsr = to_device_bsr(csr, block_size=block_size, dtype=dtype)
    return bsr.val, bsr.bcol, bsr.n_rows
