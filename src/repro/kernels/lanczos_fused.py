"""Pallas TPU kernel: fused ELL SpMV + alpha-dot — one read of the Krylov vector.

A Lanczos iteration (Alg. 1 lines 5-7) is three memory-bound passes today:

    w     = A @ v                      (SpMV kernel)
    alpha = <v, w>                     (dot over n)
    u     = w - alpha v - beta v_prev  (+ ||u||^2, fused update kernel)

``bench/roofline.py`` puts the step firmly on the memory roofline, so every pass
saved over the n-length vectors is throughput.  The dependency structure
caps fusion at *two* passes, not one: alpha needs every row of ``w`` before
any element of ``u`` can be written, and the TPU grid is sequential — a
single kernel that both produced ``w`` and consumed the finished alpha
would have to revisit output blocks non-consecutively, which Pallas does
not guarantee.  What *is* legal is folding the alpha reduction into the
SpMV itself: each row tile of ``w`` is still in VMEM when its width sweep
finishes, so the kernel accumulates ``alpha += <v_tile, w_tile>`` right
there (the (1,) alpha output block is pinned to every grid step, exactly
like the norm accumulator in ``lanczos_update.py``).  Combined with the
fused update kernel the iteration touches each n-vector once per pass:

    pass 1: spmv_alpha  -> w, alpha     (reads x/val/col, writes w, alpha free)
    pass 2: lanczos_update -> u, ||u||^2 (reads w/v/v_prev, writes u, norm free)

i.e. 2 passes instead of 4, and both reductions ride along for free.

``x`` is the gather source in *storage* dtype (full vector, VMEM-resident —
see spmv_ell.py for why); ``v`` is the same vector in *compute* dtype so the
in-kernel alpha matches the reference ``dot(v, w)`` association exactly on a
single row tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["spmv_ell_alpha_kernel_call"]


def _kernel(x_ref, v_ref, val_ref, col_ref, y_ref, alpha_ref, *, accum_dtype, n_w_steps):
    i = pl.program_id(0)
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

    # The row tile of w is complete once the (sequential) width sweep ends;
    # fold its alpha contribution in while it is still in VMEM.  The (1,)
    # alpha block is pinned to every grid step, so it accumulates across
    # row tiles like the norm accumulator in lanczos_update.py.
    @pl.when(j == n_w_steps - 1)
    def _alpha():
        contrib = jnp.sum(y_ref[...] * v_ref[...].astype(accum_dtype))

        @pl.when(i == 0)
        def _first():
            alpha_ref[0] = contrib

        @pl.when(i != 0)
        def _rest():
            alpha_ref[0] = alpha_ref[0] + contrib


@functools.partial(
    jax.jit, static_argnames=("block_r", "block_w", "accum_dtype", "interpret")
)
def spmv_ell_alpha_kernel_call(
    val: jax.Array,
    col: jax.Array,
    x: jax.Array,
    v: jax.Array,
    *,
    block_r: int = 8,
    block_w: int = 512,
    accum_dtype=jnp.float32,
    interpret: bool = True,
):
    """Fused ``w = ELL(val, col) @ x`` and ``alpha = <v, w>`` in one pass.

    ``x`` is the gather source (storage dtype); ``v`` is the dot operand
    (compute dtype), padded to ``rows`` — padded rows of an ELL layout have
    all-zero values, so they contribute w = 0 and nothing to alpha.
    Returns ``(w (rows,) accum_dtype, alpha (1,) accum_dtype)``.
    """
    rows, width = val.shape
    block_w = min(block_w, width)
    if rows % block_r or width % block_w:
        raise ValueError(f"ELL shape {val.shape} not divisible by ({block_r},{block_w})")
    if v.shape[0] != rows:
        raise ValueError(f"v length {v.shape[0]} != padded rows {rows}")
    n = x.shape[0]
    grid = (rows // block_r, width // block_w)
    return pl.pallas_call(
        functools.partial(_kernel, accum_dtype=accum_dtype, n_w_steps=grid[1]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((n,), lambda i, j: (0,)),  # x: full vector each step
            pl.BlockSpec((block_r,), lambda i, j: (i,)),  # v: row tile
            pl.BlockSpec((block_r, block_w), lambda i, j: (i, j)),
            pl.BlockSpec((block_r, block_w), lambda i, j: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((block_r,), lambda i, j: (i,)),
            pl.BlockSpec((1,), lambda i, j: (0,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows,), accum_dtype),
            jax.ShapeDtypeStruct((1,), accum_dtype),
        ],
        interpret=interpret,
        name="spmv_ell_alpha",
    )(x, v, val, col)
