"""Pallas TPU kernel: fused Lanczos three-term recurrence + norm.

Between two SpMVs the paper performs (Alg. 1 lines 6/11):

    u      = w - alpha * v - beta * v_prev     (vector update)
    beta'  = ||u||_2                           (next normalization)

Executed separately these are 4 full memory passes over n-length vectors
(read w/v/v_prev + write u, then read u again for the norm).  This kernel
fuses them into a single pass — the squared-norm partial is accumulated
across the sequential TPU grid while the update tile is still in VMEM.
This is a beyond-paper optimization targeting the memory roofline term of
the solver.

Layout (what Mosaic accepts): the n-vectors are viewed lane-dense as
(n / 128, 128) and tiled in (block / 128, 128) row blocks; alpha and beta
ride in SMEM as one (1, 2) operand; the squared norm accumulates into an
(8, 128) f32 block pinned to every grid step (sublane-group sums, no
cross-lane reduction in the loop), reduced to a scalar by XLA afterwards.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["LANES", "lanczos_update_kernel_call", "lane_partial", "pinned_block", "row_block"]

LANES = 128
# Rows of the (8, 128) accumulator block: one f32 vreg.
_ACC_ROWS = 8


# Index maps return explicit int32 block indices: under x64 a bare Python 0
# lowers as i64, which Mosaic refuses.
def row_block(i):
    return i, jnp.int32(0)


def pinned_block(i):
    return jnp.int32(0), jnp.int32(0)


def lane_partial(x: jax.Array) -> jax.Array:
    """(rows, 128) -> (8, 128): sum of the row groups, lane by lane."""
    return jnp.sum(x.reshape(-1, _ACC_ROWS, LANES), axis=0)


def _kernel(ab_ref, w_ref, v_ref, vp_ref, u_ref, nrm_ref, *, accum_dtype):
    i = pl.program_id(0)
    acc = accum_dtype
    alpha = ab_ref[0, 0].astype(acc)
    beta = ab_ref[0, 1].astype(acc)
    u = w_ref[...].astype(acc) - alpha * v_ref[...].astype(acc) - beta * vp_ref[...].astype(acc)
    u_ref[...] = u.astype(u_ref.dtype)
    part = lane_partial(u * u)

    @pl.when(i == 0)
    def _init():
        nrm_ref[...] = part

    @pl.when(i != 0)
    def _acc():
        nrm_ref[...] = nrm_ref[...] + part


@functools.partial(jax.jit, static_argnames=("block", "accum_dtype", "interpret"))
def lanczos_update_kernel_call(
    w: jax.Array,
    v: jax.Array,
    v_prev: jax.Array,
    alpha: jax.Array,
    beta: jax.Array,
    *,
    block: int = 1 << 16,
    accum_dtype=jnp.float32,
    interpret: bool = True,
):
    """Returns (u (n,) w.dtype, norm_sq (1,) accum_dtype).

    ``n`` must be a multiple of ``block`` (after clamping to ``n``), and
    ``block`` a multiple of 8 x 128 (``ops.lanczos_update`` pads for you).
    """
    n = w.shape[0]
    block = min(block, n)
    if n % block or block % (_ACC_ROWS * LANES):
        raise ValueError(f"length {n} not divisible by block {block} (a multiple of 1024)")
    rows, brows = n // LANES, block // LANES
    ab = jnp.stack([jnp.reshape(alpha, ()), jnp.reshape(beta, ())]).astype(accum_dtype)
    vec = pl.BlockSpec((brows, LANES), row_block)
    u, part = pl.pallas_call(
        functools.partial(_kernel, accum_dtype=accum_dtype),
        grid=(rows // brows,),
        in_specs=[pl.BlockSpec((1, 2), pinned_block, memory_space=pltpu.SMEM), vec, vec, vec],
        out_specs=[vec, pl.BlockSpec((_ACC_ROWS, LANES), pinned_block)],
        out_shape=[
            jax.ShapeDtypeStruct((rows, LANES), w.dtype),
            jax.ShapeDtypeStruct((_ACC_ROWS, LANES), accum_dtype),
        ],
        interpret=interpret,
        name="lanczos_update",
    )(ab.reshape(1, 2), *(a.reshape(rows, LANES) for a in (w, v, v_prev)))
    return u.reshape(n), jnp.sum(part).reshape(1)
