"""Pallas TPU kernel: mixed-precision dot product / squared norm.

The alpha and beta reductions are the accuracy-critical synchronization
points of the paper's Lanczos (Alg. 1 lines 6/10); the paper computes them
in f64 while storing vectors in f32.  TPUs have no fast f64, so this kernel
offers the TPU-native ladder:

  * inputs in any storage dtype (bf16 / f16 / f32),
  * per-tile products and sums in ``accum_dtype`` (f32),
  * optional Neumaier compensation *across tiles* — the sequential TPU grid
    makes the cross-tile accumulation a genuine running sum, so carrying a
    compensation term recovers most of the accuracy a 2x-wider accumulator
    would give (the stand-in for the paper's f64).

Layout (what Mosaic accepts): inputs are viewed lane-dense as (n / 128,
128); the running sum and its compensation are two (8, 128) blocks pinned
to every grid step, so each of the 1024 lanes carries its own compensated
sum.  Output: (2,) = (sum, compensation); callers take ``out.sum()``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .lanczos_update import LANES, lane_partial, pinned_block, row_block

__all__ = ["mixed_dot_kernel_call"]


def _kernel(a_ref, b_ref, s_ref, c_ref, *, accum_dtype, compensated):
    i = pl.program_id(0)
    part = lane_partial(a_ref[...].astype(accum_dtype) * b_ref[...].astype(accum_dtype))

    @pl.when(i == 0)
    def _init():
        s_ref[...] = part
        c_ref[...] = jnp.zeros_like(part)

    @pl.when(i != 0)
    def _acc():
        s = s_ref[...]
        if compensated:
            t = s + part
            comp = jnp.where(jnp.abs(s) >= jnp.abs(part), (s - t) + part, (part - t) + s)
            s_ref[...] = t
            c_ref[...] = c_ref[...] + comp
        else:
            s_ref[...] = s + part


@functools.partial(jax.jit, static_argnames=("block", "accum_dtype", "compensated", "interpret"))
def mixed_dot_kernel_call(
    a: jax.Array,
    b: jax.Array,
    *,
    block: int = 1 << 16,
    accum_dtype=jnp.float32,
    compensated: bool = False,
    interpret: bool = True,
) -> jax.Array:
    """Returns (2,) accum_dtype (sum, compensation); dot = out.sum()."""
    n = a.shape[0]
    block = min(block, n)
    if n % block or block % (8 * LANES):
        raise ValueError(f"length {n} not divisible by block {block} (a multiple of 1024)")
    rows, brows = n // LANES, block // LANES
    vec = pl.BlockSpec((brows, LANES), row_block)
    acc = pl.BlockSpec((8, LANES), pinned_block)
    s, c = pl.pallas_call(
        functools.partial(_kernel, accum_dtype=accum_dtype, compensated=compensated),
        grid=(rows // brows,),
        in_specs=[vec, vec],
        out_specs=[acc, acc],
        out_shape=[jax.ShapeDtypeStruct((8, LANES), accum_dtype)] * 2,
        interpret=interpret,
        name="mixed_dot",
    )(a.reshape(rows, LANES), b.reshape(rows, LANES))
    return jnp.stack([jnp.sum(s), jnp.sum(c)])
