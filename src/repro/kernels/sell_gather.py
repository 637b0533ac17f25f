"""Pallas TPU kernel: the ``"sell"`` SpMV's gather of ``x``, bit for bit
``jnp.take(x, col)``.

XLA gathers one element at ~7.5 ns on a v5e whatever the layout (PERF.md
§5): the cost is per index, not per byte.  This kernel keeps the whole of
``x`` resident in VMEM and gathers it with the scalar unit's addressed row
loads:

  * ``x`` (32-bit) is viewed as ``(ceil(n / 128), 128)`` rows and held in
    VMEM once per call: a constant block, single-buffered.  Its bits are
    moved as ``int32`` and never computed on, so ``inf``, NaN payloads,
    ``-0.0`` and subnormals come out exactly as ``jnp.take`` gives them.
  * ``col`` streams through in lane-dense ``(rows, 128)`` blocks.  Per
    chunk of slots the vector unit shifts out each slot's row ``col >> 7``
    and a DMA copies the rows to one of two SMEM buffers, so the scalar
    loop reads them at fixed offsets while the next chunk's copy is in
    flight.
  * Per 128 slots, each slot's ``x`` row is loaded at its dynamic row
    address into a ``(128, 128)`` scratch (one scalar load and one
    addressed vector load a slot).  The scratch is transposed, and lane
    ``col & 127`` of each slot is picked by a ``where`` against
    ``INT32_MIN`` and an integer ``max`` over sublanes: a selection, with
    no multiply and no ``dot``.

The unrolled slot loop is what makes it fast (1.47 ns a slot on a v5e
against ``jnp.take``'s 7.55, PERF.md §6): its chunk is 1,024 slots
compiled, and one group of 128 under the interpreter.  The chunk body is
written once and picks its SMEM buffer at run time: one body for each
buffer ran at 1.16 ns, but tracing and lowering it took 5.4 s of every
process's set-up on the chip's host.
``executor`` decides from ``x``'s length and dtype whether the kernel may
run; callers fall back to ``jnp.take`` otherwise.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .lanczos_update import LANES, pinned_block, row_block

__all__ = ["executor", "footprint_bytes", "sell_gather", "vmem_capacity_bytes"]

# VMEM of one v5e TensorCore, for interpret mode where no TPU is attached.
V5E_VMEM_BYTES = 128 << 20
# VMEM left to Mosaic's own scratch, above the kernel's buffers.
VMEM_MARGIN_BYTES = 16 << 20
# Chunks of slots per grid step (at least two: two SMEM buffers).
BLOCK_CHUNKS = 16
_FILL = -(2**31)  # INT32_MIN: any 32-bit pattern wins the max against it


def _chunk_groups(interpret: bool) -> int:
    """128-slot groups per chunk: the unrolled scalar loop's length."""
    return 1 if interpret else 8


def _x_rows(n: int) -> int:
    return -(-n // LANES)


def footprint_bytes(n: int, interpret: bool = False) -> int:
    """VMEM the kernel holds for an ``n``-long ``x``: ``x`` once, the
    double-buffered ``col`` and output blocks, the shifted rows of a block
    and the ``(128, 128)`` row scratch."""
    block = BLOCK_CHUNKS * _chunk_groups(interpret) * LANES * 4  # (rows, 128) int32
    return _x_rows(n) * LANES * 4 + 5 * block + LANES * LANES * 4


@functools.cache
def vmem_capacity_bytes() -> int:
    """VMEM of the attached TPU's core (``get_tpu_info``); a v5e's where
    none is attached."""
    if pltpu.is_tpu_device():
        return pltpu.get_tpu_info().vmem_capacity_bytes
    return V5E_VMEM_BYTES


def executor(n: int, dtype, slots: int, interpret: bool) -> str:
    """What gathers ``slots`` entries of an ``n``-long ``x`` of ``dtype``:
    the kernel (``"mosaic"``, or ``"pallas_interpret"`` under the
    interpreter) where ``x`` is a 32-bit float, ``slots`` a positive
    multiple of 128 and the footprint, with the margin, fits the core's
    VMEM; ``"xla"`` (``jnp.take``) otherwise.  Under ``vmap`` the batch is
    a leading grid axis and one batch element's ``x`` is resident at a
    time, so the same footprint holds."""
    if (
        jnp.dtype(dtype) != jnp.dtype(jnp.float32)
        or slots <= 0
        or slots % LANES
        or footprint_bytes(n, interpret) + VMEM_MARGIN_BYTES > vmem_capacity_bytes()
    ):
        return "xla"
    return "pallas_interpret" if interpret else "mosaic"


def _each_slot(body, unroll: bool) -> None:
    """``body(slot)`` for the 128 slots of a group: unrolled with static
    slots when compiled (static SMEM and scratch addresses), a loop under
    the interpreter (whose lowering time grows with the body)."""
    if unroll:
        for slot in range(LANES):
            body(slot)
    else:
        jax.lax.fori_loop(
            jnp.int32(0), jnp.int32(LANES), lambda slot, c: (body(slot), c)[1], jnp.int32(0)
        )


def _kernel(col_ref, x_ref, out_ref, rows_ref, rsh_ref, smem, sem, *, groups, chunks, unroll):
    # rsh: each slot's x row, clamped: a partial last block holds stale
    # indices past the end, whose loads must stay inside x.
    rsh_ref[...] = jnp.minimum(jnp.maximum(col_ref[...] >> 7, 0), x_ref.shape[0] - 1)

    def copy(k, b):
        # int32 offsets and buffer indices: under x64 Python ints lower as i64
        rows = pl.ds(jnp.int32(k) * groups, groups)
        b = jnp.asarray(b, jnp.int32)
        return pltpu.make_async_copy(rsh_ref.at[rows], smem.at[b], sem.at[b])

    copy(0, 0).start()
    copy(1, 1).start()

    def chunk(k, b):
        for gg in range(groups):

            def load_row(slot, gg=gg):
                rows_ref[pl.ds(slot, 1), :] = x_ref[pl.ds(smem[b, gg, slot], 1), :]

            _each_slot(load_row, unroll)
            g = k * groups + gg
            picked = rows_ref[...].T  # [lane, slot]
            lane = col_ref[pl.ds(g, 1), :] & (LANES - 1)
            sub = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
            pick = jnp.where(sub == lane, picked, jnp.int32(_FILL))
            out_ref[pl.ds(g, 1), :] = jnp.max(pick, axis=0, keepdims=True)

    def step(k, carry):
        b = jax.lax.rem(k, jnp.int32(2))
        copy(k, b).wait()
        chunk(k, b)

        @pl.when(k + 2 < chunks)
        def _():
            copy(k + 2, b).start()

        return carry

    jax.lax.fori_loop(jnp.int32(0), jnp.int32(chunks), step, jnp.int32(0))


@functools.partial(jax.jit, static_argnames=("interpret",))
def sell_gather(x: jax.Array, col: jax.Array, *, interpret: bool = False) -> jax.Array:
    """``jnp.take(x, col)`` for a 32-bit ``x`` and in-range ``col`` whose
    length is a multiple of 128 (``to_device_sell`` pads the layout to it),
    with the same bits."""
    slots = col.shape[0]
    if slots % LANES:
        raise ValueError(f"sell_gather: {slots} slots is not a multiple of {LANES}")
    if jnp.dtype(x.dtype).itemsize != 4:
        raise ValueError(f"sell_gather: x must be 32-bit, got {x.dtype}")
    n = x.shape[0]
    rows = _x_rows(n)
    groups = _chunk_groups(interpret)
    block_rows = BLOCK_CHUNKS * groups
    xi = jax.lax.bitcast_convert_type(x, jnp.int32)
    if rows * LANES != n:
        xi = jnp.pad(xi, (0, rows * LANES - n))
    out = pl.pallas_call(
        functools.partial(_kernel, groups=groups, chunks=BLOCK_CHUNKS, unroll=not interpret),
        grid=(pl.cdiv(slots // LANES, block_rows),),
        in_specs=[
            pl.BlockSpec((block_rows, LANES), row_block),
            pl.BlockSpec((rows, LANES), pinned_block, pipeline_mode=pl.Buffered(1)),
        ],
        out_specs=pl.BlockSpec((block_rows, LANES), row_block),
        out_shape=jax.ShapeDtypeStruct((slots // LANES, LANES), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((LANES, LANES), jnp.int32),
            pltpu.VMEM((block_rows, LANES), jnp.int32),
            pltpu.SMEM((2, groups, LANES), jnp.int32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=footprint_bytes(n, interpret) + VMEM_MARGIN_BYTES
        ),
        cost_estimate=pl.CostEstimate(
            flops=0, transcendentals=0, bytes_accessed=8 * slots + 4 * rows * LANES
        ),
        interpret=interpret,
        name="sell_gather",
    )(col.reshape(-1, LANES), xi.reshape(rows, LANES))
    return jax.lax.bitcast_convert_type(out.reshape(slots), x.dtype)
