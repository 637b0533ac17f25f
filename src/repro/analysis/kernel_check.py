"""Pallas kernel static checker: every kernel x every tile the tuner can emit.

The kernels are traced (``jax.make_jaxpr`` — nothing executes, no TPU
needed) and each ``pallas_call`` equation's ``GridMapping`` is checked:

  * **K001** — every block shape divides its operand's padded dims (the ELL
    conversions pad to the tile, ``_fit_tile`` clamps runtime tiles; this
    verifies the contract holds for every candidate the autotuner probes);
  * **K002** — the index map stays in bounds: evaluated at every corner of
    the grid (index maps here are monotone affine, so corners are
    sufficient), ``(block_index + 1) * block_shape`` must not exceed the
    operand extent;
  * **K003** — VMEM footprint: double-buffered block working set
    (``2 x sum(block bytes)``) against a configurable budget
    (``REPRO_ANALYSIS_VMEM_MB``, default 16 MB/core).  Interpret-mode
    traces are exempt — the interpreter has no VMEM ceiling and its tile
    table is deliberately huge.  Compiled, only the vector kernels run
    (``lanczos_update``, ``mixed_dot``); the SpMV kernels are swept in
    interpret mode, the one mode the engine runs them in;
  * **K004** — a grid-pinned accumulator output (an output block mapping
    that is constant along some grid dim, like the (1,)-block alpha of the
    fused SpMV) may only be pinned along *sequentially executed* dims.
    ``PARALLEL_DIMS`` is each kernel's declared contract of which grid dims
    its design allows to be farmed out; a pinned output along one of those
    is a read-modify-write race.
"""

from __future__ import annotations

import itertools
import math
from typing import Dict, FrozenSet, Iterable, List, Optional, Sequence, Tuple

import numpy as np

import jax
from jax.extend import core as jcore
import jax.numpy as jnp

from ..configs import env as envcfg
from .findings import Finding, Findings

__all__ = [
    "KERNELS",
    "PARALLEL_DIMS",
    "vmem_budget_bytes",
    "pallas_eqns",
    "check_pallas_eqn",
    "check_kernel_trace",
    "run",
]

KERNELS = (
    "spmv_ell",
    "spmv_ell_packed",
    "spmv_bsr",
    "lanczos_update",
    "lanczos_fused",
    "mixed_dot",
)

# Which grid dims each kernel's DESIGN permits to execute in parallel.
# Everything else is sequential (TPU grids execute minor-to-major in order;
# the kernels rely on that for their accumulator patterns):
#   spmv_ell / spmv_bsr: row tiles (dim 0) are independent — the width/slot
#     sweep (dim 1) accumulates into the pinned row-tile output;
#   spmv_ell_packed: 1-D grid of independent row tiles (the delta cumsum
#     keeps the full width in one tile, so there is no accumulator at all);
#   lanczos_update / mixed_dot / lanczos_fused: a scalar accumulator is
#     pinned across the whole grid, so NO dim may be parallel.
PARALLEL_DIMS: Dict[str, FrozenSet[int]] = {
    "spmv_ell": frozenset({0}),
    "spmv_ell_packed": frozenset({0}),
    "spmv_bsr": frozenset({0}),
    "lanczos_update": frozenset(),
    "lanczos_fused": frozenset(),
    "mixed_dot": frozenset(),
}


def vmem_budget_bytes(override_mb: Optional[float] = None) -> int:
    mb = override_mb if override_mb is not None else envcfg.get_float(
        "REPRO_ANALYSIS_VMEM_MB"
    )
    return int(mb * (1 << 20))


def pallas_eqns(jaxpr) -> List:
    """Every pallas_call eqn reachable from a (Closed)Jaxpr."""
    if isinstance(jaxpr, jcore.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn)
            continue
        for p in eqn.params.values():
            if isinstance(p, jcore.ClosedJaxpr):
                out.extend(pallas_eqns(p.jaxpr))
            elif isinstance(p, jcore.Jaxpr):
                out.extend(pallas_eqns(p))
            elif isinstance(p, (tuple, list)):
                for item in p:
                    if isinstance(item, (jcore.ClosedJaxpr, jcore.Jaxpr)):
                        out.extend(pallas_eqns(item))
    return out


def _eval_index_map(bm, grid_point: Sequence[int]) -> Tuple[int, ...]:
    cj = bm.index_map_jaxpr
    out = jcore.jaxpr_as_fun(cj)(*(np.int32(g) for g in grid_point))
    return tuple(int(v) for v in out)


def _grid_corners(grid: Sequence[int]) -> Iterable[Tuple[int, ...]]:
    return itertools.product(*[(0,) if g <= 1 else (0, g - 1) for g in grid])


def _block_dims(bm) -> Tuple[int, ...]:
    # Block dims are Blocked(n)/Element(n) records; squeezed (None) dims
    # carry no block extent and count as 1.
    return tuple(int(getattr(b, "block_size", None) or 1) for b in bm.block_shape)


def check_pallas_eqn(
    eqn,
    kernel_name: str,
    *,
    vmem_budget: Optional[int] = None,
    parallel_dims: Optional[FrozenSet[int]] = None,
    context: str = "",
) -> Findings:
    """All four K-rules for one traced pallas_call equation."""
    gm = eqn.params["grid_mapping"]
    grid = tuple(int(g) for g in gm.grid)
    interpret = bool(eqn.params.get("interpret", False))
    if parallel_dims is None:
        parallel_dims = PARALLEL_DIMS.get(kernel_name, frozenset())
    budget = vmem_budget if vmem_budget is not None else vmem_budget_bytes()
    ctx = context or kernel_name
    findings: List[Finding] = []

    vmem = 0
    for pos, bm in enumerate(gm.block_mappings):
        arr = bm.array_aval
        block = _block_dims(bm)
        vmem += math.prod(block) * jnp.dtype(arr.dtype).itemsize
        # K001: blocks divide the padded operand dims
        for d, (bdim, adim) in enumerate(zip(block, arr.shape)):
            if int(adim) % bdim:
                findings.append(
                    Finding(
                        "K001",
                        f"operand {pos} dim {d}: extent {adim} not divisible"
                        f" by block {bdim}",
                        context=ctx,
                    )
                )
        # K002: index map in bounds at every grid corner
        for corner in _grid_corners(grid):
            idx = _eval_index_map(bm, corner)
            for d, (i_blk, bdim, adim) in enumerate(zip(idx, block, arr.shape)):
                if (i_blk + 1) * bdim > int(adim) or i_blk < 0:
                    findings.append(
                        Finding(
                            "K002",
                            f"operand {pos} dim {d}: block index {i_blk} at"
                            f" grid point {corner} addresses"
                            f" [{i_blk * bdim}, {(i_blk + 1) * bdim}) outside"
                            f" extent {adim}",
                            context=ctx,
                        )
                    )
                    break  # one finding per (operand, corner) is enough

    # K003: double-buffered working set vs the VMEM budget (compiled mode)
    if not interpret and 2 * vmem > budget:
        findings.append(
            Finding(
                "K003",
                f"double-buffered block working set {2 * vmem} B exceeds"
                f" VMEM budget {budget} B",
                context=ctx,
            )
        )

    # K004: pinned accumulator outputs along declared-parallel dims
    for pos, bm in enumerate(gm.block_mappings_output):
        for d in range(len(grid)):
            if grid[d] <= 1:
                continue
            lo = [0] * len(grid)
            hi = list(lo)
            hi[d] = grid[d] - 1
            if _eval_index_map(bm, lo) == _eval_index_map(bm, hi) and d in parallel_dims:
                findings.append(
                    Finding(
                        "K004",
                        f"output {pos} is grid-pinned along dim {d}, which"
                        f" {kernel_name} declares parallel — accumulation"
                        f" across parallel steps is a write race",
                        context=ctx,
                    )
                )
    return findings


def check_kernel_trace(
    fn,
    avals: Sequence[jax.ShapeDtypeStruct],
    kernel_name: str,
    *,
    vmem_budget: Optional[int] = None,
    parallel_dims: Optional[FrozenSet[int]] = None,
    context: str = "",
) -> Findings:
    """Trace ``fn(*avals)`` and check every pallas_call inside.

    An entrypoint that *raises* on bad tiles (the kernels' own divisibility
    guards) reports as K001 rather than crashing the pass.
    """
    ctx = context or kernel_name
    try:
        jaxpr = jax.make_jaxpr(fn)(*avals)
    except ValueError as exc:
        return [Finding("K001", f"kernel rejected the configuration: {exc}", context=ctx)]
    findings: List[Finding] = []
    for eqn in pallas_eqns(jaxpr):
        findings.extend(
            check_pallas_eqn(
                eqn, kernel_name,
                vmem_budget=vmem_budget, parallel_dims=parallel_dims, context=ctx,
            )
        )
    return findings


# ------------------------------------------------------------- the sweep


def _pad_to(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def _ell_tile_universe(dtype, rows: int, width: int):
    """Every (block_r, block_w, rows_pad, width_pad, interpret) the engine
    can actually run: the static-table prior plus the autotuner's candidate
    grid, clamped by ``_fit_tile`` against the layout the conversions build
    — exactly what ``ell_matvec`` does at runtime.  The SpMV kernels run
    only under the interpreter (``kernels.engine.spmv_runs_pallas``), so that
    is the one execution mode the universe holds."""
    from ..kernels.engine import _candidate_tiles, _fit_tile, select_tiles, spmv_runs_pallas

    for interpret in filter(spmv_runs_pallas, (False, True)):
        prior = select_tiles(rows, width, dtype, interpret=interpret)
        width_pad = _pad_to(width, 128)  # slot_tile in make_operator
        rows_pad = _pad_to(rows, prior.block_r)
        configs = {prior}
        configs.update(_candidate_tiles(prior, dtype, interpret, prior.block_size))
        for cfg in sorted(configs, key=lambda c: (c.block_r, c.block_w)):
            br = _fit_tile(cfg.block_r, rows_pad)
            bw = _fit_tile(cfg.block_w, width_pad)
            yield br, bw, rows_pad, width_pad, interpret


def run(
    vmem_budget_mb: Optional[float] = None,
    *,
    rows: int = 960,
    width: int = 48,
    dtypes=(jnp.float32, jnp.bfloat16),
) -> Findings:
    """The CI sweep over every kernel entrypoint and emittable tile."""
    from ..kernels.engine import _ITER_BSR_BLOCKS
    from ..kernels.lanczos_fused import spmv_ell_alpha_kernel_call
    from ..kernels.spmv_bsr import spmv_bsr_kernel_call
    from ..kernels.spmv_ell import spmv_ell_kernel_call

    budget = vmem_budget_bytes(vmem_budget_mb)
    findings: List[Finding] = []
    f32 = jnp.float32
    i32 = jnp.int32

    for dtype in dtypes:
        dname = jnp.dtype(dtype).name
        for br, bw, rpad, wpad, interp in _ell_tile_universe(dtype, rows, width):
            val = jax.ShapeDtypeStruct((rpad, wpad), dtype)
            col = jax.ShapeDtypeStruct((rpad, wpad), i32)
            x = jax.ShapeDtypeStruct((rows,), dtype)
            v = jax.ShapeDtypeStruct((rpad,), f32)
            mode = "interp" if interp else "compiled"
            findings.extend(
                check_kernel_trace(
                    lambda a, c, xx: spmv_ell_kernel_call(
                        a, c, xx, block_r=br, block_w=bw, accum_dtype=f32,
                        interpret=interp,
                    ),
                    (val, col, x), "spmv_ell", vmem_budget=budget,
                    context=f"spmv_ell/{dname}/r{br}xw{bw}/{mode}",
                )
            )
            findings.extend(
                check_kernel_trace(
                    lambda a, c, xx, vv: spmv_ell_alpha_kernel_call(
                        a, c, xx, vv, block_r=br, block_w=bw, accum_dtype=f32,
                        interpret=interp,
                    ),
                    (val, col, x, v), "lanczos_fused", vmem_budget=budget,
                    context=f"lanczos_fused/{dname}/r{br}xw{bw}/{mode}",
                )
            )

        # BSR: the tile is fixed by the block edge; sweep the probe set.
        for bs in _ITER_BSR_BLOCKS:
            nbr = _pad_to(rows, bs) // bs
            slots = 4
            val = jax.ShapeDtypeStruct((nbr, slots, bs, bs), dtype)
            bcol = jax.ShapeDtypeStruct((nbr, slots), i32)
            x = jax.ShapeDtypeStruct((nbr * bs,), dtype)
            findings.extend(
                check_kernel_trace(
                    lambda a, c, xx: spmv_bsr_kernel_call(
                        a, c, xx, accum_dtype=f32, interpret=True
                    ),
                    (val, bcol, x), "spmv_bsr", vmem_budget=budget,
                    context=f"spmv_bsr/{dname}/bs{bs}",
                )
            )

    # Packed-ELL (compressed staging): 1-D row grid, full width per tile.
    # The staging layer builds rows_pad at the STAGED dtype's sublane
    # minimum (bf16: 16, fp8: 32) and block_r adapts via _fit_tile, so
    # the universe is the packed dtypes x index widths x row tiles.
    from ..kernels.engine import _fit_tile as _fit
    from ..kernels.spmv_ell_packed import (
        PACKED_VALUE_DTYPES,
        spmv_ell_packed_kernel_call,
    )

    wpadp = _pad_to(width, 128)
    for pmode, vdt in sorted(PACKED_VALUE_DTYPES.items()):
        min_r = {1: 32, 2: 16}.get(np.dtype(vdt).itemsize, 8)
        rpadp = _pad_to(rows, min_r)
        for idt in (jnp.int16, jnp.int32):
            for want_br in (8, 16, 32):
                br = _fit(max(want_br, min_r), rpadp)
                pval = jax.ShapeDtypeStruct((rpadp, wpadp), np.dtype(vdt))
                pscale = jax.ShapeDtypeStruct((rpadp, 1), f32)
                pbase = jax.ShapeDtypeStruct((rpadp, 1), i32)
                pdcol = jax.ShapeDtypeStruct((rpadp, wpadp), idt)
                px = jax.ShapeDtypeStruct((rows,), f32)
                # SpMV kernels run only under the interpreter.
                findings.extend(
                    check_kernel_trace(
                        lambda a, s, b, d, xx, _br=br: spmv_ell_packed_kernel_call(
                            a, s, b, d, xx, block_r=_br, accum_dtype=f32, interpret=True
                        ),
                        (pval, pscale, pbase, pdcol, px),
                        "spmv_ell_packed", vmem_budget=budget,
                        context=f"spmv_ell_packed/{pmode}/{jnp.dtype(idt).name}/r{br}/interp",
                    )
                )

    # Vector kernels — the ones the TPU path runs compiled — at lengths that
    # exercise the block clamp and the padding wrappers, up to the paper's
    # Wikipedia matrix (n = 3,566,907, not a multiple of any block).
    from ..kernels import ops as kops

    for n in (960, 4096, 8000, 8192, 3_566_907):
        a = jax.ShapeDtypeStruct((n,), f32)
        s = jax.ShapeDtypeStruct((), f32)
        findings.extend(
            check_kernel_trace(
                lambda w, v, vp, al, be: kops.lanczos_update(
                    w, v, vp, al, be, accum_dtype=f32, interpret=False
                ),
                (a, a, a, s, s), "lanczos_update", vmem_budget=budget,
                context=f"lanczos_update/n{n}",
            )
        )
        for comp in (False, True):
            findings.extend(
                check_kernel_trace(
                    lambda p, q: kops.mixed_dot(
                        p, q, accum_dtype=f32, compensated=comp, interpret=False
                    ),
                    (a, a), "mixed_dot", vmem_budget=budget,
                    context=f"mixed_dot/n{n}/{'kahan' if comp else 'plain'}",
                )
            )
    return findings
