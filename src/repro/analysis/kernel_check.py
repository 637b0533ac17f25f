"""Pallas kernel static checker: the vector kernels at the lengths they run.

The kernels are traced (``jax.make_jaxpr`` — nothing executes, no TPU
needed) and each ``pallas_call`` equation's ``GridMapping`` is checked:

  * **K001** — every block shape divides its operand's padded dims (the
    wrappers pad vectors to the kernel block; this verifies they do);
  * **K002** — the index map stays in bounds: evaluated at every corner of
    the grid (index maps here are monotone affine, so corners are
    sufficient), ``(block_index + 1) * block_shape`` must not exceed the
    operand extent;
  * **K003** — VMEM footprint: double-buffered block working set
    (``2 x sum(block bytes)``) against a configurable budget
    (``REPRO_ANALYSIS_VMEM_MB``, default 16 MB/core).  Interpret-mode
    traces are exempt — the interpreter has no VMEM ceiling;
  * **K004** — a grid-pinned accumulator output (an output block mapping
    that is constant along some grid dim, like the scalar norm of
    ``lanczos_update``) may only be pinned along *sequentially executed* dims.
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
    "lanczos_update",
    "mixed_dot",
)

# Which grid dims each kernel's DESIGN permits to execute in parallel.
# Everything else is sequential (TPU grids execute minor-to-major in order;
# the kernels rely on that for their accumulator patterns):
#   lanczos_update / mixed_dot: a scalar accumulator is pinned across the
#     whole grid, so NO dim may be parallel.
PARALLEL_DIMS: Dict[str, FrozenSet[int]] = {
    "lanczos_update": frozenset(),
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


def run(vmem_budget_mb: Optional[float] = None) -> Findings:
    """The CI sweep over the vector kernels' entrypoints."""
    budget = vmem_budget_bytes(vmem_budget_mb)
    findings: List[Finding] = []
    f32 = jnp.float32

    # Compiled, at lengths that exercise the block clamp and the padding
    # wrappers, up to the paper's Wikipedia matrix (n = 3,566,907, not a
    # multiple of any block).
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
