"""jaxpr walking utilities for the precision-flow verifier.

Everything here operates on traces (``jax.make_jaxpr`` output) — nothing is
executed.  Two products:

  * :func:`count_ops_by_dtype` — element-operation counts per float dtype,
    descending into sub-jaxprs with the right multipliers (``scan`` bodies
    count ``length`` times, ``pallas_call`` bodies once per grid step, a
    ``while`` body once — a trace records a dynamic loop's body, not its
    trip count);
  * :func:`conversions` — every ``convert_element_type`` with its
    (src, dst) dtypes and a def-use link to the producing conversion, the
    raw material of the upcast / double-rounding rules.

Counting conventions (shared with ``core.precision.phase_op_counts`` via the
parity assertion's ratio tolerance): elementwise arithmetic counts its
output size in the *output* dtype; ``dot_general`` counts its
multiply-accumulates (``prod(out_shape) * prod(contracted dims)``) in the
output dtype; reductions count their operand size in the operand dtype.
Conversions, layout ops, and integer index arithmetic are not "work".
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
from jax.extend import core as jcore
import jax.numpy as jnp

__all__ = [
    "ARITH_PRIMS",
    "REDUCE_PRIMS",
    "Conversion",
    "count_ops_by_dtype",
    "conversions",
    "make_jaxpr_of",
]

# Elementwise float arithmetic counted as work (output-size ops).
ARITH_PRIMS = frozenset(
    {
        "add", "sub", "mul", "div", "rem", "neg", "sign", "abs",
        "max", "min", "pow", "integer_pow", "sqrt", "rsqrt", "cbrt",
        "exp", "log", "log1p", "expm1", "tanh", "logistic",
        "atan2", "erf", "square",
    }
)
# Reductions counted as operand-size ops in the operand dtype.
REDUCE_PRIMS = frozenset(
    {"reduce_sum", "reduce_max", "reduce_min", "reduce_prod", "cumsum", "cumprod"}
)
# Collectives: per-device arithmetic negligible; not counted.
_SKIP_PRIMS = frozenset(
    {
        "convert_element_type", "broadcast_in_dim", "reshape", "transpose",
        "squeeze", "slice", "dynamic_slice", "dynamic_update_slice", "concatenate",
        "gather", "scatter", "scatter-add", "pad", "iota", "select_n", "rev",
        "copy", "device_put", "stop_gradient", "eq", "ne", "lt", "le", "gt", "ge",
        "and", "or", "not", "xor", "is_finite", "argmax", "argmin", "sort",
        "reduce_and", "reduce_or", "rng_bit_generator", "clamp", "round", "floor",
        "ceil", "nextafter", "real", "imag", "sharding_constraint",
        "all_gather", "psum", "pmax", "pmin", "ppermute", "axis_index",
    }
)


def _aval_size(aval) -> int:
    shape = getattr(aval, "shape", ())
    return int(math.prod(shape)) if shape else 1


def _is_float(aval) -> bool:
    dt = getattr(aval, "dtype", None)
    return dt is not None and jnp.issubdtype(dt, jnp.floating)


def _dtype_name(aval) -> str:
    return jnp.dtype(aval.dtype).name


def _sub_jaxprs(params: Any) -> List[Any]:
    """Every Jaxpr/ClosedJaxpr reachable from an eqn's params (one level)."""
    out: List[Any] = []

    def visit(v):
        if isinstance(v, jcore.ClosedJaxpr):
            out.append(v.jaxpr)
        elif isinstance(v, jcore.Jaxpr):
            out.append(v)
        elif isinstance(v, (tuple, list)):
            for item in v:
                visit(item)
        elif isinstance(v, dict):
            for item in v.values():
                visit(item)

    for v in params.values():
        visit(v)
    return out


def _pallas_grid_steps(params: Dict[str, Any]) -> int:
    gm = params.get("grid_mapping")
    grid = getattr(gm, "grid", ()) if gm is not None else params.get("grid", ())
    steps = 1
    for g in grid:
        try:
            steps *= int(g)
        except (TypeError, ValueError):  # dynamic/symbolic dim: count once
            pass
    return max(steps, 1)


def _eqn_scale(eqn) -> int:
    """Multiplier applied to ops inside this eqn's sub-jaxprs."""
    name = eqn.primitive.name
    if name == "scan":
        return int(eqn.params.get("length", 1))
    if name == "pallas_call":
        return _pallas_grid_steps(eqn.params)
    # while: the trace holds one body; trip count is dynamic -> count once.
    return 1


def _dot_general_macs(eqn) -> int:
    (lhs, rhs) = eqn.invars[:2]
    dims = eqn.params["dimension_numbers"]
    (lhs_contract, _), _ = dims
    out_size = _aval_size(eqn.outvars[0].aval)
    contracted = 1
    for d in lhs_contract:
        contracted *= int(lhs.aval.shape[d])
    return out_size * max(contracted, 1)


def count_ops_by_dtype(jaxpr, _scale: int = 1) -> Dict[str, int]:
    """Float element-op counts per dtype name for a (Closed)Jaxpr."""
    if isinstance(jaxpr, jcore.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    counts: Dict[str, int] = {}

    def add(name: str, ops: int) -> None:
        if ops:
            counts[name] = counts.get(name, 0) + ops

    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        out_aval = eqn.outvars[0].aval if eqn.outvars else None
        if name == "dot_general":
            if out_aval is not None and _is_float(out_aval):
                add(_dtype_name(out_aval), _scale * _dot_general_macs(eqn))
            continue
        if name in REDUCE_PRIMS:
            in_aval = eqn.invars[0].aval
            if _is_float(in_aval):
                add(_dtype_name(in_aval), _scale * _aval_size(in_aval))
            continue
        if name in ARITH_PRIMS:
            if out_aval is not None and _is_float(out_aval):
                add(_dtype_name(out_aval), _scale * _aval_size(out_aval))
            continue
        subs = _sub_jaxprs(eqn.params)
        if not subs:
            continue
        scale = _scale * _eqn_scale(eqn)
        if name == "cond":
            # Branches are alternatives: count the heaviest one.
            best: Dict[str, int] = {}
            for sub in subs:
                c = count_ops_by_dtype(sub, scale)
                if sum(c.values()) > sum(best.values()):
                    best = c
            for dt, c in best.items():
                add(dt, c)
            continue
        for sub in subs:
            for dt, c in count_ops_by_dtype(sub, scale).items():
                add(dt, c)
    return counts


class Conversion(NamedTuple):
    """One convert_element_type: src -> dst, with the producing conversion
    of its operand when that operand itself came from a convert."""

    src: str
    dst: str
    prev_src: Optional[str]  # dtype the operand held before ITS conversion


def conversions(jaxpr) -> List[Conversion]:
    """Every float->float conversion in the trace (recursing into sub-jaxprs),
    def-use-linked one step back for double-rounding detection."""
    if isinstance(jaxpr, jcore.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    out: List[Conversion] = []
    produced_by_convert: Dict[Any, str] = {}  # outvar -> src dtype name

    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "convert_element_type":
            in_aval = eqn.invars[0].aval
            out_aval = eqn.outvars[0].aval
            if not (_is_float(in_aval) and _is_float(out_aval)):
                continue
            src, dst = _dtype_name(in_aval), _dtype_name(out_aval)
            if src == dst:
                continue
            invar = eqn.invars[0]
            prev = produced_by_convert.get(invar)
            out.append(Conversion(src, dst, prev))
            produced_by_convert[eqn.outvars[0]] = src
        else:
            for sub in _sub_jaxprs(eqn.params):
                out.extend(conversions(sub))
    return out


def make_jaxpr_of(fn, *avals) -> jcore.ClosedJaxpr:
    """``jax.make_jaxpr`` over ShapeDtypeStructs — tracing only, no execution."""
    return jax.make_jaxpr(fn)(*avals)


def abstract(shape: Tuple[int, ...], dtype) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))
