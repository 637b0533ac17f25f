"""Pure-jnp references: the ELL SpMV body the engine runs
(``spmv_ell_ref``) and the oracles of the vector Pallas kernels
(``tests/test_kernels.py`` sweeps shapes/dtypes and asserts allclose).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["spmv_ell_ref", "mixed_dot_ref", "lanczos_update_ref"]


def spmv_ell_ref(
    val: jax.Array, col: jax.Array, x: jax.Array, accum_dtype=jnp.float32
) -> jax.Array:
    """ELL SpMV: y[r] = sum_s val[r, s] * x[col[r, s]] with wide accumulation."""
    gathered = jnp.take(x, col).astype(accum_dtype)
    return (val.astype(accum_dtype) * gathered).sum(axis=1)


def mixed_dot_ref(a: jax.Array, b: jax.Array, accum_dtype=jnp.float32) -> jax.Array:
    """Mixed-precision dot: storage-dtype inputs, accum-dtype products + sum."""
    return jnp.sum(a.astype(accum_dtype) * b.astype(accum_dtype))


def lanczos_update_ref(
    w: jax.Array,
    v: jax.Array,
    v_prev: jax.Array,
    alpha: jax.Array,
    beta: jax.Array,
    accum_dtype=jnp.float32,
):
    """Fused three-term recurrence + norm^2 of the result (single pass).

    u = w - alpha v - beta v_prev;  returns (u in w.dtype, ||u||^2 in accum).
    """
    acc = accum_dtype
    u = w.astype(acc) - alpha.astype(acc) * v.astype(acc) - beta.astype(acc) * v_prev.astype(acc)
    return u.astype(w.dtype), jnp.sum(u * u)
