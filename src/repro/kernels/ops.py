"""Public wrappers around the vector Pallas kernels (``lanczos_update``,
``mixed_dot``).

``interpret`` defaults to True off-TPU (this container is CPU-only; TPU is
the compilation target) and False on real TPU backends.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .lanczos_update import LANES, lanczos_update_kernel_call
from .mixed_dot import mixed_dot_kernel_call

__all__ = [
    "default_interpret",
    "mixed_dot",
    "lanczos_update",
]


# Elements per grid step of the vector kernels (lanczos_update, mixed_dot):
# a (512, 128) f32 tile — 256 KiB per operand, so a step moves ~1 MiB.
VECTOR_BLOCK = 1 << 16


def default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _vector_block(n: int, block: int, dtype) -> int:
    """Vector-kernel block for an n-vector: at most ``block``, and no more
    than n rounded up to the dtype's minimum tile — (8, 128) for 32 bits,
    (16, 128) for 16 — (callers zero-pad n to it)."""
    tile = (32 // jnp.dtype(dtype).itemsize) * LANES
    return min(block, -(-n // tile) * tile)


def mixed_dot(
    a: jax.Array, b: jax.Array, accum_dtype=None, compensated: bool = False, **kw
) -> jax.Array:
    acc = jnp.dtype(accum_dtype or jnp.float32)
    if acc == jnp.dtype(jnp.float64):
        return jnp.sum(a.astype(acc) * b.astype(acc))
    kw.setdefault("interpret", default_interpret())
    # Zero-pad up to the kernel block (padding lanes contribute nothing to
    # the sum or its compensation) — mirrors lanczos_update below.
    n = a.shape[0]
    narrow = min(a.dtype, b.dtype, key=lambda d: jnp.dtype(d).itemsize)
    block = _vector_block(n, kw.pop("block", VECTOR_BLOCK), narrow)
    pad = (-n) % block
    if pad:
        a, b = jnp.pad(a, (0, pad)), jnp.pad(b, (0, pad))
    out = mixed_dot_kernel_call(
        a, b, block=block, accum_dtype=acc, compensated=compensated, **kw
    )
    return out.sum()


def lanczos_update(w, v, v_prev, alpha, beta, accum_dtype=None, **kw):
    """Fused ``u = w - alpha v - beta v_prev`` + ``||u||^2`` (one memory pass).

    Arbitrary lengths are zero-padded up to the kernel block (padding lanes
    produce u = 0 and contribute nothing to the norm) and sliced back.
    """
    acc = jnp.dtype(accum_dtype or jnp.float32)
    if acc == jnp.dtype(jnp.float64):
        from .ref import lanczos_update_ref

        return lanczos_update_ref(w, v, v_prev, alpha, beta, accum_dtype=acc)
    kw.setdefault("interpret", default_interpret())
    n = w.shape[0]
    block = _vector_block(n, kw.pop("block", VECTOR_BLOCK), w.dtype)
    pad = (-n) % block
    if pad:
        w, v, v_prev = (jnp.pad(a, (0, pad)) for a in (w, v, v_prev))
    u, nrm = lanczos_update_kernel_call(
        w, v, v_prev, alpha, beta, block=block, accum_dtype=acc, **kw
    )
    return u[:n], nrm[0]
