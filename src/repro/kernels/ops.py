"""jit'd public wrappers around the Pallas kernels.

``interpret`` defaults to True off-TPU (this container is CPU-only; TPU is
the compilation target) and False on real TPU backends.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..sparse.formats import DeviceELL
from .lanczos_fused import spmv_ell_alpha_kernel_call
from .lanczos_update import LANES, lanczos_update_kernel_call
from .mixed_dot import mixed_dot_kernel_call
from .spmv_bsr import blocked_ell_from_csr, spmv_bsr_kernel_call
from .spmv_ell import spmv_ell_kernel_call
from .spmv_ell_packed import spmv_ell_packed_kernel_call

__all__ = [
    "default_interpret",
    "spmv_ell",
    "spmv_ell_alpha",
    "spmv_ell_packed",
    "spmv_bsr",
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


def spmv_ell(mat: DeviceELL, x: jax.Array, accum_dtype=None, **kw) -> jax.Array:
    """SpMV through the Pallas ELL kernel; returns (n_rows,) in accum dtype."""
    acc = jnp.dtype(accum_dtype or jnp.float32)
    # The Pallas gather path needs a real dtype accumulator supported on TPU;
    # f64 accumulation (CPU-only validation) falls back to the jnp reference.
    if acc == jnp.dtype(jnp.float64):
        return mat.matvec(x, accum_dtype=acc)
    kw.setdefault("interpret", default_interpret())
    y = spmv_ell_kernel_call(mat.val, mat.col, x, accum_dtype=acc, **kw)
    return y[: mat.n_rows]


def spmv_ell_alpha(mat: DeviceELL, x: jax.Array, v: jax.Array, accum_dtype=None, **kw):
    """Fused ``w = A @ x`` and ``alpha = <v, w>`` through one Pallas pass.

    ``x`` is the gather source (storage dtype); ``v`` the alpha operand in
    compute dtype, length ``n_rows`` — padded up to the ELL row padding
    (padded rows have zero values, so they add nothing to alpha).  Returns
    ``(w (n_rows,), alpha scalar)`` in accum dtype.  f64 accumulation (CPU
    validation) falls back to the jnp reference pair.
    """
    acc = jnp.dtype(accum_dtype or jnp.float32)
    if acc == jnp.dtype(jnp.float64):
        w = mat.matvec(x, accum_dtype=acc)
        return w, jnp.sum(v.astype(acc) * w)
    kw.setdefault("interpret", default_interpret())
    rows = mat.val.shape[0]
    vpad = jnp.pad(v, (0, rows - v.shape[0])) if v.shape[0] < rows else v
    w, alpha = spmv_ell_alpha_kernel_call(mat.val, mat.col, x, vpad, accum_dtype=acc, **kw)
    return w[: mat.n_rows], alpha[0]


def spmv_ell_packed(
    val: jax.Array,
    scale: jax.Array,
    base: jax.Array,
    dcol: jax.Array,
    x: jax.Array,
    n_rows: int,
    accum_dtype=None,
    **kw,
) -> jax.Array:
    """SpMV over one compressed staged chunk (see ``spmv_ell_packed.py``):
    dequantizes bf16/fp8 values by the row-block scales and cumsums the
    delta-encoded columns in-kernel.  Returns (n_rows,) in accum dtype."""
    acc = jnp.dtype(accum_dtype or jnp.float32)
    if acc == jnp.dtype(jnp.float64):
        # jnp reference for CPU f64 validation (same decompress arithmetic).
        vals = val.astype(acc) * scale.astype(acc)
        cols = base + jnp.cumsum(dcol.astype(jnp.int32), axis=1)
        y = jnp.sum(vals * jnp.take(x, cols).astype(acc), axis=1)
        return y[:n_rows]
    kw.setdefault("interpret", default_interpret())
    y = spmv_ell_packed_kernel_call(val, scale, base, dcol, x, accum_dtype=acc, **kw)
    return y[:n_rows]


def spmv_bsr(blocked, x: jax.Array, accum_dtype=None, **kw) -> jax.Array:
    """SpMV through the blocked-ELL (MXU) kernel.

    ``blocked``: (val, bcol, n_rows) from ``blocked_ell_from_csr``.
    """
    val, bcol, n_rows = blocked
    acc = jnp.dtype(accum_dtype or jnp.float32)
    if acc == jnp.dtype(jnp.float64):
        # jnp fallback for CPU f64 validation
        nbr, slots, bs, _ = val.shape
        xs = x[: nbr * bs].reshape(nbr, bs) if x.shape[0] >= nbr * bs else jnp.pad(
            x, (0, nbr * bs - x.shape[0])).reshape(nbr, bs)
        gathered = jnp.take(xs, bcol, axis=0)  # (nbr, slots, bs)
        y = jnp.einsum("rsij,rsj->ri", val.astype(acc), gathered.astype(acc))
        return y.reshape(-1)[:n_rows]
    kw.setdefault("interpret", default_interpret())
    xpad = x
    nbr, slots, bs, _ = val.shape
    if x.shape[0] < nbr * bs:
        xpad = jnp.pad(x, (0, nbr * bs - x.shape[0]))
    y = spmv_bsr_kernel_call(val, bcol, xpad, accum_dtype=acc, **kw)
    return y[:n_rows]


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
