"""SpMV cost per layout on the Wikipedia matrix, and a check of the two
Mosaic kernels against host f64.

    python benchmarks/probes/spmv_wk.py                  # WK at n = 3,566,907
    python benchmarks/probes/spmv_wk.py --n 20000        # a quick small run

Generates the WK matrix as ``chip_smoke.py`` does (``generate("web", n,
12.6, values="normalized", seed=0)``), prints its layout statistics, then
times one jitted SpMV per layout and accumulation dtype (median of
``--reps`` runs, host clock around ``block_until_ready``, compile excluded):

  coo     every non-zero gathered, multiplied and scattered (``row_sums``);
  hybrid  the layout auto selection builds for WK: capped-width ELL rows
          (gather + row reduce) and a COO tail of the long rows' overflow;
  gather  the gather and multiply of every non-zero, summed to one scalar
          (no scatter): the floor of any gather-based SpMV.

On a TPU it also runs ``lanczos_update`` and ``mixed_dot`` compiled at the
matrix's n and prints their error against NumPy f64.  One JSON line per
measurement on standard output.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
WK_N = 3_566_907


def _emit(**fields) -> None:
    print(json.dumps(fields, default=str), flush=True)


def _median_seconds(fn, *args, reps: int) -> float:
    fn(*args).block_until_ready()  # compile and warm up
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn(*args).block_until_ready()
        times.append(time.perf_counter() - t0)
    return statistics.median(times)


def probe_spmv(csr, reps: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.kernels.engine import choose_format, matrix_stats
    from repro.sparse.formats import to_device_coo, to_device_hybrid

    stats = matrix_stats(csr)
    _emit(probe="stats", auto_format=choose_format(stats), **stats.as_dict())
    coo = to_device_coo(csr, dtype=jnp.float32)
    hyb = to_device_hybrid(csr, dtype=jnp.float32, width_cap=stats.hyb_width)
    x_host = np.random.default_rng(1).standard_normal(csr.n)
    want = csr.to_scipy() @ x_host
    for acc in (jnp.float32, jnp.float64):
        x = jnp.asarray(x_host, acc)

        def gather(m, v, acc=acc):
            return jnp.sum(m.val.astype(acc) * jnp.take(v, m.col).astype(acc))

        paths = {
            "coo": jax.jit(lambda m, v, acc=acc: m.matvec(v, accum_dtype=acc)),
            "hybrid": jax.jit(lambda m, v, acc=acc: m.matvec(v, accum_dtype=acc)),
            "gather": jax.jit(gather),
        }
        mats = {"coo": coo, "hybrid": hyb, "gather": coo}
        for name, fn in paths.items():
            sec = _median_seconds(fn, mats[name], x, reps=reps)
            out = {"probe": "spmv", "layout": name, "accum": jnp.dtype(acc).name,
                   "median_ms": sec * 1e3, "reps": reps}
            if name != "gather":
                y = np.asarray(fn(mats[name], x), np.float64)
                out["max_rel_err"] = float(np.max(np.abs(y - want)) / np.max(np.abs(want)))
            _emit(**out)


def probe_kernels(n: int) -> None:
    import jax.numpy as jnp

    from repro.kernels import ops as kops

    rng = np.random.default_rng(2)
    w, v, vp = (rng.standard_normal(n).astype(np.float32) for _ in range(3))
    a, b = np.float32(0.3), np.float32(0.7)
    u, nrm = kops.lanczos_update(
        jnp.asarray(w), jnp.asarray(v), jnp.asarray(vp), jnp.float32(a), jnp.float32(b),
        accum_dtype=jnp.float32, interpret=False,
    )
    ref = w.astype(np.float64) - a * v.astype(np.float64) - b * vp.astype(np.float64)
    _emit(probe="kernel", name="lanczos_update", n=n,
          max_abs_err=float(np.max(np.abs(np.asarray(u, np.float64) - ref))),
          norm_rel_err=float(abs(float(nrm) - ref @ ref) / (ref @ ref)))
    for dtype in (jnp.float32, jnp.bfloat16):
        xa, xb = jnp.asarray(w, dtype), jnp.asarray(v, dtype)
        ha, hb = np.asarray(xa, np.float64), np.asarray(xb, np.float64)
        scale = np.linalg.norm(ha) * np.linalg.norm(hb)
        for comp in (False, True):
            got = float(kops.mixed_dot(xa, xb, accum_dtype=jnp.float32,
                                       compensated=comp, interpret=False))
            _emit(probe="kernel", name="mixed_dot", dtype=jnp.dtype(dtype).name,
                  compensated=comp, n=n, rel_err=abs(got - ha @ hb) / scale)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--n", type=int, default=WK_N)
    ap.add_argument("--reps", type=int, default=5)
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    from repro.launch.compile_cache import enable_compile_cache
    from repro.sparse import generate

    enable_compile_cache()
    jax.config.update("jax_enable_x64", True)
    dev = jax.devices()[0]
    t0 = time.perf_counter()
    csr = generate("web", args.n, 12.6, seed=0, values="normalized")
    _emit(probe="setup", platform=dev.platform, kind=dev.device_kind, n=csr.n,
          nnz=csr.nnz, seconds=time.perf_counter() - t0)
    probe_spmv(csr, args.reps)
    if dev.platform == "tpu":
        probe_kernels(csr.n)
    return 0


if __name__ == "__main__":
    sys.exit(main())
