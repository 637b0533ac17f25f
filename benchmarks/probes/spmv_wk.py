"""SpMV cost per layout on the Wikipedia matrix, the ``"sell"`` gather
kernel against XLA's gather, and a check of the Mosaic kernels against host
f64.

    python benchmarks/probes/spmv_wk.py                  # WK at n = 3,566,907
    python benchmarks/probes/spmv_wk.py --n 20000        # a quick small run
    python benchmarks/probes/spmv_wk.py --matrix gap-kron-s20 --only sell-gather

Generates the WK matrix as ``chip_smoke.py`` does (``generate("web", n,
12.6, values="normalized", seed=0)``), prints its layout statistics, then
times one jitted SpMV per layout and accumulation dtype (median of
``--reps`` runs, host clock around ``block_until_ready``, compile excluded):

  coo     every non-zero gathered, multiplied and scattered (``row_sums``);
  hybrid  the layout auto selection builds for WK under the interpreter:
          capped-width ELL rows (gather + row reduce) and a COO tail of the
          long rows' overflow;
  sell    the layout auto selection builds where the SpMV runs as XLA
          gathers (a TPU): row-length-bucketed ELL, one gather over every
          slot, dense sums per width class and one scatter of a sum per row
          piece of at most 1,024 entries;
  gather  the gather and multiply of every non-zero, summed to one scalar
          (no scatter): the floor of any gather-based SpMV.

``sell-gather`` times the ``"sell"`` layout's gather of a random float32
``x`` alone, ``jnp.take(x, col)`` and the ``sell_gather`` kernel (compiled
on a TPU, interpreted elsewhere), in ns a slot, and checks that their bits
are equal; then the whole jitted ``"sell"`` SpMV with each gather, whose
bits must be equal too.  ``--matrix`` takes a benchmark configuration's name
(``bench/configs/<name>.json``, graph made by ``bench.gen`` with seed 1) in
place of WK.

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
from unittest import mock

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

    from repro.kernels.engine import choose_format, matrix_stats, spmv_runs_pallas
    from repro.kernels.ops import default_interpret
    from repro.sparse.formats import to_device_coo, to_device_hybrid, to_device_sell

    stats = matrix_stats(csr)
    compiled = not spmv_runs_pallas(default_interpret())
    _emit(probe="stats", auto_format=choose_format(stats, compiled=compiled), **stats.as_dict())
    coo = to_device_coo(csr, dtype=jnp.float32)
    hyb = to_device_hybrid(csr, dtype=jnp.float32, width_cap=stats.hyb_width)
    sell = to_device_sell(csr, dtype=jnp.float32)
    _emit(probe="sell_layout", **sell.summary())
    x_host = np.random.default_rng(1).standard_normal(csr.n)
    want = csr.to_scipy() @ x_host
    for acc in (jnp.float32, jnp.float64):
        x = jnp.asarray(x_host, acc)

        def gather(m, v, acc=acc):
            return jnp.sum(m.val.astype(acc) * jnp.take(v, m.col).astype(acc))

        matvec = jax.jit(lambda m, v, acc=acc: m.matvec(v, accum_dtype=acc))
        paths = {"coo": matvec, "hybrid": matvec, "sell": matvec, "gather": jax.jit(gather)}
        mats = {"coo": coo, "hybrid": hyb, "sell": sell, "gather": coo}
        for name, fn in paths.items():
            sec = _median_seconds(fn, mats[name], x, reps=reps)
            out = {"probe": "spmv", "layout": name, "accum": jnp.dtype(acc).name,
                   "median_ms": sec * 1e3, "reps": reps}
            if name != "gather":
                y = np.asarray(fn(mats[name], x), np.float64)
                out["max_rel_err"] = float(np.max(np.abs(y - want)) / np.max(np.abs(want)))
            _emit(**out)


def probe_sell_gather(csr, reps: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.kernels import sell_gather as sg
    from repro.sparse.formats import to_device_sell

    sell = to_device_sell(csr, dtype=jnp.float32)
    col = sell.col
    how = sell.gather_executor(jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(3), (csr.n,), jnp.float32)
    take = jax.jit(jnp.take)
    out = {"probe": "sell-gather", "n": csr.n, "slots": int(col.shape[0]), "executor": how}
    sec = _median_seconds(take, x, col, reps=reps)
    out.update(take_ms=sec * 1e3, take_ns_slot=sec / col.shape[0] * 1e9)
    if how != "xla":
        kernel = jax.jit(lambda v, c: sg.sell_gather(v, c, interpret=how == "pallas_interpret"))
        sec = _median_seconds(kernel, x, col, reps=reps)
        want = np.asarray(take(x, col)).view(np.uint32)
        got = np.asarray(kernel(x, col)).view(np.uint32)
        out.update(kernel_ms=sec * 1e3, kernel_ns_slot=sec / col.shape[0] * 1e9,
                   bit_equal=bool(np.array_equal(got, want)))
        # The whole jitted SpMV, the gather kernel's against XLA's gather
        # (no VMEM to hold x: the fallback): the sums must not move either.
        sec, y = _jitted_spmv(sell, x, reps)
        with mock.patch.object(sg, "vmem_capacity_bytes", lambda: 0):
            jax.clear_caches()
            sec_xla, y_xla = _jitted_spmv(sell, x, reps)
        jax.clear_caches()
        out.update(spmv_ms=sec * 1e3, spmv_xla_ms=sec_xla * 1e3,
                   spmv_bit_equal=bool(np.array_equal(y.view(np.uint32), y_xla.view(np.uint32))))
    _emit(**out)


def _jitted_spmv(sell, x, reps: int):
    import jax

    fn = jax.jit(lambda m, v: m.matvec(v))
    return _median_seconds(fn, sell, x, reps=reps), np.asarray(fn(sell, x))


def load_matrix(name: str, n: int):
    """WK at ``n`` rows, or a benchmark configuration's graph."""
    from repro.sparse import CSR, generate

    if name == "wk":
        return generate("web", n, 12.6, seed=0, values="normalized")
    sys.path.insert(0, ROOT)
    from bench.gen import generate as gap_generate

    with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as f:
        g = gap_generate(json.load(f), 1)
    return CSR(g.indptr, g.indices, g.data, (g.n, g.n))


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
    ap.add_argument("--matrix", default="wk",
                    help="'wk', or a configuration under bench/configs (e.g. gap-kron-s20)")
    ap.add_argument("--only", choices=("all", "sell-gather"), default="all")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_enable_x64", True)
    dev = jax.devices()[0]
    t0 = time.perf_counter()
    csr = load_matrix(args.matrix, args.n)
    _emit(probe="setup", platform=dev.platform, kind=dev.device_kind, matrix=args.matrix,
          n=csr.n, nnz=csr.nnz, seconds=time.perf_counter() - t0)
    probe_sell_gather(csr, args.reps)
    if args.only == "all":
        probe_spmv(csr, args.reps)
        if dev.platform == "tpu":
            probe_kernels(csr.n)
    return 0


if __name__ == "__main__":
    sys.exit(main())
