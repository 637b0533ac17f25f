"""Chip smoke: the eigensolver's main path, once, on a TPU.

    python chip_smoke.py             # one chip: phases a-c below
    python chip_smoke.py --chips 4   # the distributed solve on a 4-chip mesh

The matrix is the paper's Wikipedia web graph (Table I, WK) at its published
size, n = 3,566,907, generated on the host from a fixed seed
(``generate("web", n, 12.6, values="normalized")``, ~29M stored non-zeros).
One chip, in one process:

  a. ``eigsh(A, k=8, tol=1e-5, policy="FFF")`` and the same at ``policy="FDF",
     tol=1e-7`` (the restarted engine);
  b. ``prepare(A)`` and ``eigsh_many`` of four queries (k in {4, 8});
  c. an ``EigenScheduler`` holding A answers eight submitted queries.

Every answer is checked on the host against the f64 CSR with SciPy: the
relative residual ``||Ax - lam x|| / |lam|`` of every pair stays under the
bound of its policy (ten times its tolerance: the tolerance bounds the Ritz
estimate, and the f32-stored basis adds rounding on top), the policy is the
one asked for, and no recovery step ran.  Phases b and c must also agree with
phase a's eigenvalues.  ``--chips 4`` runs only the distributed backend on a
four-chip mesh and the same solve on a one-device mesh, and checks that they
agree and that the shards really spread over the four devices.

Each phase prints one line; the last line of standard output is the JSON
``{"ok": true, "device": {...}}``.  Any failed check raises: the script then
exits non-zero and prints no such line.  Without an accelerator (or outside
this repository) it fails at start-up.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
WK_N = 3_566_907
WK_AVG_DEG = 12.6
SEED = 0
# Relative-residual bound per policy: ten times the requested tolerance.
BOUND = {"FFF": 1e-4, "FDF": 1e-6}
TOL = {"FFF": 1e-5, "FDF": 1e-7}
# Restarted-engine budget: the top of WK's spectrum (1, -0.99, then a tight
# cluster near +-0.47) converges in a few hundred steps with a 32-vector
# subspace; the default 16 vectors and 30 restarts are not enough there.
SOLVE = {"subspace": 32, "max_restarts": 100}
# Eigenvalues of two solves of one matrix agree to this (absolute: the
# normalized adjacency's spectrum lies in [-1, 1]).
AGREE = 1e-4


class SmokeFailure(AssertionError):
    pass


def _require(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _device_mem(dev) -> dict:
    st = dev.memory_stats() or {}
    return {k: st.get(k) for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")}


class Checker:
    """Host-side f64 verification of results against the generated CSR."""

    def __init__(self, csr):
        import scipy.sparse as sp

        self.a = sp.csr_matrix(
            (np.asarray(csr.data, np.float64), csr.indices, csr.indptr), shape=csr.shape
        )

    def residual(self, res) -> float:
        """Largest relative residual ``||Ax - lam x|| / |lam|`` of a result."""
        x = np.asarray(res.eigenvectors, np.float64)
        lam = np.asarray(res.eigenvalues, np.float64)
        return float(np.max(np.linalg.norm(self.a @ x - x * lam, axis=0) / np.abs(lam)))


def _verify(res, policy: str, worst: float, label: str) -> None:
    _require(res.policy == policy, f"{label}: ran policy {res.policy!r}, asked {policy!r}")
    _require(not res.recovery_trail, f"{label}: recovery ran: {res.recovery_trail}")
    _require(res.backend in ("single", "restarted"), f"{label}: backend {res.backend}")
    kernels = res.partition["spmv"]["kernels"]
    _require("pallas_interpret" not in kernels.values(), f"{label}: interpreter in {kernels}")
    _require(worst <= BOUND[policy], f"{label}: residual {worst:.3e} > {BOUND[policy]:.0e}")


def _eig_diff(res, ref) -> float:
    got = np.asarray(res.eigenvalues, np.float64)
    return float(np.max(np.abs(got - np.asarray(ref.eigenvalues, np.float64)[: got.size])))


def _spmv_summary(res) -> dict:
    spmv = res.partition["spmv"]
    return {
        "format": spmv.get("format"),
        "kernels": spmv.get("kernels"),
        "interpret": spmv.get("interpret"),
        "conversions": spmv.get("conversions"),
        "reused": spmv.get("reused"),
    }


def _report(phase: str, t0: float, dev, **fields) -> None:
    line = {"phase": phase, "seconds": round(time.perf_counter() - t0, 3)}
    line.update(fields)
    line["device_memory"] = _device_mem(dev)
    print(json.dumps(line, default=str), flush=True)


def _generate(n: int):
    from repro.sparse import generate

    t0 = time.perf_counter()
    csr = generate("web", n, WK_AVG_DEG, seed=SEED, values="normalized")
    print(
        json.dumps(
            {"phase": "setup", "seconds": round(time.perf_counter() - t0, 3),
             "n": csr.n, "nnz": csr.nnz, "seed": SEED}
        ),
        flush=True,
    )
    return csr


def _check_batch(phase, t0, dev, results, ref, check, **fields) -> None:
    """Report a batch of FFF answers, then hold each to its bound and to
    phase a's eigenvalues."""
    worst = [check.residual(r) for r in results]
    diffs = [_eig_diff(r, ref) for r in results]
    _report(
        phase, t0, dev, queries=len(results), backend=results[0].backend,
        max_rel_residual=max(worst), max_eigenvalue_diff_vs_a=max(diffs),
        spmv=_spmv_summary(results[0]), **fields,
    )
    for r, w, d in zip(results, worst, diffs):
        _verify(r, "FFF", w, f"{phase}/k{r.k}")
        _require(d <= AGREE, f"{phase}/k{r.k}: eigenvalues differ from phase a by {d:.3e}")


def run_one_chip(n: int = WK_N) -> None:
    """Phases a-c on the default device."""
    import jax

    from repro.api import EigQuery, eigsh, prepare
    from repro.serving import EigenScheduler, SchedulerConfig

    dev = jax.devices()[0]
    csr = _generate(n)
    check = Checker(csr)

    # a. the frontend, restarted engine, both policies
    ref = {}
    for policy in ("FFF", "FDF"):
        t0 = time.perf_counter()
        res = eigsh(csr, 8, tol=TOL[policy], policy=policy, **SOLVE)
        worst = check.residual(res)
        ref[policy] = res
        _report(
            f"a/{policy}", t0, dev, backend=res.backend, policy=res.policy,
            iterations=res.iterations, restarts=res.restarts,
            converged=bool(np.all(res.converged)), max_rel_residual=worst,
            eigenvalues=np.asarray(res.eigenvalues, np.float64).tolist(),
            spmv=_spmv_summary(res),
        )
        _verify(res, policy, worst, f"a/{policy}")

    # b. plan once, then a batch of queries against the prepared session
    t0 = time.perf_counter()
    session = prepare(csr)
    queries = [
        EigQuery(k=4, policy="FFF", tol=1e-5, **SOLVE),
        EigQuery(k=8, policy="FFF", tol=1e-5, **SOLVE),
        EigQuery(k=4, policy="FFF", tol=1e-4, **SOLVE),
        EigQuery(k=8, policy="FFF", tol=1e-4, **SOLVE),
    ]
    results = session.eigsh_many(queries)
    _check_batch("b/eigsh_many", t0, dev, results, ref["FFF"], check)

    # c. the eigensolver service with A resident
    t0 = time.perf_counter()
    cfg = SchedulerConfig(admission_window_s=0.05, max_group=8)
    with EigenScheduler(cfg) as sched:
        key = sched.add_matrix(csr, name="wk")
        handles = [
            sched.submit(key, k=k, policy="FFF", tol=1e-5, **SOLVE)
            for k in (2, 3, 4, 5, 6, 7, 8, 8)
        ]
        served = [h.result(timeout=600.0) for h in handles]
        stats = sched.stats()
    _check_batch(
        "c/scheduler", t0, dev, served, ref["FFF"], check,
        completed=stats.completed, groups=stats.groups,
    )


def run_four_chips(n: int = WK_N, num_iters: int = 64, k: int = 2) -> None:
    """The distributed backend on a 4-chip mesh against the same solve on a
    one-device mesh of the same process."""
    import jax
    from jax.sharding import Mesh

    from repro.api import eigsh

    devs = jax.devices()
    _require(len(devs) == 4, f"--chips 4 needs 4 devices, found {len(devs)}")
    csr = _generate(n)
    check = Checker(csr)
    out = {}
    for label, mesh_devs in (("mesh4", devs), ("mesh1", devs[:1])):
        t0 = time.perf_counter()
        mesh = Mesh(np.array(mesh_devs), ("data",))
        res = eigsh(
            csr, k, backend="distributed", mesh=mesh, policy="FFF", num_iters=num_iters
        )
        _require(res.policy == "FFF" and not res.recovery_trail, f"{label}: {res.policy}")
        _require(res.num_devices == len(mesh_devs), f"{label}: ran on {res.num_devices}")
        worst = check.residual(res)
        mem = [_device_mem(d) for d in devs]
        out[label] = res
        print(
            json.dumps(
                {"phase": f"distributed/{label}", "seconds": round(time.perf_counter() - t0, 3),
                 "devices": len(mesh_devs), "format": res.spmv_format,
                 "kernels": res.partition["spmv"].get("kernels"),
                 "max_rel_residual": worst,
                 "eigenvalues": np.asarray(res.eigenvalues, np.float64).tolist(),
                 "bytes_in_use_per_device": [m["bytes_in_use"] for m in mem],
                 "peak_bytes_per_device": [m["peak_bytes_in_use"] for m in mem]},
                default=str,
            ),
            flush=True,
        )
        _require(worst <= BOUND["FFF"], f"{label}: residual {worst:.3e}")
        if label == "mesh4":
            # Each device held its shard and its slice of the basis (the
            # mesh4 solve runs first, so the peaks are its own): none may
            # have stayed near empty.
            peak = [m["peak_bytes_in_use"] or 0 for m in mem]
            _require(min(peak) >= 0.25 * max(peak), f"shards not spread: {peak}")
    diff = float(
        np.max(np.abs(np.asarray(out["mesh4"].eigenvalues, np.float64)
                      - np.asarray(out["mesh1"].eigenvalues, np.float64)))
    )
    _require(diff <= AGREE, f"4-chip and 1-device eigenvalues differ by {diff:.3e}")
    print(json.dumps({"phase": "distributed/agreement", "max_eigenvalue_diff": diff}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    os.environ["JAX_PLATFORMS"] = "tpu"  # before JAX loads: no CPU fallback
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_enable_x64", True)  # FDF computes in f64
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (found {dev.platform})", file=sys.stderr)
        return 2
    if args.chips == 4:
        run_four_chips()
    else:
        run_one_chip()
    print(
        json.dumps(
            {"ok": True, "device": {"platform": dev.platform, "kind": dev.device_kind,
                                    "count": len(jax.devices())}}
        )
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
