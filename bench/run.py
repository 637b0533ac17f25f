"""Run one cell of the benchmark on the chips of this machine.

    python bench/run.py --workload kron-s20.fff --seed 7 --seconds 10 --trace 0

Reads the cell from ``BENCHMARK.json`` at the root of the checkout, makes
its matrix and start vectors from ``--seed`` (``bench/gen``), warms the
program up, drives ``repro.eigsh`` in a closed loop for ``--seconds``, then
checks every answer of the window against the float64 reference
(``bench/reference.py``).  With ``--trace 1`` the window runs under the JAX
profiler and the run reports the cell's per-layer metrics instead of its
end-to-end ones.

Earlier lines of standard error show, per run, that every request went
through ``eigsh``, hit the session cache and ran the mix's steps, and how
many compilations the window saw; its last lines are the numbers compared
beside their limits.  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
``breakdown`` (traced runs) and ``checks``.  Without a TPU, with fewer chips
than the cell asks for, on a device kind missing from ``bench/peaks.json``
or outside a checkout of the repository, it exits non-zero and prints no
result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
# Python put this file's directory first on the path; its modules are
# imported as the package ``bench`` instead (bench/trace.py would shadow
# the standard library's ``trace``).
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != BENCH]


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def result_line(o, peaks: dict, devices: list, traced: bool) -> dict:
    """The run's JSON result (``checks`` last)."""
    from bench import harness

    dev = devices[0]
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(devices),
        "memory_peak_bytes": o.memory_peak_bytes,
    }
    out = {
        "correct": o.correct(),
        "attempted": len(o.requests) + (o.error is not None),
        "failed": int(o.error is not None),
    }
    if traced:
        out["metrics"] = harness.per_layer(o, peaks)
        device["busy_s"] = o.trace.busy_s
        device["window_s"] = o.trace.window_s
        out["device"] = device
        out["breakdown"] = o.trace.breakdown()
    else:
        out["metrics"] = harness.end_to_end(o)
        out["device"] = device
    out["checks"] = {
        name: {"value": o.numbers[name], "limit": lim} for name, lim in o.limits().items()
    }
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        _log(f"bench: no program at {ROOT}/src/repro: run from a checkout of the repository")
        return 2
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness, roofline

    try:
        cell = harness.load_cell(args.workload)
    except (KeyError, FileNotFoundError, ValueError) as exc:
        _log(f"bench: {exc}")
        return 2
    harness.configure_jax()
    peaks = roofline.load_peaks()
    try:
        devices = harness.open_devices(cell.chips, peaks)
    except (harness.NoDevice, roofline.UnknownDevice, RuntimeError) as exc:
        _log(f"bench: {exc}")
        return 3
    dev_peaks = roofline.device_peaks(devices[0].device_kind, peaks)

    o = harness.run(cell, args.seed, args.seconds, bool(args.trace), t_start=T_START)
    reuse = sum(r.session_reuse and r.prepare_s == 0.0 for r in o.requests)
    want = cell.traffic["steps"]
    _log(
        f"bench: {cell.name} seed {args.seed}: n {o.n} nnz {o.nnz}; "
        f"{len(o.requests)} requests through repro.eigsh in {o.window_s:.3f} s, "
        f"{reuse} of them session-cache hits with prepare_s 0, "
        f"steps per request {[r.iterations for r in o.requests]} (mix: {want}), "
        f"backend {sorted({r.backend for r in o.requests})}, "
        f"SpMV format {sorted({r.spmv_format for r in o.requests})}; "
        f"seconds per request {[round(r.t1 - r.t0, 3) for r in o.requests]}, "
        f"outside the solve {[round(r.api_s, 3) for r in o.requests]}"
    )
    _log(f"bench: compilations inside the window: {sum(o.compiles.values())} {o.compiles}")
    _log(f"bench: set-up {o.setup_s:.3f} s; device memory peak {o.memory_peak_bytes} bytes")
    if o.error:
        _log(f"bench: {o.error}")
    res = result_line(o, dev_peaks, devices, bool(args.trace))
    for name, c in res["checks"].items():
        _log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
