"""Readings that the limits of ``correct`` are set from (not part of a run).

    python bench/calibrate.py --workload kron-s20.fff --seeds 101-112 --control-seeds 101-103

For each seed, in one process: the cell's set-up, a window of
``run_seconds`` of the mix's requests (the program, as a run drives it), and
the widest of each compared number over its answers; for each control seed
also the same requests under the mix's ``control`` keywords (the program's
own path one precision below the mix's: for ``FFF``, ``BFF``, bfloat16
storage of the matrix and the Krylov basis), over the same start vectors.
One JSON line per seed and side on standard output.  Needs the chip, as a
run does.
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
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != BENCH]


def _seeds(spec: str) -> list:
    out = []
    for part in spec.split(","):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi or lo) + 1))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="e.g. 101-112 or 5,9")
    ap.add_argument("--control-seeds", default="", help="seeds that also run the control")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        seconds = float(json.load(f)["run_seconds"])
    cell = harness.load_cell(args.workload)
    harness.configure_jax()
    harness.open_devices(cell.chips)
    control = dict(cell.traffic["request"], **cell.traffic["control"])
    ctl_seeds = set(_seeds(args.control_seeds)) if args.control_seeds else set()
    for seed in _seeds(args.seeds):
        t0 = time.perf_counter()
        s = harness.setup(cell, seed)
        sides = [("program", cell.traffic["request"])]
        if seed in ctl_seeds:
            sides.append(("control", control))
        count = None
        for side, request in sides:
            reqs, t_open, t_close, error = harness.window(s, seconds, request=request, count=count)
            count = len(reqs)
            harness.fetch(reqs)
            numbers = harness.check(s, reqs)
            line = {
                "workload": cell.name, "seed": seed, "side": side, "policy": request["policy"],
                "requests": len(reqs), "steps": [r.iterations for r in reqs], "error": error,
                "window_s": t_close - t_open, **numbers,
            }
            print(json.dumps(line), flush=True)
        print(json.dumps({"seed": seed, "seconds": time.perf_counter() - t0}), file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
