"""Record a traced run of a cell and show how its trace is laid out.

    python bench/tests/record_trace.py --workload kron-s20.fff --seed 5 \\
        --seconds 0 --scale 10 --out bench/testdata/kron-s10

Runs one traced run (``bench.harness.run``, optionally at a smaller scale),
keeps the profiler's ``.xplane.pb`` under ``--out`` (as ``trace.xplane.pb``),
writes the reduction of ``bench.trace`` beside it (``reduced.json``) and
prints each plane with its lines, their event counts and their most common
event names, for reading the trace by hand.  Needs the chip.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def describe(profile) -> None:
    for plane in profile.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            names = collections.Counter(e.name for e in line.events)
            top = names.most_common(12)
            print(f"  line {line.name!r}: {sum(names.values())} events; top {top}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--scale", type=int, default=None)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness, trace

    cell = harness.load_cell(args.workload)
    harness.configure_jax()
    harness.open_devices(cell.chips)
    tdir = tempfile.mkdtemp(prefix="bench-record-")
    o = harness.run(
        cell, args.seed, args.seconds, True, t_start=T_START, scale=args.scale, trace_dir=tdir
    )
    os.makedirs(args.out, exist_ok=True)
    dst = os.path.join(args.out, "trace.xplane.pb")
    shutil.copyfile(trace.find_xplane(tdir), dst)
    shutil.rmtree(tdir, ignore_errors=True)
    reduced = trace.reduce(trace.load(dst))
    with open(os.path.join(args.out, "reduced.json"), "w") as f:
        json.dump(reduced.as_dict(), f, indent=1, sort_keys=True)
    describe(trace.load(dst))
    print(json.dumps({
        "correct": o.correct(), "requests": len(o.requests), "numbers": o.numbers,
        "compiles": o.compiles, "busy_s": reduced.busy_s, "window_s": reduced.window_s,
        "programs": reduced.programs, "breakdown": reduced.breakdown(),
        "trace_bytes": os.path.getsize(dst),
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
