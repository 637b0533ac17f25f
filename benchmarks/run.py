"""Benchmark entry point: one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines (see common.emit) and writes
JSON artifacts under artifacts/.

``--smoke`` runs a tiny-size subset (CI's bench-smoke job): captures every
emitted metric plus a machine-speed calibration probe and writes them to a
single JSON (default ``artifacts/BENCH_pr.json``) that
``benchmarks/compare.py`` gates against the committed baseline.
"""

import argparse
import json
import os
import sys
import traceback


def _full_sections():
    from . import (
        fig2_speedup,
        fig3a_multidev,
        fig3b_reorth,
        fig4_precision,
        engine_bench,
        kernels_bench,
        table1_suite,
    )

    return [
        ("table1_suite", table1_suite.run),
        ("fig2_speedup", fig2_speedup.run),
        ("fig3a_multidev", fig3a_multidev.run),
        ("fig3b_reorth", fig3b_reorth.run),
        ("fig4_precision", fig4_precision.run),
        ("kernels_bench", kernels_bench.run),
        ("engine_bench", engine_bench.run),
    ]


def _smoke_sections():
    from . import engine_bench, fig2_speedup, kernels_bench, table1_suite

    return [
        ("table1_suite", lambda: table1_suite.run(scale=0.02)),
        (
            "fig2_speedup",
            lambda: fig2_speedup.run(kset=(4,), matrices=("WB-TA", "PA"), scale=0.03),
        ),
        ("kernels_bench", lambda: kernels_bench.run(scale=0.05, vec_pow=16)),
        ("engine_bench", lambda: engine_bench.run(scale=0.25)),
    ]


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument(
        "--smoke",
        action="store_true",
        help="tiny sizes; capture metrics to a comparable JSON artifact",
    )
    parser.add_argument(
        "--out",
        default=None,
        help="metrics JSON path (smoke mode; default artifacts/BENCH_pr.json)",
    )
    args = parser.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from .common import ARTIFACTS, calibration_us, captured_metrics, captured_plans, start_capture

    if args.smoke:
        start_capture()
        sections = _smoke_sections()
    else:
        sections = _full_sections()

    failures = []
    for name, fn in sections:
        print(f"# --- {name} ---")
        try:
            fn()
        except Exception as e:  # noqa: BLE001
            traceback.print_exc()
            failures.append((name, str(e)))

    if args.smoke:
        out_path = args.out or os.path.join(ARTIFACTS, "BENCH_pr.json")
        os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
        payload = {
            "calibration_us": calibration_us(),
            "metrics": captured_metrics(),
            "plans": captured_plans(),
        }
        with open(out_path, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
        print(f"# wrote {out_path} ({len(payload['metrics'])} metrics)")

    if failures:
        print("FAILED SECTIONS:", failures)
        sys.exit(1)


if __name__ == "__main__":
    main()
