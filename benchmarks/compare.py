"""Gate a bench-smoke run against the committed baseline — and against itself.

Usage:
    python -m benchmarks.compare artifacts/BENCH_pr.json \
        benchmarks/baseline_smoke.json --max-slowdown 2.0 \
        --pair engine/lanczos_step/fused:engine/lanczos_step/unfused

Three independent checks, one exit code:

* **Baseline gate** — metrics large enough to time stably (>=
  ``--gate-floor-us`` in either run, default 50ms) must not exceed
  ``--max-slowdown`` x their committed baseline: measured run-to-run
  dispersion of the smoke suite is <= ~1.4x for these, so a >2x raw ratio is
  a real regression, not scheduler noise.  Smaller metrics are printed for
  trend-watching but never fail the gate.  New/removed metrics are reported
  but never fail — refresh the baseline when the benched surface changes:
  ``python -m benchmarks.run --smoke --out benchmarks/baseline_smoke.json``.

* **Pair gates** (``--pair A:B[:RATIO]``) — intra-run invariants: metric A
  must not exceed RATIO x metric B *within the same run* (default
  ``--max-ratio``, 1.0).  This is what makes "the fused path lost to the
  unfused path" unlandable even when both moved together (the baseline gate
  compares each metric only to its own past).  A pair is *escaped* when the
  run's recorded decision plan for the metrics' common prefix selected
  something other than A's leaf — e.g. the execution mode runs the unfused
  update, so fused losing is the measured, routed-around truth,
  not a shipped regression.  No recorded plan means no escape.

* **Trend watch** (``--trend history.jsonl``) — warn-only: flags metrics
  that degraded monotonically over the last 3 runs (slow leaks the 2x gate
  can't see).  History lines are appended on main by ``--append-history``.

``--summary-out`` appends a markdown report (CI passes
``$GITHUB_STEP_SUMMARY``); ``--skip-gate`` reports without failing (the
post-merge history step on main — its PR already passed the real gate).
"""

import argparse
import json
import os
import sys
from datetime import datetime, timezone


def load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _md(lines, row) -> None:
    if lines is not None:
        lines.append(row)


def compare(pr, base, max_slowdown, gate_floor_us, md=None) -> list:
    """Baseline gate: returns the list of failing (name, ratio) pairs."""
    pr_m, base_m = pr.get("metrics", {}), base.get("metrics", {})
    shared = sorted(set(pr_m) & set(base_m))
    regressions = []
    gated = 0
    print(
        f"calibration (informational): pr={float(pr.get('calibration_us') or 0):.1f}us "
        f"baseline={float(base.get('calibration_us') or 0):.1f}us"
    )
    print(f"{'metric':45s} {'base_us':>10s} {'pr_us':>10s} {'ratio':>7s}")
    _md(md, "### Baseline gate (vs committed smoke baseline)\n")
    _md(md, "| metric | base µs | pr µs | ratio | status |")
    _md(md, "|---|---:|---:|---:|---|")
    for name in shared:
        b, p = float(base_m[name]), float(pr_m[name])
        if b <= 0 or p <= 0:
            continue  # unmeasured placeholders (e.g. table1's 0.0 rows)
        ratio = p / b
        in_gate = max(b, p) >= gate_floor_us
        gated += in_gate
        flag = ""
        status = "ok" if in_gate else "info"
        if in_gate and ratio > max_slowdown:
            regressions.append((name, ratio))
            flag = "  << REGRESSION"
            status = "**REGRESSION**"
        elif not in_gate:
            flag = "  (info only)"
        print(f"{name:45s} {b:10.1f} {p:10.1f} {ratio:6.2f}x{flag}")
        _md(md, f"| {name} | {b:.1f} | {p:.1f} | {ratio:.2f}x | {status} |")
    for name in sorted(set(pr_m) - set(base_m)):
        print(f"{name:45s} {'-':>10s} {float(pr_m[name]):10.1f}   (new)")
        _md(md, f"| {name} | – | {float(pr_m[name]):.1f} | – | new |")
    for name in sorted(set(base_m) - set(pr_m)):
        print(f"{name:45s} {float(base_m[name]):10.1f} {'-':>10s}   (removed)")
        _md(md, f"| {name} | {float(base_m[name]):.1f} | – | – | removed |")
    if regressions:
        print(f"\nFAIL: {len(regressions)} gated metric(s) slowed by >{max_slowdown}x:")
        for name, ratio in regressions:
            print(f"  {name}: {ratio:.2f}x")
    else:
        print(f"\nOK: no gated metric slowed by >{max_slowdown}x ({gated} gated)")
    return regressions


def _split_common(a: str, b: str):
    """('engine/x/fused', 'engine/x/unfused') -> ('engine/x', 'fused')."""
    pa, pb = a.split("/"), b.split("/")
    i = 0
    while i < min(len(pa), len(pb)) and pa[i] == pb[i]:
        i += 1
    return "/".join(pa[:i]), "/".join(pa[i:])


def check_pairs(pr, pair_specs, default_ratio, md=None) -> list:
    """Intra-run pair gates: returns the list of failing (spec, ratio) pairs."""
    if not pair_specs:
        return []
    metrics, plans = pr.get("metrics", {}), pr.get("plans", {})
    failures = []
    print(f"\n{'pair gate':60s} {'ratio':>7s} {'limit':>7s}")
    _md(md, "\n### Pair gates (intra-run invariants)\n")
    _md(md, "| A | B | ratio | limit | status |")
    _md(md, "|---|---|---:|---:|---|")
    for spec in pair_specs:
        parts = spec.split(":")
        if len(parts) not in (2, 3):
            raise SystemExit(f"--pair {spec!r}: expected A:B or A:B:RATIO")
        a, b = parts[0], parts[1]
        limit = float(parts[2]) if len(parts) == 3 else default_ratio
        pa, pb = metrics.get(a), metrics.get(b)
        if pa is None or pb is None or float(pb) <= 0:
            print(f"{spec:60s} {'-':>7s} {limit:6.2f}x  (metric missing; skipped)")
            _md(md, f"| {a} | {b} | – | {limit:.2f}x | metric missing |")
            continue
        ratio = float(pa) / float(pb)
        prefix, leaf = _split_common(a, b)
        selected = (plans.get(prefix) or {}).get("selected")
        escaped = selected is not None and selected != leaf
        if ratio > limit and not escaped:
            failures.append((spec, ratio))
            note = "  << PAIR REGRESSION"
            status = "**FAIL**"
        elif ratio > limit:
            note = f"  (escaped: plan[{prefix}] selected {selected!r}, not {leaf!r})"
            status = f"escaped (plan→{selected})"
        else:
            note = ""
            status = "ok"
        print(f"{a + ' : ' + b:60s} {ratio:6.2f}x {limit:6.2f}x{note}")
        _md(md, f"| {a} | {b} | {ratio:.2f}x | {limit:.2f}x | {status} |")
    if failures:
        print(f"\nFAIL: {len(failures)} pair gate(s) exceeded:")
        for spec, ratio in failures:
            print(f"  {spec}: {ratio:.2f}x")
    else:
        print("\nOK: all pair gates hold")
    return failures


def _read_history(path: str) -> list:
    entries = []
    if os.path.exists(path):
        with open(path) as f:
            for line in f:
                line = line.strip()
                if line:
                    try:
                        entries.append(json.loads(line))
                    except json.JSONDecodeError:
                        pass  # a torn line must not break CI
    return entries


def check_trend(pr, history_path, runs=3, min_total=1.10, md=None) -> list:
    """Warn-only: metrics monotonically degrading over the last ``runs`` runs
    (history tail + this run) with a total slowdown > ``min_total``x."""
    history = _read_history(history_path)
    pr_m = pr.get("metrics", {})
    warnings = []
    for name, value in sorted(pr_m.items()):
        tail = [
            float(e["metrics"][name])
            for e in history[-(runs - 1) :]
            if isinstance(e.get("metrics"), dict) and name in e["metrics"]
        ]
        seq = tail + [float(value)]
        if len(seq) < runs:
            continue  # not enough history yet
        monotone = all(seq[i] < seq[i + 1] for i in range(len(seq) - 1))
        if monotone and seq[0] > 0 and seq[-1] / seq[0] > min_total:
            warnings.append((name, seq))
    if warnings:
        print(f"\nTREND WARNING ({len(warnings)} metric(s) degrading over {runs} runs):")
        _md(md, f"\n### ⚠ Trend warnings ({runs}-run monotone degradation)\n")
        _md(md, "| metric | trajectory (µs) | total |")
        _md(md, "|---|---|---:|")
        for name, seq in warnings:
            traj = " -> ".join(f"{v:.1f}" for v in seq)
            print(f"  {name}: {traj}  ({seq[-1] / seq[0]:.2f}x, warn-only)")
            _md(md, f"| {name} | {traj} | {seq[-1] / seq[0]:.2f}x |")
    elif history:
        print(f"\ntrend: no metric degraded monotonically over the last {runs} runs")
    return warnings


def append_history(pr, history_path, sha) -> None:
    entry = {
        "sha": sha or "unknown",
        "date": datetime.now(timezone.utc).strftime("%Y-%m-%dT%H:%M:%SZ"),
        "calibration_us": pr.get("calibration_us"),
        "metrics": pr.get("metrics", {}),
        "plans": pr.get("plans", {}),
    }
    os.makedirs(os.path.dirname(os.path.abspath(history_path)), exist_ok=True)
    with open(history_path, "a") as f:
        f.write(json.dumps(entry, sort_keys=True) + "\n")
    print(f"\nappended run {entry['sha'][:12]} to {history_path}")


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("pr_json")
    parser.add_argument("baseline_json")
    parser.add_argument("--max-slowdown", type=float, default=2.0)
    parser.add_argument(
        "--gate-floor-us",
        type=float,
        default=50_000.0,
        help="gate only metrics at least this large in one run (smaller ones "
        "are too noisy on shared runners and are reported info-only)",
    )
    parser.add_argument(
        "--pair",
        action="append",
        default=[],
        metavar="A:B[:RATIO]",
        help="intra-run gate: metric A must be <= RATIO x metric B in the PR "
        "run (repeatable; RATIO defaults to --max-ratio); escaped when the "
        "run's plan for the common prefix selected a different leaf",
    )
    parser.add_argument(
        "--max-ratio",
        type=float,
        default=1.0,
        help="default RATIO for --pair gates without an explicit one",
    )
    parser.add_argument(
        "--trend",
        metavar="HISTORY_JSONL",
        help="warn (never fail) on metrics degrading monotonically over the "
        "last 3 runs recorded in this history file",
    )
    parser.add_argument(
        "--append-history",
        metavar="HISTORY_JSONL",
        help="append this run's metrics+plans as one JSONL line (main only)",
    )
    parser.add_argument("--sha", default=os.environ.get("GITHUB_SHA", ""),
                        help="commit sha recorded with --append-history")
    parser.add_argument(
        "--summary-out",
        metavar="MD_PATH",
        help="append a markdown report (CI passes $GITHUB_STEP_SUMMARY)",
    )
    parser.add_argument(
        "--skip-gate",
        action="store_true",
        help="report everything but always exit 0 (post-merge history runs)",
    )
    args = parser.parse_args(argv)

    pr, base = load(args.pr_json), load(args.baseline_json)
    md = [] if args.summary_out else None
    _md(md, "## bench-smoke comparison\n")
    regressions = compare(pr, base, args.max_slowdown, args.gate_floor_us, md=md)
    pair_failures = check_pairs(pr, args.pair, args.max_ratio, md=md)
    if args.trend:
        check_trend(pr, args.trend, md=md)
    if args.append_history:
        append_history(pr, args.append_history, args.sha)
    if md is not None:
        with open(args.summary_out, "a") as f:
            f.write("\n".join(md) + "\n")
    failed = bool(regressions or pair_failures)
    if failed and args.skip_gate:
        print("\n(--skip-gate: failures reported above are not enforced here)")
    sys.exit(1 if failed and not args.skip_gate else 0)


if __name__ == "__main__":
    main()
