"""Reduction of the program's own host spans (``repro.*``, named in
``repro.tracing``) in a profiler trace of one benchmark run.

    python -m bench.spans <trace.xplane.pb or .xplane.pb.gz>

For every span name on the harness thread (the host line that carries
``bench.window``) whose events lie inside the window, the reduction keeps

* ``count``: its executions;
* ``host_ns``: their summed host duration;
* ``idle_ns``: chip 0's idle time inside the union of their intervals (the
  gaps between its busy intervals, as ``bench.trace`` computes them for
  ``idle``); 0 in a trace without a device plane, such as a CPU run's;
* ``stats``: the sum of each numeric stat the spans carry.

Device and host events share one clock, so an idle gap inside
``repro.lanczos.step`` is the chip waiting on that step's host work.  The
per-request quantities below are what a span metric of the restarted engine
or the session layer reads; the script prints them with the table, chip 0's
idle time in the window and the part of it outside every ``repro.*`` span.
"""

from __future__ import annotations

import gzip
import json
import sys

from .trace import DEVICE_PLANE, OPS_LINE, WINDOW_SPAN, _clip, _events, _merge

PREFIX = "repro."


def _overlap_ns(a, b) -> float:
    """Length of the intersection of two sorted lists of disjoint intervals."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _harness_line(profile):
    """The host line that carries ``bench.window``, and the window."""
    for plane in profile.planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for name, lo, hi in _events(line):
                    if name == WINDOW_SPAN:
                        return line, lo, hi
    return None, None, None


def _window(profile):
    """``(harness line, lo, hi, idle gaps of chip 0)``; gaps are empty
    without a device plane, and the line is None without a window span."""
    line, lo, hi = _harness_line(profile)
    devices = sorted(
        (p for p in profile.planes if DEVICE_PLANE.match(p.name)), key=lambda p: p.name
    )
    gaps = []
    if line is not None and devices:
        ops = [ln for ln in devices[0].lines if ln.name == OPS_LINE]
        busy = _merge(_clip([(s, e) for ln in ops for _, s, e in _events(ln)], lo, hi))
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
    return line, lo, hi, gaps


def _span_events(line, lo, hi):
    for e in line.events:
        s = float(e.start_ns)
        end = s + float(e.duration_ns)
        if e.name.startswith(PREFIX) and lo <= s and end <= hi:
            yield e.name, s, end, e.stats


def reduce(profile) -> dict:
    """``name -> {"count", "host_ns", "idle_ns", "stats"}`` for the
    ``repro.*`` spans of the window; empty without a ``bench.window``."""
    line, lo, hi, gaps = _window(profile)
    if line is None:
        return {}
    out, intervals = {}, {}
    for name, s, e, stats in _span_events(line, lo, hi):
        rec = out.setdefault(name, {"count": 0, "host_ns": 0.0, "idle_ns": 0.0, "stats": {}})
        rec["count"] += 1
        rec["host_ns"] += e - s
        for key, value in stats:
            if isinstance(value, (int, float)) and not isinstance(value, bool):
                rec["stats"][key] = rec["stats"].get(key, 0) + value
        intervals.setdefault(name, []).append((s, e))
    for name, rec in out.items():
        rec["idle_ns"] = _overlap_ns(gaps, _merge(intervals[name]))
    return out


def idle_split(profile) -> dict:
    """Chip 0's idle time in the window (``total_ns``) and the part of it
    outside every ``repro.*`` span (``outside_spans_ns``)."""
    line, lo, hi, gaps = _window(profile)
    if line is None:
        return {"total_ns": 0.0, "outside_spans_ns": 0.0}
    inside = _merge([(s, e) for _, s, e, _ in _span_events(line, lo, hi)])
    total = sum((e - s for s, e in gaps), 0.0)
    return {"total_ns": total, "outside_spans_ns": total - _overlap_ns(gaps, inside)}


def per_request(spans: dict) -> dict:
    """What the span metrics read, in ms; a quantity whose span is absent
    is left out.

    * ``fingerprint_ms``: host time of ``repro.session.fingerprint`` per
      ``repro.eigsh`` call;
    * ``step_idle_ms``: chip-0 idle time inside ``repro.lanczos.step`` per
      step;
    * ``jacobi_ms``: host time of ``repro.restarted.jacobi`` per execution.
    """
    out = {}
    calls = spans.get("repro.eigsh", {}).get("count", 0)
    fp = spans.get("repro.session.fingerprint")
    if fp and calls:
        out["fingerprint_ms"] = 1e-6 * fp["host_ns"] / calls
    step = spans.get("repro.lanczos.step")
    if step:
        out["step_idle_ms"] = 1e-6 * step["idle_ns"] / step["count"]
    jac = spans.get("repro.restarted.jacobi")
    if jac:
        out["jacobi_ms"] = 1e-6 * jac["host_ns"] / jac["count"]
    return out


def load(path: str):
    import jax

    if path.endswith(".gz"):
        with gzip.open(path) as f:
            return jax.profiler.ProfileData.from_serialized_xspace(f.read())
    return jax.profiler.ProfileData.from_file(path)


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__.split("\n\n")[1], file=sys.stderr)
        return 2
    profile = load(argv[0])
    spans = reduce(profile)
    print(json.dumps({
        "spans": spans,
        "per_request": per_request(spans),
        "idle": idle_split(profile),
    }, indent=1, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
