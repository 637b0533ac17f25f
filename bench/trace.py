"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the per-layer
metrics read.

On a TPU the trace holds one plane per chip (``/device:TPU:0``, ...) and one
for the host (``/host:CPU``).  On a chip's plane the line ``XLA Ops`` has
one event per operation the chip ran (named by its HLO instruction,
``%fusion.1 = f32[...] fusion(...)``), and ``XLA Modules`` one per execution
of a compiled program, named after the jitted function and its fingerprint
(``jit__container_spmv(1714...)``).  On the host plane each thread is a
line; the harness's thread carries its spans (``bench.window``,
``bench.request``, ``bench.check``), JAX's ``PjitFunction(<name>)`` around
each call of a jitted function, and the Python tracer's function events
(``$session.py:1508 _run_restarted``).  Device and host events share one
clock.

Only the measured window counts: the interval of the ``bench.window`` span.
In it the reduction gives

* the busy time of each chip: the union of its operations' intervals;
* per program, its executions and their device time;
* per operation, its device time, named ``<program>/<instruction>``;
* the idle gaps of chip 0 (between its busy intervals), summed per label of
  what the host's harness thread was doing at each gap's midpoint: the
  innermost harness span, then the innermost JAX or Python function event
  (built-ins left out).
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:(?!CUSTOM)[A-Za-z]+:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
HARNESS_PREFIX = "bench."


def _merge(intervals):
    """Union of ``(start, end)`` intervals, sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def program_name(event_name: str) -> str:
    """``jit_orth(12)`` -> ``jit_orth``: a module event's name without the
    program's fingerprint."""
    return re.sub(r"\(\d+\)$", "", event_name).strip()


def op_name(event_name: str) -> str:
    """``%fusion.1 = f32[...] fusion(...)`` -> ``%fusion.1``."""
    return event_name.split(" = ", 1)[0].strip()


def _is_activity(name: str) -> bool:
    """Host events that say what the harness thread was doing: not the
    Python tracer's built-ins."""
    return not (name.startswith("$builtins") or name.startswith("$<unknown>"))


@dataclasses.dataclass
class Reduced:
    window: tuple  # (start_ns, end_ns)
    busy_ns: list  # per device plane, busy nanoseconds inside the window
    programs: dict  # name -> [executions, device ns] (device 0)
    ops: dict  # op name -> device ns (device 0)
    idle: dict  # host label -> idle ns of device 0 under it
    requests: int  # bench.request spans inside the window

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        """Busy seconds averaged over the devices."""
        return sum(self.busy_ns) / max(len(self.busy_ns), 1) * 1e-9

    def program(self, key: str):
        """``(executions, device seconds)`` summed over programs whose name
        contains ``key``; None when no such program ran."""
        hits = [v for name, v in self.programs.items() if key in name]
        if not hits:
            return None
        return sum(c for c, _ in hits), sum(ns for _, ns in hits) * 1e-9

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1])[:top]
        idle = sorted(self.idle.items(), key=lambda kv: -kv[1])[:top]
        return {
            "device_ops": [[name, ns * 1e-9] for name, ns in ops],
            "idle_gaps": [[name, ns * 1e-9] for name, ns in idle],
        }

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.start_ns) + float(e.duration_ns)


class _Innermost:
    """Finds the shortest of a set of host events that covers a time."""

    def __init__(self, events):
        self.names = [n for n, _, _ in events]
        self.start = np.array([s for _, s, _ in events], np.float64)
        self.end = np.array([e for _, _, e in events], np.float64)

    def __call__(self, t: float):
        cover = np.flatnonzero((self.start <= t) & (self.end > t))
        if not cover.size:
            return None
        return self.names[cover[np.argmin(self.end[cover] - self.start[cover])]]


def _labeller(host_events):
    """``t -> label``: the innermost harness span and the innermost other
    activity of the host that cover time ``t``."""
    harness = _Innermost([ev for ev in host_events if ev[0].startswith(HARNESS_PREFIX)])
    other = _Innermost(
        [ev for ev in host_events if not ev[0].startswith(HARNESS_PREFIX) and _is_activity(ev[0])]
    )
    return lambda t: f"{harness(t) or 'outside spans'}/{other(t) or 'python'}"


def reduce(profile, chips: int | None = None) -> Reduced:
    """Reduce a ``jax.profiler.ProfileData``; ``chips`` keeps the first so
    many device planes (the chips the run used)."""
    devices = sorted(
        (p for p in profile.planes if DEVICE_PLANE.match(p.name)), key=lambda p: p.name
    )[:chips]
    threads = [line for p in profile.planes if p.name.startswith("/host:CPU") for line in p.lines]
    host_events, lo, hi = [], None, None
    for line in threads:
        events = list(_events(line))
        spans = [ev for ev in events if ev[0] == WINDOW_SPAN]
        if spans:
            host_events = events
            lo, hi = spans[0][1], spans[0][2]
            break
    if lo is None:
        ends = [ev for p in devices for line in p.lines for ev in _events(line)]
        lo, hi = min(s for _, s, _ in ends), max(e for _, _, e in ends)
    busy, programs, ops, idle = [], {}, {}, {}
    for i, plane in enumerate(devices):
        lines = {line.name: line for line in plane.lines}
        op_events = list(_events(lines[OPS_LINE])) if OPS_LINE in lines else []
        merged = _merge(_clip([(s, e) for _, s, e in op_events], lo, hi))
        busy.append(sum(e - s for s, e in merged))
        if i:
            continue
        modules = (
            sorted(_events(lines[MODULES_LINE]), key=lambda ev: ev[1])
            if MODULES_LINE in lines
            else []
        )
        starts = np.array([s for _, s, _ in modules], np.float64)
        for name, s, e in modules:
            if lo <= s < hi:
                rec = programs.setdefault(program_name(name), [0, 0.0])
                rec[0] += 1
                rec[1] += min(e, hi) - s
        for name, s, e in op_events:
            for cs, ce in _clip([(s, e)], lo, hi):
                j = int(np.searchsorted(starts, s, side="right")) - 1
                inside = j >= 0 and s < modules[j][2]
                key = f"{program_name(modules[j][0]) if inside else '?'}/{op_name(name)}"
                ops[key] = ops.get(key, 0.0) + (ce - cs)
        label_at = _labeller(host_events)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for gs, ge in zip(edges[::2], edges[1::2]):
            if ge > gs:
                label = label_at(0.5 * (gs + ge))
                idle[label] = idle.get(label, 0.0) + (ge - gs)
    requests = sum(1 for n, s, _ in host_events if n == "bench.request" and lo <= s < hi)
    return Reduced((lo, hi), busy, programs, ops, idle, requests)


def find_xplane(directory: str) -> str:
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]


def load(path: str):
    import jax

    return jax.profiler.ProfileData.from_file(path)


def reduce_dir(directory: str, chips: int | None = None) -> Reduced:
    """Reduce the newest trace the profiler wrote under ``directory``."""
    return reduce(load(find_xplane(directory)), chips)
