"""One run of one benchmark cell: set-up, the measured window, the check.

A cell is an entry of ``BENCHMARK.json``'s ``workloads``: a configuration
(``bench/configs/<config>.json``, read by a generator of ``bench/gen``) under
a traffic mix (``bench/traffic/<traffic>.json``).  Everything here is
driven by those files; a new cell, mix or per-layer metric is new files and
new entries, never an edit of this module.

The traffic is a closed loop of one client: each request starts when the
previous one has returned.  A request is one call of ``repro.eigsh`` on the
generated host CSR with the mix's ``request`` keywords and a start vector
``v0`` drawn from (``--seed``, request index).  The window opens after
set-up and closes when the request in progress at ``seconds`` returns, so
its time and its work are both whole.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib
import json
import os
import shutil
import tempfile
import time
from contextlib import contextmanager

import numpy as np

from . import reference, roofline
from .gen import generate

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoDevice(RuntimeError):
    """The machine lacks the accelerator the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list  # BENCHMARK.json metric entries this cell reports
    per_layer: list


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(workload: str, root: str = ROOT) -> Cell:
    """The cell named ``workload`` in ``<root>/BENCHMARK.json``, with its
    configuration and traffic files read from under ``root``."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    (cfg,) = [c for c in bench["configs"] if c["name"] == w["config"]]
    with open(os.path.join(root, cfg["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "bench", "traffic", w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return Cell(
        name=workload,
        chips=int(w["chips"]),
        config=config,
        traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, workload)],
    )


def configure_jax() -> None:
    """x64 on (an f64 policy is a traffic file away), and every compiled
    program kept in the persistent cache, so that only a cell's first run
    in a checkout compiles."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    jax.config.update("jax_enable_x64", True)
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def open_devices(chips: int, peaks: dict | None = None) -> list:
    """The cell's TPU devices; fails without a TPU, with fewer chips than the
    cell asks for, or on a device kind the table of peaks lacks."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoDevice(f"no TPU: the first device is {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, found {len(devs)}")
    roofline.device_peaks(devs[0].device_kind, peaks)
    return devs[:chips]


def seed_key(seed: int) -> int:
    return int(seed) % (1 << 64)


def start_vector(n: int, seed: int, idx: int) -> np.ndarray:
    """The start vector of request ``idx`` (``idx = -1``: the warm-up)."""
    rng = np.random.default_rng([seed_key(seed), idx % (1 << 32)])
    return rng.standard_normal(n).astype(np.float32)


@contextmanager
def span(name: str, enabled: bool, **kw):
    """A host span in the profiler's trace (nothing when not tracing)."""
    if not enabled:
        yield
        return
    import jax

    with jax.profiler.TraceAnnotation(name, **kw):
        yield


@contextmanager
def count_compiles():
    """Counts XLA compilations (persistent-cache loads included) per program
    while the context is open."""
    import jax

    programs: dict = {}

    def on_event(event, duration, fun_name=None, **kw):
        if event == BACKEND_COMPILE_EVENT:
            programs[fun_name] = programs.get(fun_name, 0) + 1

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        yield programs
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)


@dataclasses.dataclass
class Setup:
    cell: Cell
    seed: int
    graph: object  # bench.gen.Graph: what the reference reads
    csr: object  # repro.sparse.CSR: the program's own copy


def setup(cell: Cell, seed: int, scale: int | None = None) -> Setup:
    """Generate the matrix and warm the program up: the first ``eigsh``
    builds the session (layout, transfer) and compiles the cell's shapes."""
    import jax

    from repro import eigsh
    from repro.sparse import CSR

    g = generate(cell.config, seed_key(seed), scale)
    csr = CSR(g.indptr.copy(), g.indices.copy(), g.data.copy(), (g.n, g.n))
    res = eigsh(csr, v0=start_vector(g.n, seed, -1), **cell.traffic["request"])
    jax.block_until_ready((res.eigenvalues, res.eigenvectors))
    return Setup(cell, seed, g, csr)


@dataclasses.dataclass
class Request:
    idx: int
    t0: float
    t1: float
    theta: object  # device arrays until fetch(), then host
    x: object
    iterations: int
    solve_s: float
    prepare_s: float
    session_reuse: bool
    backend: str
    spmv_format: str

    @property
    def api_s(self) -> float:
        """Host time of the call outside the engine's solve."""
        return (self.t1 - self.t0) - self.solve_s


def window(
    s: Setup,
    seconds: float,
    traced: bool = False,
    request: dict | None = None,
    count: int | None = None,
):
    """Closed loop of requests until the one in progress at ``seconds`` has
    returned (or, given ``count``, until ``count`` have), with the mix's
    request keywords or ``request``.  Returns ``(requests, t_open, t_close,
    error)``."""
    import jax

    from repro import eigsh

    kw = dict(s.cell.traffic["request"] if request is None else request)
    out = []
    error = None
    with span("bench.window", traced):
        t_open = time.perf_counter()
        while True:
            idx = len(out)
            v0 = start_vector(s.graph.n, s.seed, idx)
            with span("bench.request", traced, idx=idx):
                t0 = time.perf_counter()
                try:
                    res = eigsh(s.csr, v0=v0, **kw)
                    jax.block_until_ready((res.eigenvalues, res.eigenvectors))
                except Exception as exc:  # an answer that never comes
                    error = f"request {idx}: {type(exc).__name__}: {exc}"
                    break
                t1 = time.perf_counter()
            out.append(
                Request(
                    idx, t0, t1, res.eigenvalues, res.eigenvectors, int(res.iterations),
                    float(res.timings.get("solve_s", 0.0)),
                    float(res.timings.get("prepare_s", 0.0)),
                    bool(res.session_reuse), res.backend, str(res.spmv_format),
                )
            )
            if (len(out) >= count) if count is not None else (t1 - t_open >= seconds):
                break
    t_close = out[-1].t1 if out else time.perf_counter()
    return out, t_open, t_close, error


def fetch(requests: list) -> None:
    """Copy every answer to the host and drop the program's state."""
    from repro.api import session_cache_clear

    for r in requests:
        r.theta = np.asarray(r.theta, np.float64)
        r.x = np.asarray(r.x, np.float64).T  # (k, n)
    session_cache_clear()
    gc.collect()


def check(s: Setup, requests: list) -> dict:
    """The widest of each compared number over the window's answers."""
    g = s.graph
    a = reference.matrix(g.indptr, g.indices, g.data, g.n)
    steps = int(s.cell.traffic["steps"])
    k = int(s.cell.traffic["request"]["k"])
    worst = dict.fromkeys(reference.NUMBERS, 0.0)
    for r in requests:
        ref_theta, ref_x = reference.ritz_pairs(a, start_vector(g.n, s.seed, r.idx), steps, k)
        try:
            got = reference.compare(a, r.theta, r.x, ref_theta, ref_x)
        except ValueError:  # an answer of the wrong shape says the wrong thing
            got = dict.fromkeys(reference.NUMBERS, float("inf"))
        for name, v in got.items():
            worst[name] = max(worst[name], v) if np.isfinite(v) else float("inf")
    return worst


@dataclasses.dataclass
class Outcome:
    """Everything one run measured, before it is printed."""

    cell: Cell
    seed: int
    setup_s: float
    requests: list
    t_open: float
    t_close: float
    error: str | None
    compiles: dict  # program -> compilations inside the window
    numbers: dict
    memory_peak_bytes: int | None  # on the fullest chip
    nnz: int
    n: int
    trace: object = None  # bench.trace.Reduced of the traced run

    @property
    def steps(self) -> int:
        return sum(r.iterations for r in self.requests)

    @property
    def window_s(self) -> float:
        return self.t_close - self.t_open

    def limits(self) -> dict:
        return self.cell.traffic["limits"]

    def correct(self) -> bool:
        want = int(self.cell.traffic["steps"])
        return (
            self.error is None
            and bool(self.requests)
            and all(r.iterations == want for r in self.requests)
            and all(self.numbers[k] <= lim for k, lim in self.limits().items())
        )


def run(
    cell: Cell,
    seed: int,
    seconds: float,
    traced: bool,
    *,
    t_start: float,
    scale: int | None = None,
    trace_dir: str | None = None,
) -> Outcome:
    """Set-up, window and check of one run; ``t_start`` is when the process
    started, so that set-up counts interpreter and JAX start-up too.
    ``trace_dir`` keeps the profiler's files (else they are deleted)."""
    import jax

    s = setup(cell, seed, scale)
    own_dir = traced and trace_dir is None
    tdir = tempfile.mkdtemp(prefix="bench-trace-") if own_dir else trace_dir
    setup_s = time.perf_counter() - t_start
    if traced:
        jax.profiler.start_trace(tdir)
    with count_compiles() as compiles:
        requests, t_open, t_close, error = window(s, seconds, traced)
    mem = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.devices()[: cell.chips]]
    fetch(requests)
    with span("bench.check", traced):
        numbers = check(s, requests)
    reduced = None
    if traced:
        jax.profiler.stop_trace()
        from . import trace

        reduced = trace.reduce_dir(tdir, cell.chips)
        if own_dir:
            shutil.rmtree(tdir, ignore_errors=True)
    return Outcome(
        cell, seed, setup_s, requests, t_open, t_close, error, compiles, numbers,
        max(mem) if None not in mem else None, s.graph.nnz, s.graph.n, reduced,
    )


def end_to_end(o: Outcome) -> dict:
    """The cell's end-to-end metrics, by their names in ``BENCHMARK.json``."""
    values = {
        "setup_s": o.setup_s,
        "step_ms": 1e3 * o.window_s / max(o.steps, 1),
    }
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]} for m in o.cell.end_to_end}


def per_layer(o: Outcome, peaks: dict) -> dict:
    """The cell's per-layer metrics, each read by ``bench/metrics/<name>.py``;
    a reader that finds nothing to read returns None and the metric is left
    out."""
    out = {}
    for m in o.cell.per_layer:
        reader = importlib.import_module(f"{__package__}.metrics.{m['name']}")
        v = reader.read(o, peaks)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out
