"""Pluggable SpMV execution layer: format selection + tile configuration.

The paper's headline speedup is the SpMV hot loop, but which *layout* wins is
a property of the matrix, not the solver: ELL when row lengths are near
uniform (padding overhead bounded), blocked-ELL/BSR when the non-zeros
cluster into dense blocks (SpMV becomes a stream of MXU matmuls — see
``spmv_bsr.py`` for the ~1/BS fill crossover), COO ``segment_sum`` otherwise.
:class:`SpmvEngine` packages that decision — format + accumulation dtype +
Pallas tile parameters — behind one object so every solver engine
(``solve_fixed``, ``solve_sharded``, ``ChunkedOperator``) executes the same
kernels instead of each open-coding its own SpMV.

Format auto-selection (``choose_format``) runs on cheap O(nnz) statistics of
the host CSR:

  * ``ell_overhead``  — padded ELL slots / nnz = ``max_row_nnz * n / nnz``.
    ELL is chosen when this is bounded (default <= 3.0: at most 2/3 of the
    kernel's work is padding).
  * ``block_fill``    — nnz / (touched BS x BS blocks * BS^2).  BSR wins when
    a stored block is dense enough that one MXU matvec beats BS scalar-gather
    rows; the absolute flop crossover is ~1/BS (spmv_bsr.py), but padding and
    bandwidth push the practical line higher, so the default requires
    ``block_fill >= BSR_FILL_FACTOR / BS`` (factor 4 => half-dense blocks at
    BS=8).

A fourth format, ``hybrid``, is the hub-row split: ELL width is capped at a
quantile of the row lengths and the overflow of the few hub rows spills into
a COO tail (``segment_sum``).  Power-law matrices whose max row blows the ELL
bound still run the Pallas kernel for the bounded bulk of their non-zeros
(``hyb_overhead`` / ``hyb_tail_frac`` in :class:`SpmvStats` drive the choice).

Tile parameters come from the static table (``select_tiles``) by default, or
from the **measured autotuner** (:func:`tuned_tiles`) when
``REPRO_SPMV_TUNE=1``: a small candidate grid is timed on probe SpMVs for the
actual (shape-bucket, dtype, format), memoized in-process and persisted to a
JSON cache (``REPRO_SPMV_TUNE_CACHE``).  The static table remains the prior
and the cold-start fallback, and ``REPRO_SPMV_TILES`` pins tiles outright;
the decision's provenance ("table" | "tuned" | "override") is surfaced in
``partition["spmv"]``.

On top of the per-SpMV tile probes, the tuner resolves a **whole-iteration
plan** (:class:`IterationPlan`): fused-vs-unfused Lanczos update (and the
fully-fused SpMV+alpha pass for ELL) x tile shapes x BSR block size, timed
on a real Lanczos step — SpMV, alpha dot, three-term update, norm — because
the fastest SpMV tile is not always the fastest *iteration* (the fused
kernels shift where the memory traffic goes).  The winner persists in the
same JSON cache (``kind: "iteration"`` entries) and is surfaced as
``partition["spmv"]["iteration_plan"]``; with tuning off a static table
keyed on the execution mode decides (interpret mode pays per-grid-step
interpreter overhead that makes the fused kernels lose, so it defaults to
unfused; compiled Mosaic defaults to fused).  Every persisted entry carries
a grid fingerprint (:func:`grid_fingerprint`) hashing the candidate-space
definition, so autotuner or kernel-grid changes auto-invalidate stale
entries instead of requiring a manual CI cache-key bump.

**What runs compiled on TPU** (:func:`spmv_runs_pallas`).  The SpMV kernels
(``spmv_ell``, ``spmv_ell_alpha``, ``spmv_ell_packed``, ``spmv_bsr``) hold
the whole ``x`` in VMEM and gather from it: Mosaic refuses their 1-D gather,
and the 16 MiB scoped VMEM caps a double-buffered f32 ``x`` near 2M entries.
So compiled (TPU) execution runs the SpMV as XLA gathers over the same ELL /
BSR / hybrid layouts, and only the vector kernels (``lanczos_update``,
``mixed_dot``) run as Mosaic kernels.  Interpret mode still runs every
kernel, which is how the CPU tests cover them.  ``partition["spmv"]
["kernels"]`` reports the split per phase (:func:`phase_executors`).
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import json
import os
import time
import warnings
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import env as envcfg

__all__ = [
    "FORMATS",
    "ITER_UPDATE_MODES",
    "TileConfig",
    "IterationPlan",
    "TileTuner",
    "SpmvStats",
    "SpmvEngine",
    "grid_fingerprint",
    "matrix_stats",
    "shard_stats",
    "choose_format",
    "select_tiles",
    "tuned_tiles",
    "resolve_iteration_plan",
    "table_update_mode",
    "spmv_runs_pallas",
    "phase_executors",
    "get_tuner",
    "tuner_probe_count",
    "make_engine",
]

FORMATS = ("coo", "ell", "bsr", "hybrid")

# ELL accepted while padded slots <= ELL_MAX_OVERHEAD * nnz.
ELL_MAX_OVERHEAD = 3.0
# BSR accepted while block_fill >= BSR_FILL_FACTOR / block_size.
BSR_FILL_FACTOR = 4.0
DEFAULT_BLOCK_SIZE = 8
# Hybrid ELL+COO: cap the ELL width at this quantile of the row lengths...
HYBRID_QUANTILE = 0.95
# ...and accept while the spilled tail stays a minority of the nnz (the
# kernel must do the bulk of the work for the split to beat plain COO).
HYBRID_MAX_TAIL = 0.6


def _env_float(name: str, default: float) -> float:
    return envcfg.get_float(name, default, lenient=True)


def ell_overhead_bound() -> float:
    """The effective ELL padding bound (env-overridable) — the single parse
    every consumer of ``REPRO_SPMV_ELL_OVERHEAD`` shares."""
    return _env_float("REPRO_SPMV_ELL_OVERHEAD", ELL_MAX_OVERHEAD)


def _fit_tile(tile: int, extent: int) -> int:
    """Largest tile <= ``tile`` that divides ``extent`` (halving search)."""
    t = max(1, min(tile, extent))
    while extent % t:
        t //= 2
    return t


@dataclasses.dataclass(frozen=True)
class TileConfig:
    """Pallas grid tile parameters for the SpMV kernels.

    ``block_r`` / ``block_w`` tile the ELL (rows, width) grid; ``block_size``
    is the dense block edge of the blocked-ELL/BSR layout.  Conversions pad
    rows to ``block_r`` and widths to ``block_w`` so the kernel BlockSpecs
    always divide evenly.
    """

    block_r: int = 8
    block_w: int = 128
    block_size: int = DEFAULT_BLOCK_SIZE


# How the Lanczos three-term update runs, in increasing fusion order:
#   unfused    — jnp expressions (XLA fuses what it can; fastest in interpret
#                mode, where Pallas pays per-grid-step interpreter overhead)
#   fused      — the lanczos_update kernel (update + norm in one pass)
#   fused_spmv — spmv_ell_alpha + lanczos_update: the whole iteration in two
#                passes over the Krylov vectors (ELL only)
ITER_UPDATE_MODES = ("unfused", "fused", "fused_spmv")
# BSR block edges the iteration probe re-converts through (the block size
# changes the *layout*, so picking it needs a measurement, not a re-tile).
_ITER_BSR_BLOCKS = (4, 8, 16)


@dataclasses.dataclass(frozen=True)
class IterationPlan:
    """Measured whole-iteration decision: update mode + tiles (jointly).

    ``tiles.block_size`` carries the BSR block-edge decision (a re-conversion,
    not a re-tile).  ``source`` is the provenance: "table" (static default for
    the execution mode), "tuned" (won a measured whole-iteration probe), or
    "override" (``REPRO_ITER_UPDATE`` pin).
    """

    update: str = "unfused"
    tiles: TileConfig = TileConfig()
    source: str = "table"  # "table" | "tuned" | "override"

    def __post_init__(self):
        if self.update not in ITER_UPDATE_MODES:
            raise ValueError(
                f"unknown update mode {self.update!r}; expected {ITER_UPDATE_MODES}"
            )

    def as_dict(self) -> dict:
        return {
            "update": self.update,
            "block_r": self.tiles.block_r,
            "block_w": self.tiles.block_w,
            "block_size": self.tiles.block_size,
            "source": self.source,
        }


# Bump when the cache entry layout itself changes (fields, key format).
_GRID_SCHEMA = 2


def grid_fingerprint() -> str:
    """Hash of the autotuner's candidate-space definition.

    Stamped into every persisted cache entry and checked on load: a change to
    the tile table, the update-mode space, or the probe grids silently drops
    stale entries (they re-measure on next use) instead of serving tiles that
    were never measured against the current kernels.  This replaces the old
    "bump the CI cache-key suffix by hand" contract.
    """
    payload = repr((_GRID_SCHEMA, _TILE_TABLE, ITER_UPDATE_MODES, _ITER_BSR_BLOCKS))
    return hashlib.blake2b(payload.encode(), digest_size=8).hexdigest()


# Static tile table: (max_rows, max_width) upper bounds -> (block_r, block_w).
# Larger shards get taller/wider tiles to amortize grid steps; entries are
# scanned in order and the first row that fits is used.  bf16/f16 rows double
# block_r to honor the TPU (16, 128) sublane minimum for 16-bit dtypes.
_TILE_TABLE: Tuple[Tuple[int, int, int, int], ...] = (
    # max_rows, max_width, block_r, block_w
    (1 << 10, 1 << 8, 8, 128),
    (1 << 10, 1 << 30, 8, 256),
    (1 << 14, 1 << 8, 16, 128),
    (1 << 14, 1 << 30, 16, 256),
    (1 << 30, 1 << 8, 32, 128),
    (1 << 30, 1 << 30, 32, 512),
)


def select_tiles(
    n_rows: int,
    width: int,
    dtype=jnp.float32,
    block_size: int = DEFAULT_BLOCK_SIZE,
    interpret: bool = False,
) -> TileConfig:
    """Pick kernel tiles from the static table (env override wins).

    ``REPRO_SPMV_TILES="block_r,block_w[,block_size]"`` pins the tiles for
    experiments (the env/config hook the ROADMAP autotuner will replace).

    ``interpret=True`` (CPU validation): the Pallas interpreter executes grid
    steps sequentially with high per-step overhead and has no VMEM ceiling,
    so it gets few, large tiles — same kernel code, tractable wall time.
    """
    env = envcfg.get_str("REPRO_SPMV_TILES")
    if env:
        parts = [int(p) for p in env.split(",")]
        if len(parts) not in (2, 3):
            raise ValueError(
                f"REPRO_SPMV_TILES={env!r}: expected 'block_r,block_w[,block_size]'"
            )
        bs = parts[2] if len(parts) == 3 else block_size
        return TileConfig(block_r=parts[0], block_w=parts[1], block_size=bs)

    if interpret:
        return TileConfig(block_r=512, block_w=2048, block_size=block_size)

    block_r, block_w = _TILE_TABLE[-1][2:]
    for max_rows, max_width, br, bw in _TILE_TABLE:
        if n_rows <= max_rows and width <= max_width:
            block_r, block_w = br, bw
            break
    if jnp.dtype(dtype).itemsize == 2:  # bf16/f16 sublane minimum is 16
        block_r = max(block_r, 16)
    return TileConfig(block_r=block_r, block_w=block_w, block_size=block_size)


# ------------------------------ tile autotuner -------------------------------

DEFAULT_TUNE_CACHE = os.path.join(
    os.path.expanduser("~"), ".cache", "repro", "spmv_tune.json"
)
# Formats whose kernel exposes tile knobs (the BSR kernel's tiling is fixed by
# its block size, so only the ELL-family grids are tunable).
_TUNABLE_FORMATS = ("ell", "hybrid")


def tune_enabled() -> bool:
    """Measured tuning is opt-in: the static table is the default behavior."""
    return envcfg.get_bool("REPRO_SPMV_TUNE")


class TileTuner:
    """Measured tile cache: in-process memo + persistent JSON.

    One entry per (format, dtype, shape-bucket, execution mode) key; the value
    is the fastest :class:`TileConfig` of the measured candidate grid plus the
    raw per-candidate timings (kept for postmortems).  Whole-iteration plans
    (:class:`IterationPlan`) live in the same file as ``kind: "iteration"``
    entries under an ``iter|``-prefixed key.  Every entry is stamped with the
    current :func:`grid_fingerprint`; entries whose stamp mismatches (or is
    absent — pre-fingerprint caches) are dropped on load, so a stale cache
    re-measures instead of serving tiles from a different candidate space.
    The JSON survives processes (CI caches it between runs); a missing/corrupt
    file degrades to an empty cache, never an error.
    """

    def __init__(self, cache_path: Optional[str] = None):
        self.cache_path = cache_path or DEFAULT_TUNE_CACHE
        self._mem: Dict[str, TileConfig] = {}
        self._plans: Dict[str, IterationPlan] = {}
        self._meta: Dict[str, dict] = {}
        self._loaded = False
        self.measure_count = 0  # tune passes actually run (tests assert on it)

    def _load(self) -> None:
        if self._loaded:
            return
        self._loaded = True
        fp = grid_fingerprint()
        try:
            with open(self.cache_path) as f:
                payload = json.load(f)
            for key, rec in payload.get("entries", {}).items():
                if rec.get("grid") != fp:
                    continue  # stale candidate space: drop, re-measure on use
                tiles = TileConfig(
                    block_r=int(rec["block_r"]),
                    block_w=int(rec["block_w"]),
                    block_size=int(rec.get("block_size", DEFAULT_BLOCK_SIZE)),
                )
                if rec.get("kind") == "iteration":
                    self._plans[key] = IterationPlan(
                        update=str(rec["update"]), tiles=tiles, source="tuned"
                    )
                else:
                    self._mem[key] = tiles
                self._meta[key] = rec
        except (OSError, ValueError, KeyError, TypeError):
            pass  # absent or corrupt cache = cold start

    def lookup(self, key: str) -> Optional[TileConfig]:
        self._load()
        return self._mem.get(key)

    def lookup_plan(self, key: str) -> Optional[IterationPlan]:
        self._load()
        return self._plans.get(key)

    def _dump(self) -> None:
        try:
            os.makedirs(os.path.dirname(os.path.abspath(self.cache_path)), exist_ok=True)
            tmp = self.cache_path + ".tmp"
            with open(tmp, "w") as f:
                json.dump({"version": 2, "entries": self._meta}, f, indent=1, sort_keys=True)
            os.replace(tmp, self.cache_path)
        except OSError:
            pass  # read-only cache dir: keep the in-process memo only

    def record(self, key: str, tiles: TileConfig, timings: Dict[str, float]) -> None:
        self._load()
        self._mem[key] = tiles
        self._meta[key] = {
            "block_r": tiles.block_r,
            "block_w": tiles.block_w,
            "block_size": tiles.block_size,
            "grid": grid_fingerprint(),
            "best_us": min(timings.values()) if timings else None,
            "candidates_us": timings,
        }
        self._dump()

    def record_plan(self, key: str, plan: IterationPlan, timings: Dict[str, float]) -> None:
        self._load()
        plan = dataclasses.replace(plan, source="tuned")
        self._plans[key] = plan
        self._meta[key] = {
            "kind": "iteration",
            "update": plan.update,
            "block_r": plan.tiles.block_r,
            "block_w": plan.tiles.block_w,
            "block_size": plan.tiles.block_size,
            "grid": grid_fingerprint(),
            "best_us": min(timings.values()) if timings else None,
            "candidates_us": timings,
        }
        self._dump()


_TUNER: Optional[TileTuner] = None


def get_tuner() -> TileTuner:
    """Process-wide tuner bound to the current ``REPRO_SPMV_TUNE_CACHE``."""
    global _TUNER
    path = envcfg.raw("REPRO_SPMV_TUNE_CACHE") or DEFAULT_TUNE_CACHE
    if _TUNER is None or _TUNER.cache_path != path:
        _TUNER = TileTuner(path)
    return _TUNER


def tuner_probe_count() -> int:
    """Measured tune passes run by this process so far (0 when tuning is
    off).  The session layer (api/session.py) verifies plan reuse against
    this: a cache-hit solve must not add probes."""
    return _TUNER.measure_count if _TUNER is not None else 0


def _next_pow2(x: int) -> int:
    return 1 << max(0, int(x) - 1).bit_length()


def _tune_key(fmt: str, dtype, n_rows: int, width: int, interpret: bool) -> str:
    """Shape-bucketed cache key: tiles depend on the size class, not the
    exact shard shape, so nearby problems share one measurement."""
    mode = "interp" if interpret else "mosaic"
    return f"{fmt}|{jnp.dtype(dtype).name}|r{_next_pow2(n_rows)}|w{_next_pow2(width)}|{mode}"


def _candidate_tiles(
    prior: TileConfig, dtype, interpret: bool, block_size: int
) -> Tuple[TileConfig, ...]:
    """Small grid around the static-table prior (the prior is always in it,
    so a tuned choice can never be worse than the table on the probe)."""
    budget = envcfg.get_int("REPRO_SPMV_TUNE_BUDGET")
    min_r = 16 if jnp.dtype(dtype).itemsize == 2 else 8
    if interpret:
        # The interpreter pays ~ms per grid step: only few-large-tile layouts
        # are viable, so the grid just probes the step-count tradeoff.
        rows = (prior.block_r, prior.block_r * 2, max(min_r, prior.block_r // 2))
        widths = (prior.block_w,)
    else:
        rows = (prior.block_r, prior.block_r * 2, max(min_r, prior.block_r // 2))
        widths = (prior.block_w, max(128, prior.block_w // 2), min(2048, prior.block_w * 2))
    out = []
    for r in rows:
        for w in widths:
            cfg = TileConfig(block_r=r, block_w=w, block_size=block_size)
            if cfg not in out:
                out.append(cfg)
    return tuple(out[: max(1, budget)])


def _measure_ell_tiles(
    n_rows: int,
    width: int,
    dtype,
    candidates: Sequence[TileConfig],
    interpret: bool,
    reps: int = 3,
) -> Dict[str, float]:
    """Median wall time (us) of probe ELL SpMVs per candidate tile config.

    The probe is a synthetic uniform ELL at the *layout* width the caller's
    conversions would build (callers pass the aligned width, see
    ``make_engine``), so the width tile each candidate is timed with is the
    one ``ell_matvec``'s divisibility clamp would actually run — the
    recorded key holds that runtime-adapted tile, never an unmeasured one.
    Rows are pow2-bucketed and capped so a tune pass stays sub-second-ish
    per candidate in interpret mode; the result is a *relative* ranking for
    this (shape, dtype, mode), not an absolute projection.
    """
    from .spmv_ell import spmv_ell_kernel_call

    # Probe at the problem's own row bucket: candidates whose block_r exceeds
    # it are skipped below (building the layout at such a tile would inflate
    # the real padded rows — a cost a bigger probe could never see).
    min_br = min(c.block_r for c in candidates)
    rows_cap = 1 << 12 if interpret else 1 << 16
    rows = min(max(_next_pow2(n_rows), min_br), max(rows_cap, min_br))
    # Probe width: the real (already-aligned) layout width, capped for cost —
    # the cap rounds DOWN to the width's own alignment so candidate tiles
    # divide the probe exactly when they divide the real layout.
    width = max(8, width)
    width_cap = 1 << 11
    if width <= width_cap:
        width_b = width
    else:
        align = 128 if width % 128 == 0 else 8
        width_b = max(align, (width_cap // align) * align)
    rng = np.random.default_rng(0)
    val = jnp.asarray(rng.standard_normal((rows, width_b)), dtype=dtype)
    col = jnp.asarray(rng.integers(0, rows, (rows, width_b)), jnp.int32)
    x = jnp.asarray(rng.standard_normal(rows), dtype=dtype)
    # Dedup on the runtime-adapted tile: candidates differing only in a
    # block_w that _fit_tile collapses to the same width are one measurement.
    fitted = []
    for cfg in candidates:
        if rows % cfg.block_r:
            continue
        bw_real = _fit_tile(cfg.block_w, width)  # what ell_matvec would run
        if (cfg.block_r, bw_real) not in fitted:
            fitted.append((cfg.block_r, bw_real))
    timings: Dict[str, float] = {}
    for block_r, bw_real in fitted:
        bw_probe = _fit_tile(bw_real, width_b)
        acc = jnp.float32

        def run(br=block_r, bw=bw_probe):
            return spmv_ell_kernel_call(
                val, col, x, block_r=br, block_w=bw, accum_dtype=acc, interpret=interpret
            ).block_until_ready()

        run()  # compile/trace outside the timed reps
        ts = []
        for _ in range(max(1, reps)):
            t0 = time.perf_counter()
            run()
            ts.append(time.perf_counter() - t0)
        timings[f"{block_r}x{bw_real}"] = float(np.median(ts) * 1e6)
    return timings


def tuned_tiles(
    n_rows: int,
    width: int,
    dtype=jnp.float32,
    format: str = "ell",
    block_size: int = DEFAULT_BLOCK_SIZE,
    interpret: bool = False,
) -> Tuple[TileConfig, str]:
    """Resolve kernel tiles with provenance: "table" | "tuned" | "override".

    Resolution order: the ``REPRO_SPMV_TILES`` pin wins outright ("override");
    otherwise the static table is the prior, and — only when
    ``REPRO_SPMV_TUNE=1`` and the format has tunable tiles — a measured pass
    over a small candidate grid refines it ("tuned"), cached under
    ``REPRO_SPMV_TUNE_CACHE`` so each (shape-bucket, dtype, format, mode) is
    measured at most once per cache lifetime.
    """
    if envcfg.get_str("REPRO_SPMV_TILES"):
        return select_tiles(n_rows, width, dtype, block_size, interpret), "override"
    prior = select_tiles(n_rows, width, dtype, block_size, interpret)
    if (
        not tune_enabled()
        or not spmv_runs_pallas(interpret)  # no kernel whose tiles could be timed
        or format not in _TUNABLE_FORMATS
        or n_rows <= 0
        or width <= 0
    ):
        return prior, "table"
    tuner = get_tuner()
    key = _tune_key(format, dtype, n_rows, width, interpret)
    hit = tuner.lookup(key)
    if hit is not None:
        return dataclasses.replace(hit, block_size=block_size), "tuned"
    candidates = _candidate_tiles(prior, dtype, interpret, block_size)
    timings = _measure_ell_tiles(n_rows, width, dtype, candidates, interpret)
    tuner.measure_count += 1
    if not timings:  # no candidate survived shape constraints: keep the prior
        return prior, "table"
    best_name = min(timings, key=timings.get)
    br, bw = (int(p) for p in best_name.split("x"))
    best = TileConfig(block_r=br, block_w=bw, block_size=block_size)
    tuner.record(key, best, timings)
    return best, "tuned"


# --------------------------- whole-iteration tuner ---------------------------


def spmv_runs_pallas(interpret: bool) -> bool:
    """Whether the SpMV goes through its Pallas kernels: only under the
    interpreter.  Compiled (TPU) SpMV runs as XLA gathers over the same
    layouts — see the module docstring for why Mosaic cannot take them."""
    return interpret


def phase_executors(fmt: str, interpret: bool, update: str, compute_dtype) -> dict:
    """What runs each per-iteration phase: ``"mosaic"`` (a compiled Pallas
    kernel), ``"pallas_interpret"`` or ``"xla"``.  ``fmt`` is the SpMV format
    ("coo" also stands for dense and matrix-free operators, and has no
    kernel), ``update`` the effective update mode; f64 compute keeps
    the jnp update."""
    pallas = "pallas_interpret" if interpret else "mosaic"
    spmv = pallas if fmt != "coo" and spmv_runs_pallas(interpret) else "xla"
    fused = update != "unfused" and jnp.dtype(compute_dtype) != jnp.dtype(jnp.float64)
    return {"spmv": spmv, "update": pallas if fused else "xla"}


def table_update_mode(interpret: bool) -> str:
    """Static update-mode prior when no measured plan exists.

    Interpret mode (CPU validation) pays ~ms of interpreter overhead per
    Pallas grid step, so the fused kernels *lose* there — the smoke baseline
    measured the fused update ~9x slower than XLA's unfused expressions.
    Compiled Mosaic is the memory-bound regime the fusion targets; there
    the ``lanczos_update`` kernel runs compiled (``fused_spmv`` needs the
    SpMV kernels, which compiled mode does not run).
    """
    return "unfused" if interpret else "fused"


def _iter_candidates(
    fmt: str, tiles: TileConfig, interpret: bool, tile_variants: bool
) -> Tuple[Tuple[str, TileConfig], ...]:
    """(update mode, tiles) candidate space for the whole-iteration probe.

    ELL probes the fully-fused pass and one taller tile variant; BSR probes
    block edges (a re-conversion decision — the layout changes with the
    edge); COO/hybrid only choose fused-vs-unfused update (their SpMV is
    identical across update modes).  Compiled mode runs the SpMV as XLA
    (:func:`spmv_runs_pallas`), so there every format chooses only the
    update.
    """
    if not spmv_runs_pallas(interpret):
        return tuple((mode, tiles) for mode in ("unfused", "fused"))
    if fmt == "bsr":
        return tuple(
            (mode, dataclasses.replace(tiles, block_size=bs))
            for mode in ("unfused", "fused")
            for bs in _ITER_BSR_BLOCKS
        )
    if fmt == "ell":
        tile_opts = [tiles]
        if tile_variants:
            taller = dataclasses.replace(tiles, block_r=tiles.block_r * 2)
            if taller not in tile_opts:
                tile_opts.append(taller)
        return tuple((mode, t) for mode in ITER_UPDATE_MODES for t in tile_opts)
    return tuple((mode, tiles) for mode in ("unfused", "fused"))


def _measure_iteration(
    n_rows: int,
    width: int,
    dtype,
    fmt: str,
    candidates: Sequence[Tuple[str, TileConfig]],
    interpret: bool,
    reps: int = 3,
) -> Tuple[Dict[str, float], Dict[str, Tuple[str, TileConfig]]]:
    """Median wall time (us) of one synthetic Lanczos step per candidate.

    The step is the real per-iteration work — SpMV, alpha dot, three-term
    update, squared norm — composed from the same kernel entrypoints the
    solvers run, jitted as one function so the ranking sees what XLA actually
    schedules.  Shapes are pow2-bucketed and capped exactly like the SpMV
    probe; the result is a relative ranking, not an absolute projection.
    """
    from .lanczos_fused import spmv_ell_alpha_kernel_call
    from .ops import lanczos_update
    from .spmv_bsr import spmv_bsr_kernel_call
    from .spmv_ell import spmv_ell_kernel_call

    acc = jnp.float32
    rows_cap = 1 << 12 if interpret else 1 << 16
    rows = min(max(_next_pow2(n_rows), 8), rows_cap)
    width = max(8, width)
    width_cap = 1 << 11
    if width <= width_cap:
        width_b = width
    else:
        align = 128 if width % 128 == 0 else 8
        width_b = max(align, (width_cap // align) * align)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(rows), dtype=acc)
    xp = jnp.asarray(rng.standard_normal(rows), dtype=acc)
    beta = jnp.asarray(0.25, acc)
    ell_data = bsr_data = w_synth = None
    if not spmv_runs_pallas(interpret):
        fmt = "coo"  # the SpMV is XLA either way: probe the update alone
    if fmt == "ell":
        ell_data = (
            jnp.asarray(rng.standard_normal((rows, width_b)), dtype=dtype),
            jnp.asarray(rng.integers(0, rows, (rows, width_b)), jnp.int32),
        )
    elif fmt == "bsr":
        bsr_data = {}
    else:
        w_synth = jnp.asarray(rng.standard_normal(rows), dtype=acc)

    def _update(w, mode):
        a = jnp.sum(x * w)
        if mode == "unfused":
            u = w - a * x - beta * xp
            return u, jnp.sum(u * u)
        return lanczos_update(w, x, xp, a, beta, accum_dtype=acc, interpret=interpret)

    timings: Dict[str, float] = {}
    by_name: Dict[str, Tuple[str, TileConfig]] = {}
    for mode, tiles in candidates:
        if fmt == "ell":
            # Fit oversized tiles to the probe shape exactly like ell_matvec
            # adapts at runtime (small problems vs interpret-mode 512-row
            # tiles); variants collapsing to the same fitted grid dedupe on
            # the name below.
            br = _fit_tile(tiles.block_r, rows)
            bw = _fit_tile(tiles.block_w, width_b)
            val, col = ell_data
            if mode == "fused_spmv":

                def step(br=br, bw=bw, val=val, col=col):
                    w, a = spmv_ell_alpha_kernel_call(
                        val, col, x, x, block_r=br, block_w=bw,
                        accum_dtype=acc, interpret=interpret,
                    )
                    return lanczos_update(
                        w, x, xp, a[0], beta, accum_dtype=acc, interpret=interpret
                    )
            else:

                def step(br=br, bw=bw, val=val, col=col, mode=mode):
                    w = spmv_ell_kernel_call(
                        val, col, x, block_r=br, block_w=bw,
                        accum_dtype=acc, interpret=interpret,
                    )
                    return _update(w, mode)

            name = f"{mode}|{br}x{bw}"
        elif fmt == "bsr":
            bs = tiles.block_size
            if rows % bs:
                continue
            if bs not in bsr_data:
                nbr = rows // bs
                slots = max(1, min(8, width_b // bs))
                bsr_data[bs] = (
                    jnp.asarray(rng.standard_normal((nbr, slots, bs, bs)), dtype=dtype),
                    jnp.asarray(rng.integers(0, nbr, (nbr, slots)), jnp.int32),
                )
            val, bcol = bsr_data[bs]

            def step(val=val, bcol=bcol, mode=mode):
                w = spmv_bsr_kernel_call(val, bcol, x, accum_dtype=acc, interpret=interpret)
                return _update(w, mode)

            name = f"{mode}|bs{bs}"
        else:
            # COO/hybrid: the SpMV is the same either way, so probe just the
            # update half the decision actually switches.
            def step(mode=mode):
                return _update(w_synth, mode)

            name = f"{mode}|update"
        if name in by_name:
            continue  # tile variants that fit to the same probe grid
        by_name[name] = (mode, tiles)
        run = jax.jit(step)

        def call():
            u, nrm = run()
            u.block_until_ready()
            return nrm

        call()  # compile/trace outside the timed reps
        ts = []
        for _ in range(max(1, reps)):
            t0 = time.perf_counter()
            call()
            ts.append(time.perf_counter() - t0)
        timings[name] = float(np.median(ts) * 1e6)
    return timings, by_name


def resolve_iteration_plan(
    n_rows: int,
    width: int,
    dtype=jnp.float32,
    format: str = "ell",
    tiles: TileConfig = TileConfig(),
    interpret: bool = False,
    tile_variants: bool = True,
) -> IterationPlan:
    """Resolve the whole-iteration plan with provenance.

    Resolution order mirrors :func:`tuned_tiles`: a ``REPRO_ITER_UPDATE`` pin
    wins outright ("override"); with ``REPRO_SPMV_TUNE=1`` a measured probe
    over :func:`_iter_candidates` decides and persists ("tuned"); otherwise
    the static mode table decides ("table").  ``tiles`` is the already-
    resolved SpMV tile choice — the probe may refine it (ELL tile variants,
    BSR block edges), and :func:`make_engine` adopts the winner's tiles.
    """
    env = (envcfg.get_str("REPRO_ITER_UPDATE") or "").strip().lower()
    if env:
        if env not in ITER_UPDATE_MODES:
            raise ValueError(
                f"REPRO_ITER_UPDATE={env!r}: expected one of {ITER_UPDATE_MODES}"
            )
        return IterationPlan(update=env, tiles=tiles, source="override")
    table = IterationPlan(update=table_update_mode(interpret), tiles=tiles, source="table")
    if not tune_enabled() or n_rows <= 0 or width <= 0:
        return table
    tuner = get_tuner()
    key = "iter|" + _tune_key(format, dtype, n_rows, width, interpret)
    hit = tuner.lookup_plan(key)
    if hit is not None:
        return hit
    candidates = _iter_candidates(format, tiles, interpret, tile_variants)
    budget = envcfg.get_int("REPRO_SPMV_TUNE_BUDGET")
    candidates = candidates[: max(2, budget * 2)]
    timings, by_name = _measure_iteration(n_rows, width, dtype, format, candidates, interpret)
    tuner.measure_count += 1
    if not timings:  # no candidate survived shape constraints
        return table
    best_name = min(timings, key=timings.get)
    mode, best_tiles = by_name[best_name]
    plan = IterationPlan(update=mode, tiles=best_tiles, source="tuned")
    tuner.record_plan(key, plan, timings)
    return plan


@dataclasses.dataclass(frozen=True)
class SpmvStats:
    """Cheap per-matrix (or per-shard) layout statistics driving selection."""

    n_rows: int
    nnz: int
    max_row_nnz: int
    mean_row_nnz: float
    ell_overhead: float  # padded ELL slots / nnz (1.0 = no padding)
    block_size: int
    n_blocks: int  # touched BS x BS blocks
    block_fill: float  # nnz / (n_blocks * BS^2)
    # Hybrid ELL+COO split: ELL width capped at the HYBRID_QUANTILE of row
    # lengths, hub overflow spilled to a COO tail.
    hyb_width: int = 0  # the capped ELL width
    hyb_tail_nnz: int = 0  # nnz spilled past the cap
    hyb_overhead: float = 0.0  # (capped ELL slots + tail) / nnz
    hyb_tail_frac: float = 0.0  # tail nnz / nnz
    block_row_max: int = 0  # touched blocks in the fullest block-row

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)

    def layout_bytes(self, fmt: str, value_bytes: int) -> int:
        """Device bytes of the layout ``fmt`` builds, padding included: a
        value and an int32 column per slot, plus an int32 row per COO
        triplet.  ELL pads every row to the longest (128-lane aligned),
        hybrid to its capped width (8-slot aligned) plus the tail (counted at
        the unaligned cap: an upper bound), BSR every block-row to the
        fullest one."""
        vb = int(value_bytes)
        if fmt == "ell":
            width = -(-max(1, self.max_row_nnz) // 128) * 128
            return width * self.n_rows * (vb + 4)
        if fmt == "hybrid":
            width = -(-max(1, self.hyb_width) // 8) * 8
            return width * self.n_rows * (vb + 4) + self.hyb_tail_nnz * (vb + 8)
        if fmt == "bsr":
            bs = self.block_size
            slots = -(-self.n_rows // bs) * self.block_row_max
            return slots * (bs * bs * vb + 4)
        return self.nnz * (vb + 8)


def hybrid_quantile() -> float:
    return _env_float("REPRO_SPMV_HYBRID_Q", HYBRID_QUANTILE)


def hybrid_width_cap(row_nnz: np.ndarray, quantile: Optional[float] = None) -> int:
    """The hybrid split's ELL width: the given quantile of the row lengths
    (hub rows above it spill their overflow into the COO tail)."""
    if not row_nnz.size or not int(row_nnz.max()):
        return 0
    q = hybrid_quantile() if quantile is None else quantile
    cap = int(np.ceil(np.quantile(row_nnz, min(max(q, 0.0), 1.0))))
    return max(1, min(cap, int(row_nnz.max())))


def _stats_from_triplets(
    row_nnz: np.ndarray,
    rows: Optional[np.ndarray],
    cols: Optional[np.ndarray],
    n_rows: int,
    block_size: int,
    width: Optional[int] = None,
    hyb_width: Optional[int] = None,
) -> SpmvStats:
    """``rows``/``cols`` may be None to skip the (sort-heavy) block census —
    used when the format is forced and block density is never consulted.
    ``width`` overrides the ELL width used for the overhead estimate (shards
    of a distributed solve all pay the *global* max row width, since
    shard_map forces one shared ELL shape); ``hyb_width`` likewise overrides
    the hybrid cap (shards share one capped width too)."""
    nnz = int(row_nnz.sum())
    max_row = int(row_nnz.max()) if row_nnz.size else 0
    mean_row = nnz / max(1, n_rows)
    overhead = (max(max_row, width or 0) * n_rows) / max(1, nnz)
    bs = block_size
    if nnz and rows is not None:
        nbc = -(-int(cols.max() + 1) // bs)
        keys = np.unique((rows // bs).astype(np.int64) * nbc + cols // bs)
        n_blocks = int(keys.size)
        block_row_max = int(np.bincount(keys // nbc).max())
    else:
        n_blocks = block_row_max = 0
    # No census (skipped or empty matrix) must read as "no block structure",
    # never as infinite fill — otherwise auto-selection would pick BSR.
    fill = nnz / (n_blocks * bs * bs) if n_blocks else 0.0
    cap = hybrid_width_cap(row_nnz) if hyb_width is None else int(hyb_width)
    tail = int(np.maximum(row_nnz - cap, 0).sum()) if (nnz and cap) else 0
    return SpmvStats(
        n_rows=n_rows,
        nnz=nnz,
        max_row_nnz=max_row,
        mean_row_nnz=mean_row,
        ell_overhead=overhead,
        block_size=bs,
        n_blocks=n_blocks,
        block_fill=fill,
        hyb_width=cap,
        hyb_tail_nnz=tail,
        hyb_overhead=(cap * n_rows + tail) / max(1, nnz),
        hyb_tail_frac=tail / max(1, nnz),
        block_row_max=block_row_max,
    )


def matrix_stats(
    csr, block_size: int = DEFAULT_BLOCK_SIZE, with_blocks: bool = True
) -> SpmvStats:
    """O(nnz) layout statistics of a host CSR (the block census is the only
    super-linear part; skip it with ``with_blocks=False``)."""
    row_nnz = csr.row_nnz()
    if with_blocks:
        rows = np.repeat(np.arange(csr.n, dtype=np.int64), row_nnz)
        return _stats_from_triplets(row_nnz, rows, csr.indices, csr.n, block_size)
    return _stats_from_triplets(row_nnz, None, None, csr.n, block_size)


def shard_stats(
    csr,
    splits: np.ndarray,
    block_size: int = DEFAULT_BLOCK_SIZE,
    with_blocks: bool = True,
) -> Tuple[SpmvStats, ...]:
    """Per-shard statistics for a row-partitioned CSR (splits from
    ``core.partition.nnz_balanced_splits``).

    Block density is measured in the *remapped padded-global* column
    coordinates the distributed BSR layout actually uses
    (``sparse.formats.shard_to_blocked_ell``: columns become
    ``owner * n_pad + local`` with ``n_pad`` block-aligned), and each shard's
    ``ell_overhead`` is charged at the *global* max row width (shard_map
    forces one shared ELL shape — ``shard_to_ell`` pads every shard to it),
    so the selector judges the layout it would build, not a local optimum.
    """
    out = []
    row_nnz = csr.row_nnz()
    global_width = int(row_nnz.max()) if row_nnz.size else 0
    global_cap = hybrid_width_cap(row_nnz)  # hybrid too shares one shape
    # Every shard is padded to the SAME row count (n_pad ~ max shard rows) and
    # the same width, so each shard's overhead is charged at that uniform
    # shape — a shard with few dense rows still allocates max_rows x width.
    max_rows = int((splits[1:] - splits[:-1]).max()) if len(splits) > 1 else csr.n
    max_rows = max(1, max_rows)
    cols_pg = None
    if with_blocks:
        n_pad_bsr = -(-max_rows // block_size) * block_size
        owner = np.searchsorted(splits, csr.indices, side="right") - 1
        cols_pg = owner * n_pad_bsr + (csr.indices - splits[owner])
    for s in range(len(splits) - 1):
        r0, r1 = int(splits[s]), int(splits[s + 1])
        lo, hi = int(csr.indptr[r0]), int(csr.indptr[r1])
        local_nnz = row_nnz[r0:r1]
        if with_blocks:
            rows = np.repeat(np.arange(r1 - r0, dtype=np.int64), local_nnz)
            cols = cols_pg[lo:hi]
        else:
            rows = cols = None
        out.append(
            _stats_from_triplets(
                local_nnz,
                rows,
                cols,
                max_rows,
                block_size,
                width=global_width,
                hyb_width=global_cap,
            )
        )
    return tuple(out)


def choose_format(
    stats,
    allowed: Sequence[str] = FORMATS,
    *,
    ell_max_overhead: Optional[float] = None,
    bsr_fill_factor: Optional[float] = None,
) -> str:
    """Pick a SpMV format from layout statistics (see module docstring).

    ``stats`` is one :class:`SpmvStats` or a sequence of per-shard stats; with
    several shards the choice must hold for *every* shard (shard_map runs one
    program on all of them), so the worst shard decides.

    ``allowed`` restricts the candidates: the distributed engine passes
    ``("ell", "bsr")`` because its hot loop is kernel-only (COO remains an
    explicit opt-out there), the chunked engine passes ``("coo", "ell")``
    because per-chunk BSR staging is not implemented.
    """
    if isinstance(stats, SpmvStats):
        stats = (stats,)
    ell_max = ell_max_overhead if ell_max_overhead is not None else ell_overhead_bound()
    bsr_factor = (
        bsr_fill_factor
        if bsr_fill_factor is not None
        else _env_float("REPRO_SPMV_BSR_FILL", BSR_FILL_FACTOR)
    )
    tail_max = _env_float("REPRO_SPMV_HYBRID_TAIL", HYBRID_MAX_TAIL)
    bsr_ok = "bsr" in allowed and all(
        s.block_fill >= bsr_factor / s.block_size for s in stats
    )
    if bsr_ok:
        return "bsr"
    ell_ok = "ell" in allowed and all(s.ell_overhead <= ell_max for s in stats)
    if ell_ok:
        return "ell"
    # Hub-row split: the quantile-capped ELL part must respect the same
    # padding bound plain ELL failed (a *memory* bound: per shard), and the
    # spilled tail must stay a minority of the nnz (a *throughput* ratio:
    # judged on the aggregate — nnz-balanced splits concentrate hubs into
    # few-row shards whose local tail share is skewed by construction).
    # Otherwise segment_sum is doing the work anyway and plain COO is the
    # honest choice.
    tail_frac = sum(s.hyb_tail_nnz for s in stats) / max(1, sum(s.nnz for s in stats))
    hyb_ok = (
        "hybrid" in allowed
        and tail_frac <= tail_max
        and all(s.hyb_overhead <= ell_max for s in stats)
    )
    if hyb_ok:
        return "hybrid"
    if "coo" in allowed:
        return "coo"
    for fmt in ("hybrid", "ell"):
        if fmt not in allowed:
            continue
        # Kernel-only paths (distributed): ELL/hybrid are always *correct*;
        # the bounds above only optimize padding, so fall back rather than
        # fail — but loudly: padded ELL costs O(n * max_row_nnz) memory,
        # which on hub-dominated (power-law) matrices can dwarf the O(nnz)
        # COO path (the hybrid split bounds that, hence it is preferred).
        worst = max(
            (s.hyb_overhead if fmt == "hybrid" else s.ell_overhead) for s in stats
        )
        warnings.warn(
            f"SpMV auto-selection is restricted to kernel formats here and "
            f"fell back to {fmt.upper()} despite a {worst:.0f}x padding "
            f"overhead (bound: {ell_max:.1f}x); for hub-dominated matrices "
            f"consider format='coo' (segment-sum reference path) or a larger "
            f"REPRO_SPMV_ELL_OVERHEAD",
            stacklevel=2,
        )
        return fmt
    raise ValueError(f"no admissible SpMV format among {tuple(allowed)}")


def _default_interpret() -> bool:
    from .ops import default_interpret  # lazy: keeps package init order simple

    return default_interpret()


@dataclasses.dataclass(frozen=True)
class SpmvEngine:
    """One SpMV execution configuration: format + accum dtype + tiles.

    Frozen and hashable so it can ride through ``jax.jit`` static arguments.
    ``interpret`` selects the Pallas interpreter (CPU containers) vs compiled
    execution (real TPU).  Compiled, the SpMV runs as XLA gathers over the
    same layouts (still ELL/BSR, never ``segment_sum``) — see
    :func:`spmv_runs_pallas`.
    """

    format: str = "auto"
    accum_dtype: Any = jnp.float32
    tiles: TileConfig = TileConfig()
    interpret: bool = True
    requested: str = "auto"
    stats: Optional[Tuple[SpmvStats, ...]] = None
    tiles_from: str = "table"  # "table" | "tuned" | "override"
    # Whole-iteration decision (update fusion mode + jointly-picked tiles);
    # None on hand-built engines — consumers treat that as the static table.
    iteration_plan: Optional[IterationPlan] = None

    def __post_init__(self):
        if self.format not in FORMATS:
            raise ValueError(f"unknown SpMV format {self.format!r}; expected {FORMATS}")

    # --- raw-array kernel dispatch (used inside shard_map / jit) -----------

    def _use_kernel(self) -> bool:
        return spmv_runs_pallas(self.interpret)

    def ell_matvec(self, val: jax.Array, col: jax.Array, x: jax.Array) -> jax.Array:
        """y = ELL(val, col) @ x -> (rows_padded,) in the accum dtype."""
        acc = jnp.dtype(self.accum_dtype)
        if not self._use_kernel():
            from .ref import spmv_ell_ref

            return spmv_ell_ref(val, col, x, accum_dtype=acc)
        from .spmv_ell import spmv_ell_kernel_call

        # Largest tiles <= the configured ones that divide the padded ELL
        # shape, so the kernel grid always divides evenly (per-chunk layouts
        # pad rows to their own small tile rather than the global block_r —
        # see ChunkedOperator — hence the row adaptation too).
        block_r = _fit_tile(self.tiles.block_r, val.shape[0])
        block_w = _fit_tile(self.tiles.block_w, val.shape[1])
        return spmv_ell_kernel_call(
            val,
            col,
            x,
            block_r=block_r,
            block_w=block_w,
            accum_dtype=acc,
            interpret=self.interpret,
        )

    def packed_ell_matvec(
        self,
        val: jax.Array,
        scale: jax.Array,
        base: jax.Array,
        dcol: jax.Array,
        x: jax.Array,
    ) -> jax.Array:
        """y = dequant(val, scale) @ x over delta-encoded columns (compressed
        out-of-core staging; see ``kernels/spmv_ell_packed.py``).  Returns
        (rows_padded,) in the accum dtype."""
        acc = jnp.dtype(self.accum_dtype)
        if not self._use_kernel():
            vals = val.astype(acc) * scale.astype(acc)
            cols = base + jnp.cumsum(dcol.astype(jnp.int32), axis=1)
            return jnp.sum(vals * jnp.take(x, cols).astype(acc), axis=1)
        from .spmv_ell_packed import spmv_ell_packed_kernel_call

        # Row tile adapts to the per-chunk padded row count (same contract
        # as ell_matvec); the width is one tile — the in-kernel delta cumsum
        # needs the whole row.
        block_r = _fit_tile(self.tiles.block_r, val.shape[0])
        return spmv_ell_packed_kernel_call(
            val,
            scale,
            base,
            dcol,
            x,
            block_r=block_r,
            accum_dtype=acc,
            interpret=self.interpret,
        )

    def bsr_matvec(self, val: jax.Array, bcol: jax.Array, x: jax.Array) -> jax.Array:
        """y = BSR(val, bcol) @ x -> (nbr * BS,) in the accum dtype."""
        acc = jnp.dtype(self.accum_dtype)
        nbr, slots, bs, _ = val.shape
        if x.shape[0] % bs:
            x = jnp.pad(x, (0, bs - x.shape[0] % bs))
        if not self._use_kernel():
            # Same einsum as DeviceBSR.matvec, without the [:n_rows] slice
            # (callers hold the logical row count).
            gathered = jnp.take(x.reshape(-1, bs), bcol, axis=0)  # (nbr, slots, bs)
            y = jnp.einsum("rsij,rsj->ri", val.astype(acc), gathered.astype(acc))
            return y.reshape(nbr * bs)
        from .spmv_bsr import spmv_bsr_kernel_call

        return spmv_bsr_kernel_call(
            val, bcol, x, accum_dtype=acc, interpret=self.interpret
        )

    def hybrid_matvec(
        self,
        val: jax.Array,
        col: jax.Array,
        tail_row: jax.Array,
        tail_col: jax.Array,
        tail_val: jax.Array,
        x: jax.Array,
        n_rows: int,
    ) -> jax.Array:
        """Hub-split SpMV: capped-width ELL kernel + COO tail (``row_sums``).

        ``tail_row`` indexes the output rows; padding slots (val 0, row 0)
        contribute nothing.  Returns (n_rows,) in the accum dtype.
        """
        from ..sparse.formats import row_sums

        acc = jnp.dtype(self.accum_dtype)
        y = self.ell_matvec(val, col, x)[:n_rows]
        prod = tail_val.astype(acc) * jnp.take(x, tail_col).astype(acc)
        return y + row_sums(prod, tail_row, n_rows)

    # --- container-level dispatch (single-device operators) ----------------

    def spmv(self, mat, x: jax.Array, accum_dtype=None) -> jax.Array:
        """SpMV on a device container (DeviceCOO/ELL/BSR/Hybrid).

        One compiled program, the container passed as an argument: run op by
        op from a host loop (the restarted engine), the SpMV would materialise
        every intermediate — ~3 GB of f64 temporaries per call on WK.
        """
        return _container_spmv(self, mat, x, jnp.dtype(accum_dtype or self.accum_dtype))

    def describe(self) -> dict:
        """Loggable summary (what ``EigenResult.partition`` records)."""
        return {
            "format": self.format,
            "requested": self.requested,
            "accum_dtype": str(jnp.dtype(self.accum_dtype)),
            "block_r": self.tiles.block_r,
            "block_w": self.tiles.block_w,
            "block_size": self.tiles.block_size,
            "interpret": self.interpret,
            "tiles_from": self.tiles_from,
            "iteration_plan": (
                self.iteration_plan.as_dict() if self.iteration_plan is not None else None
            ),
        }


@functools.partial(jax.jit, static_argnums=(0, 3))
def _container_spmv(engine: SpmvEngine, mat, x: jax.Array, acc) -> jax.Array:
    from ..sparse.formats import DeviceBSR, DeviceCOO, DeviceELL, DeviceHybrid

    if isinstance(mat, DeviceCOO):
        return mat.matvec(x, accum_dtype=acc)
    eng = engine if acc == engine.accum_dtype else dataclasses.replace(engine, accum_dtype=acc)
    if isinstance(mat, DeviceELL):
        return eng.ell_matvec(mat.val, mat.col, x)[: mat.n_rows]
    if isinstance(mat, DeviceBSR):
        return eng.bsr_matvec(mat.val, mat.bcol, x)[: mat.n_rows]
    if isinstance(mat, DeviceHybrid):
        return eng.hybrid_matvec(
            mat.ell_val, mat.ell_col, mat.tail_row, mat.tail_col, mat.tail_val,
            x, mat.n_rows,
        )
    raise TypeError(f"SpmvEngine.spmv: unsupported container {type(mat).__name__}")


def make_engine(
    csr=None,
    format: str = "auto",
    *,
    stats=None,
    accum_dtype: Any = jnp.float32,
    allowed: Sequence[str] = FORMATS,
    block_size: int = DEFAULT_BLOCK_SIZE,
    interpret: Optional[bool] = None,
    tiles: Optional[TileConfig] = None,
    storage_dtype: Any = None,
    ell_max_overhead: Optional[float] = None,
    bsr_fill_factor: Optional[float] = None,
) -> SpmvEngine:
    """Build a :class:`SpmvEngine` for a matrix (or precomputed shard stats).

    ``format="auto"`` runs :func:`choose_format` on the statistics; an
    explicit format is validated against ``allowed`` and used as-is.
    """
    requested = format
    if stats is None:
        if csr is None:
            raise ValueError("make_engine needs a csr or precomputed stats")
        # The block census (an O(nnz log nnz) sort) only matters when BSR is
        # actually in play; forced COO/ELL solves skip it.
        with_blocks = format == "auto" and "bsr" in allowed
        stats = (matrix_stats(csr, block_size=block_size, with_blocks=with_blocks),)
    elif isinstance(stats, SpmvStats):
        stats = (stats,)
    else:
        stats = tuple(stats)

    if format == "auto":
        fmt = choose_format(
            stats,
            allowed,
            ell_max_overhead=ell_max_overhead,
            bsr_fill_factor=bsr_fill_factor,
        )
    else:
        if format not in FORMATS:
            raise ValueError(f"unknown SpMV format {format!r}; expected {FORMATS} or 'auto'")
        if format not in allowed:
            raise ValueError(
                f"format={format!r} is not supported by this backend (allowed: {tuple(allowed)})"
            )
        fmt = format

    interp = _default_interpret() if interpret is None else interpret
    tiles_from = "override"
    n_rows = max(s.n_rows for s in stats)
    # Tiles (and autotune probes) must see the width the built layout
    # will actually have, not the raw row statistic: hybrid runs the ELL
    # kernel at the capped width (8-slot aligned, to_device_hybrid),
    # plain ELL pads to the 128-lane tile (to_device_ell/shard_to_ell).
    if fmt == "hybrid":
        width = -(-max(1, max(s.hyb_width for s in stats)) // 8) * 8
    elif fmt == "ell":
        width = -(-max(1, max(s.max_row_nnz for s in stats)) // 128) * 128
    else:
        width = max(s.max_row_nnz for s in stats)
    explicit_tiles = tiles is not None
    if tiles is None:
        # The storage dtype governs the TPU sublane minimum of the value tiles.
        tiles, tiles_from = tuned_tiles(
            n_rows,
            width,
            dtype=storage_dtype or accum_dtype,
            format=fmt,
            block_size=block_size,
            interpret=interp,
        )
    # Whole-iteration plan: fused-vs-unfused update (x tiles x BSR block
    # edge) measured on a composite Lanczos step when tuning is on.  f64
    # accumulation runs the jnp reference kernels, where no fusion applies.
    if jnp.dtype(accum_dtype) == jnp.dtype(jnp.float64):
        plan = IterationPlan(update="unfused", tiles=tiles, source="table")
    else:
        plan = resolve_iteration_plan(
            n_rows,
            width,
            dtype=storage_dtype or accum_dtype,
            format=fmt,
            tiles=tiles,
            interpret=interp,
            # A user-pinned TileConfig is a layout commitment the probe must
            # not second-guess (the layout may already be converted to it).
            tile_variants=not explicit_tiles and tiles_from != "override",
        )
        if plan.source == "tuned" and not explicit_tiles and tiles_from != "override":
            # The iteration probe picks update mode and tiles jointly; adopt
            # its tiles (incl. the BSR block edge — a re-conversion) so the
            # layout is built for the measured winner.
            tiles, tiles_from = plan.tiles, "tuned"
    return SpmvEngine(
        format=fmt,
        accum_dtype=accum_dtype,
        tiles=tiles,
        interpret=interp,
        requested=requested,
        stats=stats,
        tiles_from=tiles_from,
        iteration_plan=plan,
    )
