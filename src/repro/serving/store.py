"""Cross-process session persistence: the serving warm-start store.

A served matrix's expensive state — converted device layouts and
per-policy plan configuration — is a pure function of (matrix bytes,
layout config, repro version).  ``SessionStore`` persists exactly that
state, keyed by matrix fingerprint + layout fingerprint, so a restarted
server warms instantly: reloading rebuilds the device containers from the
saved arrays with the *plain constructors* (``conversion_count()`` does not
move) and injects the saved layout padding.

Layout on disk (one directory per (matrix, layout) pair)::

    <root>/<matrix_fp>-<layout_fp>/
        header.json   # schema, repro version, fingerprints, n, plan configs
        plans.npz     # the device-container arrays, one prefix per plan

Staleness is rejected, never trusted: the header carries the repro version
and the layout-config fingerprint, and :meth:`EigenSession.import_plans`
refuses any mismatch with a warning — the session then cold-rebuilds
lazily, identical to having no store at all.  A corrupt payload likewise
warns and is treated as absent.
"""

from __future__ import annotations

import hashlib
import json
import os
import tempfile
from pathlib import Path
from typing import Optional

import numpy as np

from ..configs import env as envcfg

__all__ = [
    "SessionStore",
    "SolveCheckpoint",
    "default_store_root",
    "default_checkpoint_root",
]

_HEADER = "header.json"
_PLANS = "plans.npz"
_CKPT_SCHEMA = 1


# The per-user cache directory the default store and checkpoint roots sit in.
_CACHE_DIR = os.path.join(os.path.expanduser("~"), ".cache", "repro")


def default_store_root() -> str:
    """Default store location: ``REPRO_SERVING_STORE`` if set, else
    ``~/.cache/repro/serving_store``."""
    env = envcfg.get_str("REPRO_SERVING_STORE")
    if env:
        return env
    return os.path.join(_CACHE_DIR, "serving_store")


class SessionStore:
    """Fingerprint-keyed persistent store of exported session plans."""

    def __init__(self, root: Optional[str] = None):
        self.root = Path(root if root is not None else default_store_root())
        self.root.mkdir(parents=True, exist_ok=True)

    # ------------------------------------------------------------- layout

    def _key(self, matrix_fp: str, layout_fp: str) -> str:
        return f"{matrix_fp}-{layout_fp}"

    def path_for(self, session) -> Optional[Path]:
        """Directory this session persists under, or None when the session
        has no matrix fingerprint (matrix-free input: nothing to key by)."""
        from ..api.session import _LAYOUT_FIELDS, config_fingerprint

        matrix_fp = session.ensure_fingerprint()
        if matrix_fp is None:
            return None
        layout_fp = config_fingerprint(session.cfg, _LAYOUT_FIELDS)
        return self.root / self._key(matrix_fp, layout_fp)

    def entries(self) -> list:
        """Persisted (matrix, layout) keys currently on disk."""
        return sorted(p.name for p in self.root.iterdir() if (p / _HEADER).exists())

    # --------------------------------------------------------------- save

    def save(self, session) -> Optional[Path]:
        """Persist the session's built plans; returns the entry path, or
        None when there is nothing persistable (no fingerprint, or no
        exportable plans built yet).  The write is atomic-enough (temp files
        + rename) that a concurrent reader never sees a torn entry."""
        path = self.path_for(session)
        if path is None:
            return None
        state = session.export_state()
        if not state["plans"] and not state.get("matrix_ref"):
            # Nothing persistable.  Disk-backed sessions ARE persistable even
            # with zero exportable plans: their entry is a header-only
            # POINTER (path + sampled fingerprint) to the on-disk matrix —
            # never a copy of an out-of-core payload into plans.npz.
            return None
        arrays = {}
        plan_headers = []
        for i, plan in enumerate(state["plans"]):
            rec = {k: v for k, v in plan.items() if k != "arrays"}
            rec["array_names"] = sorted(plan["arrays"])
            plan_headers.append(rec)
            for name, a in plan["arrays"].items():
                arrays[f"p{i}.{name}"] = np.asarray(a)
        header = {k: v for k, v in state.items() if k != "plans"}
        header["plans"] = plan_headers
        path.mkdir(parents=True, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=path, suffix=".npz.tmp")
        try:
            with os.fdopen(fd, "wb") as f:
                np.savez(f, **arrays)
            os.replace(tmp, path / _PLANS)
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
        fd, tmp = tempfile.mkstemp(dir=path, suffix=".json.tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(header, f, indent=1)
            os.replace(tmp, path / _HEADER)
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
        return path

    # --------------------------------------------------------------- load

    def load_state(self, session) -> Optional[dict]:
        """Read this session's persisted state back into ``export_state``
        form (arrays rehydrated from the npz), or None when absent/corrupt.
        Header validation itself happens in ``import_plans`` — this method
        only reassembles bytes."""
        import warnings

        path = self.path_for(session)
        if path is None or not (path / _HEADER).exists():
            return None
        try:
            with open(path / _HEADER) as f:
                header = json.load(f)
            with np.load(path / _PLANS) as z:
                plans = []
                for i, rec in enumerate(header.get("plans", [])):
                    plan = dict(rec)
                    plan["arrays"] = {
                        name: z[f"p{i}.{name}"] for name in rec.get("array_names", [])
                    }
                    plans.append(plan)
            header["plans"] = plans
            return header
        except Exception as exc:
            warnings.warn(
                f"corrupt serving-store entry {path.name} ignored "
                f"({type(exc).__name__}: {exc}); the session will cold-build",
                stacklevel=2,
            )
            return None

    def load_into(self, session) -> int:
        """Warm a session from its persisted entry: returns plans imported
        (0 when absent, stale, or corrupt — the session cold-builds lazily)."""
        state = self.load_state(session)
        if state is None:
            return 0
        return session.import_plans(state)

    @staticmethod
    def revive_matrix(state: dict):
        """Reopen the on-disk matrix a persisted entry's ``matrix_ref``
        points at, verifying the sampled content fingerprint — a moved,
        rewritten, or deleted mapping warns and reads as absent (None), so a
        revived server never serves plans for bytes that changed under it.
        Returns a :class:`~repro.sparse.diskcsr.DiskCSR` or None."""
        import warnings

        ref = (state or {}).get("matrix_ref")
        if not ref or ref.get("kind") != "diskcsr":
            return None
        from ..sparse.diskcsr import diskcsr_fingerprint, is_diskcsr, open_diskcsr

        path = ref.get("path")
        if not path or not is_diskcsr(path):
            warnings.warn(
                f"persisted matrix_ref points at {path!r}, which is no longer "
                "a diskcsr directory; the entry reads as absent",
                stacklevel=2,
            )
            return None
        if diskcsr_fingerprint(path) != ref.get("fingerprint"):
            warnings.warn(
                f"on-disk matrix at {path!r} changed since this entry was "
                "persisted (sampled fingerprint mismatch); refusing to revive",
                stacklevel=2,
            )
            return None
        return open_diskcsr(path)


def default_checkpoint_root() -> str:
    """Default checkpoint location: ``REPRO_SOLVE_CHECKPOINTS`` if set, else
    ``~/.cache/repro/solve_checkpoints``."""
    env = envcfg.get_str("REPRO_SOLVE_CHECKPOINTS")
    if env:
        return env
    return os.path.join(_CACHE_DIR, "solve_checkpoints")


class SolveCheckpoint:
    """Mid-solve snapshot store — the :class:`SessionStore` sibling for
    *in-flight* state rather than prepared plans.

    The restarted engine saves its full restart state (basis block,
    projected matrix, arrow border, next start vector, counters) after
    every completed compression; the chunked engine's host Lanczos loop
    saves its carry every N steps.  A killed run re-invoked with the same
    token resumes from the last snapshot **bit-identically**: each saved
    state fully determines the remaining trajectory (per-cycle
    ``beta_prev`` resets to 0, so no unsaved recurrence state leaks across
    the snapshot boundary), and arrays round-trip exactly (bf16 is widened
    to f32 — lossless — for npz, and narrowed back on load).

    Layout on disk (one directory per solve token)::

        <root>/<token>/
            header.json   # schema + scalar state (engine, cycle/step, dims)
            state.npz     # the array state

    Writes are atomic (temp file + ``os.replace``) so a crash mid-save
    leaves the previous snapshot intact, never a torn one.  Completed
    solves ``clear`` their entry so a finished token cannot resurrect.
    """

    _STATE = "state.npz"

    def __init__(self, root: Optional[str] = None):
        self.root = Path(root if root is not None else default_checkpoint_root())
        self.root.mkdir(parents=True, exist_ok=True)

    @staticmethod
    def token(matrix_fp: Optional[str], **params) -> str:
        """Deterministic solve identity: matrix fingerprint + the solve
        parameters that shape the trajectory (backend, policy, k, m, seed,
        tol, reorth — NOT budget knobs like max_restarts, which only decide
        where the trajectory stops)."""
        h = hashlib.blake2b(digest_size=12)
        h.update((matrix_fp or "anon").encode())
        for key in sorted(params):
            h.update(f"|{key}={params[key]!r}".encode())
        return h.hexdigest()

    def path_for(self, token: str) -> Path:
        return self.root / token

    def entries(self) -> list:
        return sorted(p.name for p in self.root.iterdir() if (p / _HEADER).exists())

    def save(self, token: str, state: dict) -> Path:
        """Persist one snapshot: ndarray/jax-array values go to the npz
        (bf16 widened to f32, original dtype recorded), everything else to
        the JSON header."""
        path = self.path_for(token)
        path.mkdir(parents=True, exist_ok=True)
        arrays = {}
        dtypes = {}
        header = {"schema": _CKPT_SCHEMA}
        for key, val in state.items():
            if hasattr(val, "ndim") or isinstance(val, np.ndarray):
                arr = np.asarray(val)
                dtypes[key] = str(arr.dtype)
                if arr.dtype.name == "bfloat16":
                    arr = arr.astype(np.float32)  # exact widening
                arrays[key] = arr
            else:
                header[key] = val
        header["array_dtypes"] = dtypes
        fd, tmp = tempfile.mkstemp(dir=path, suffix=".npz.tmp")
        try:
            with os.fdopen(fd, "wb") as f:
                np.savez(f, **arrays)
            os.replace(tmp, path / self._STATE)
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
        fd, tmp = tempfile.mkstemp(dir=path, suffix=".json.tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(header, f, indent=1)
            os.replace(tmp, path / _HEADER)
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
        return path

    def load(self, token: str) -> Optional[dict]:
        """The last snapshot for ``token``, or None when absent/corrupt
        (corrupt entries warn and read as absent: the solve starts over,
        identical to having no checkpoint)."""
        import warnings

        path = self.path_for(token)
        if not (path / _HEADER).exists():
            return None
        try:
            with open(path / _HEADER) as f:
                header = json.load(f)
            if header.get("schema") != _CKPT_SCHEMA:
                return None
            dtypes = header.pop("array_dtypes", {})
            state = dict(header)
            with np.load(path / self._STATE) as z:
                for key in z.files:
                    arr = z[key]
                    want = dtypes.get(key)
                    if want == "bfloat16":
                        import ml_dtypes

                        arr = arr.astype(ml_dtypes.bfloat16)  # exact narrowing back
                    state[key] = arr
            return state
        except Exception as exc:
            warnings.warn(
                f"corrupt solve checkpoint {path.name} ignored "
                f"({type(exc).__name__}: {exc}); the solve restarts from zero",
                stacklevel=2,
            )
            return None

    def clear(self, token: str) -> bool:
        """Remove ``token``'s snapshot; True when something was deleted."""
        path = self.path_for(token)
        if not path.exists():
            return False
        for name in (self._STATE, _HEADER):
            try:
                (path / name).unlink()
            except FileNotFoundError:
                pass
        try:
            path.rmdir()
        except OSError:
            pass  # stray tmp files: leave the directory, entry is still gone
        return True
