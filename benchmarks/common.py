"""Shared benchmark utilities."""

import json
import os
import time

import jax

ARTIFACTS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "artifacts")


def ensure_x64():
    jax.config.update("jax_enable_x64", True)


def timeit(fn, repeats=3, warmup=1):
    """Best-of-N wall time; in capture (bench-smoke gate) mode, a median-of-9
    instead — on shared CI runners the minimum is dominated by lucky
    scheduling windows while the median is stable enough for a 2x gate."""
    if _CAPTURE is not None:
        repeats, warmup = max(repeats, 9), max(warmup, 2)
    for _ in range(warmup):
        fn()
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2] if _CAPTURE is not None else ts[0]


# When capture is enabled (benchmarks.run --smoke), every emit() lands here as
# name -> us_per_call so the run can be written to a comparable JSON artifact.
# emit_plan() records routing decisions (update mode, auto-format picks)
# alongside: compare.py's --pair gates use them to tell "the fused path lost
# AND we shipped it" apart from "the fused path lost and the plan routed
# around it".
_CAPTURE = None
_PLANS = None


def start_capture():
    global _CAPTURE, _PLANS
    _CAPTURE = {}
    _PLANS = {}


def captured_metrics() -> dict:
    return dict(_CAPTURE or {})


def captured_plans() -> dict:
    return dict(_PLANS or {})


def emit(name: str, us_per_call: float, derived: str = ""):
    if _CAPTURE is not None:
        _CAPTURE[name] = float(us_per_call)
    print(f"{name},{us_per_call:.1f},{derived}")


def emit_plan(name: str, selected: str, detail: str = ""):
    """Record which leaf a measured decision chose under metric prefix
    ``name`` (e.g. ``engine/lanczos_step`` -> ``unfused``)."""
    if _PLANS is not None:
        _PLANS[name] = {"selected": str(selected), "detail": detail}
    print(f"plan,{name},{selected},{detail}")


def calibration_us(repeats: int = 11) -> float:
    """Machine-speed probe: median time of a large memory-bound dot product.
    Comparing metric / calibration ratios makes the bench-smoke gate robust
    to CI runners of different absolute speed.  (A dense *matmul* is NOT a
    good probe here: BLAS threading makes it bimodal on small containers.)"""
    import numpy as np

    rng = np.random.default_rng(0)
    a = rng.standard_normal(1 << 22).astype(np.float32)
    b = rng.standard_normal(1 << 22).astype(np.float32)
    ts = []
    for _ in range(2):
        float(np.dot(a, b))  # warmup
    for _ in range(repeats):
        t0 = time.perf_counter()
        float(np.dot(a, b))
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2] * 1e6


def save_artifact(name: str, obj):
    os.makedirs(ARTIFACTS, exist_ok=True)
    with open(os.path.join(ARTIFACTS, name), "w") as f:
        json.dump(obj, f, indent=1, default=str)
