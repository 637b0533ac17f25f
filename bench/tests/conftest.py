"""Tests of the benchmark itself, on the CPU: ``python -m pytest bench/tests``.

The repository's own suite (``tests/``) does not collect them.  x64 is on,
as in a run of the benchmark.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)
