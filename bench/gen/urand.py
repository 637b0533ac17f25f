"""GAP ``urand``: uniform random endpoints (an Erdos-Renyi-like graph).

``edge_factor`` edges per vertex, each with a source and a destination drawn
uniformly from the ``2**scale`` vertices, as in the suite's
``MakeUniformEL``.  Undirected, every vertex has about ``2 * edge_factor``
neighbours: Poisson-distributed row lengths.
"""

from __future__ import annotations

import numpy as np


def edges(scale: int, edge_factor: int, rng: np.random.Generator):
    n = 1 << scale
    m = n * edge_factor
    return rng.integers(0, n, m, dtype=np.uint32), rng.integers(0, n, m, dtype=np.uint32)
