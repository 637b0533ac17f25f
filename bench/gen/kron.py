"""GAP ``kron``: the Graph500 Kronecker (R-MAT) generator.

Each edge picks one quadrant of the adjacency matrix per level, ``scale``
levels deep, with probabilities A = 0.57 (top left), B = 0.19 (top right),
C = 0.19 (bottom left) and D = 0.05; the quadrants chosen spell out the
bits of its source and destination.  ``edge_factor`` edges per vertex.  As
in the suite's ``MakeRMatEL``: one uniform draw per level, the source bit is
set when it lands in C or D, the destination bit in B or D.
"""

from __future__ import annotations

import numpy as np

A, B, C = 0.57, 0.19, 0.19


def edges(scale: int, edge_factor: int, rng: np.random.Generator):
    m = (1 << scale) * edge_factor
    src = np.zeros(m, np.uint32)
    dst = np.zeros(m, np.uint32)
    for _ in range(scale):
        u = rng.random(m, dtype=np.float32)
        src <<= 1
        dst <<= 1
        src |= u >= A + B
        dst |= ((u >= A) & (u < A + B)) | (u >= A + B + C)
    return src, dst
