"""Graph generators of the GAP Benchmark Suite (Beamer, Asanovic, Patterson,
arXiv:1508.03619), kept with the benchmark so that no change to the program
can change the inputs it is measured on.

A configuration file names its generator module (``"generator": "kron"``)
and its parameters.  Each module defines ``edges(scale, edge_factor, rng)``:
the directed edge list the suite's generator draws, ``n * edge_factor``
edges over ``n = 2**scale`` vertices, before relabelling.  :func:`generate`
then does what the suite does to build an undirected graph: relabel the
vertices by a random permutation, add every edge's reverse, drop self-loops
and duplicate edges, and give every stored entry the weight 1.

The edge list is drawn from the configuration's ``graph_seed`` and the
permutation from the run's seed.  So every seed gives a graph of the same
degree sequence, stored non-zeros and SpMV layout sizes, with its rows,
columns and start vectors in another order: runs of one cell do the same
work on different data.
"""

from __future__ import annotations

import importlib
from typing import NamedTuple

import numpy as np


class Graph(NamedTuple):
    """A symmetric unit-weight adjacency matrix in CSR form (NumPy)."""

    n: int
    indptr: np.ndarray  # (n + 1,) int64
    indices: np.ndarray  # (nnz,) int32, sorted within each row
    data: np.ndarray  # (nnz,) float64, all ones

    @property
    def nnz(self) -> int:
        return int(self.indices.shape[0])


def symmetric_csr(src: np.ndarray, dst: np.ndarray, n: int) -> Graph:
    """Undirected simple graph of a directed edge list: both directions of
    every edge, no self-loops, no duplicates, unit weights."""
    keep = src != dst
    src, dst = src[keep].astype(np.int64), dst[keep].astype(np.int64)
    keys = np.concatenate((src * n + dst, dst * n + src))
    del src, dst, keep
    keys.sort()
    first = np.empty(keys.shape, bool)
    first[:1] = True
    np.not_equal(keys[1:], keys[:-1], out=first[1:])
    keys = keys[first]
    rows = keys // n
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(rows, minlength=n), out=indptr[1:])
    indices = (keys - rows * n).astype(np.int32)
    return Graph(n, indptr, indices, np.ones(indices.shape[0], np.float64))


def generate(config: dict, seed: int, scale: int | None = None) -> Graph:
    """The graph of ``config`` for run seed ``seed`` (``scale`` overrides the
    configuration's, for tests at small sizes)."""
    gen = importlib.import_module(f"{__name__}.{config['generator']}")
    scale = int(config["scale"] if scale is None else scale)
    n = 1 << scale
    rng = np.random.default_rng(config["graph_seed"])
    src, dst = gen.edges(scale, int(config["edge_factor"]), rng)
    perm = np.random.default_rng(int(seed)).permutation(n).astype(np.int64)
    return symmetric_csr(perm[src], perm[dst], n)
