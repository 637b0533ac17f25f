"""The yardstick of the roofline shares: the chip's peaks, and the bytes a
kernel must move, counted from the matrix and the precision policy.

The count never looks at the layout the program builds: padding, a COO
tail or a second pass over the data are the program's cost, not part of
the least time, so a change of format moves the measured time and never
the yardstick.  An SpMV ``y = A x`` must read every stored value and its
int32 column index, gather one entry of ``x`` per stored value and write
``y`` once: ``nnz * (value + 4 + x) + n * out`` bytes.  It does two
operations per stored value, so at any of these widths it is bound by
memory bandwidth, not compute.
"""

from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")

# Bytes per element of the letters in a policy name ("FFF": storage,
# compute, output); "C" is compensated float32.
WIDTH = {"B": 2, "H": 2, "F": 4, "C": 4, "D": 8}
INDEX_BYTES = 4


class UnknownDevice(KeyError):
    """The device kind has no entry in the table of peaks."""


def load_peaks(path: str = PEAKS_FILE) -> dict:
    with open(path) as f:
        return json.load(f)


def device_peaks(kind: str, peaks: dict | None = None) -> dict:
    """The peaks of ``kind`` (``jax.Device.device_kind``); an unknown kind is
    an error, never a default."""
    table = (peaks or load_peaks())["devices"]
    if kind not in table:
        raise UnknownDevice(f"no peaks for device kind {kind!r}; known: {sorted(table)}")
    return table[kind]


def policy_widths(policy: str) -> dict:
    """Storage and compute widths in bytes of a policy name such as "FDF"."""
    name = policy.strip().upper()
    return {"storage": WIDTH[name[0]], "compute": WIDTH[name[1]]}


def spmv_bytes(nnz: int, n: int, policy: str) -> int:
    """Bytes one SpMV must move: values, column indices and gathered ``x``
    in the storage width, the output in the compute width."""
    w = policy_widths(policy)
    return int(nnz) * (w["storage"] + INDEX_BYTES + w["storage"]) + int(n) * w["compute"]


def spmv_least_seconds(nnz: int, n: int, policy: str, peaks: dict) -> float:
    """The SpMV's least time on a chip with ``peaks``: bytes over HBM bandwidth."""
    return spmv_bytes(nnz, n, policy) / float(peaks["hbm_bytes_per_s"])
