"""``spmv_roofline``: the SpMV's share of its roofline, in percent: the
least time one SpMV of this matrix can take on the chip (its bytes, counted
from the matrix and the policy by ``bench.roofline.spmv_bytes``, over peak
HBM bandwidth: it is bandwidth-bound) over the device time one SpMV took
(``spmv_ms``)."""

from .. import roofline
from . import spmv_ms


def read(outcome, peaks):
    s = spmv_ms.per_call_seconds(outcome)
    if not s:
        return None
    policy = outcome.cell.traffic["request"]["policy"]
    return 100.0 * roofline.spmv_least_seconds(outcome.nnz, outcome.n, policy, peaks) / s
