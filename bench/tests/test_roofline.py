"""The roofline's yardstick: peaks by device kind, bytes from the matrix."""

import pytest

from bench import roofline
from bench.gen import generate


def test_unknown_device_kind_is_an_error():
    with pytest.raises(roofline.UnknownDevice):
        roofline.device_peaks("TPU v99 imaginary")
    with pytest.raises(roofline.UnknownDevice):
        roofline.device_peaks("cpu")


def test_v5e_peaks_from_the_table():
    p = roofline.device_peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12
    assert "TPU v5e" in roofline.load_peaks()["source"]


def test_spmv_bytes_by_policy():
    nnz, n = 31_406_324, 1 << 20
    assert roofline.spmv_bytes(nnz, n, "FFF") == nnz * 12 + n * 4
    assert roofline.spmv_bytes(nnz, n, "FDF") == nnz * 12 + n * 8
    assert roofline.spmv_bytes(nnz, n, "BFF") == nnz * 8 + n * 4
    least = roofline.spmv_least_seconds(nnz, n, "FFF", roofline.device_peaks("TPU v5 lite"))
    assert least == pytest.approx((nnz * 12 + n * 4) / 819e9)


def test_count_does_not_change_with_the_format():
    """The program's layouts differ in bytes; the yardstick does not."""
    from repro.kernels.engine import matrix_stats
    from repro.sparse import CSR

    g = generate({"generator": "kron", "scale": 11, "edge_factor": 16, "graph_seed": 1}, 3)
    stats = matrix_stats(CSR(g.indptr, g.indices, g.data, (g.n, g.n)))
    layouts = {fmt: stats.layout_bytes(fmt, 4) for fmt in ("coo", "ell", "hybrid", "bsr")}
    assert len(set(layouts.values())) == len(layouts)
    counts = {fmt: roofline.spmv_bytes(g.nnz, g.n, "FFF") for fmt in layouts}
    assert len(set(counts.values())) == 1
