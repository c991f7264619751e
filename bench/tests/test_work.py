"""Work counts against hand-computed values, and the peaks table."""
import pytest

from harness import peaks, work


def test_sweep_counts_by_hand():
    w = work.sweep(n=1000, M=100, d=10, p=1)
    assert w.flops == 2 * 1000 * 100 * 10 + 4 * 1000 * 100          # 2.4e6
    assert w.bytes == 4 * (1000 * 10 + 100 * 10 + 1000 + 2 * 100)  # 48800


def test_fit_counts_by_hand():
    flops = work.fit_flops(n=1000, M=100, d=10, t=3)
    sweeps = 4 * 2.4e6
    gram = 2 * 100 * 100 * 10
    factor = 2 * 100 ** 3 / 3 + 2 * 100 ** 3 / 3        # two chol + T T^T
    solves = (6 * 3 + 4) * 100 * 100
    assert flops == pytest.approx(sweeps + gram + factor + solves, rel=1e-12)


def test_least_time_picks_the_binding_resource():
    pk = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    assert work.Work(2e12, 1e9).least_seconds(pk) == (2.0, "mxu")
    assert work.Work(1e12, 3e9).least_seconds(pk) == (3.0, "hbm")


@pytest.mark.parametrize("n,M,d", [(1_000_000, 10_000, 18), (463_715, 20_000, 90)])
def test_cell_sweeps_are_mxu_bound_by_the_count(n, M, d):
    least, bound = work.sweep(n, M, d).least_seconds(peaks.peaks_for("TPU v5 lite"))
    assert bound == "mxu"
    assert least == pytest.approx((2 * n * M * d + 4 * n * M) / 197e12)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")
