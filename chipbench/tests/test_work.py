"""The sweep's least work, counted by hand at order 3, rank 4."""
import pytest

import work


def test_matvec_and_rhs_counts_by_hand():
    # per nonzero, order 3, R = 4:
    # matvec reads 3 coordinates + 1 weight, gathers 3 rows of 4 floats and
    # scatters one row of 4: (3 + 1 + 12 + 4) * 4 B = 80 B; flops 2*3*4+1
    assert work.matvec_work(10, 3, 4) == (10 * 80, 10 * 25)
    # rhs MTTKRP: 3 coordinates + 1 value, gathers 2 rows, scatters 1:
    # (3 + 1 + 8 + 4) * 4 B = 64 B; flops 3*4
    assert work.rhs_work(10, 3, 4) == (10 * 64, 10 * 12)


def test_sweep_counts_at_the_cg_bound():
    # 3 modes x (1 rhs + (cg_iters + 1) matvecs); cg_iters = 2
    b, f = work.sweep_work(10, 3, 4, 2)
    assert b == 3 * (640 + 3 * 800)
    assert f == 3 * (120 + 3 * 250)


def test_least_seconds_names_the_binding_peak():
    t, bound = work.least_seconds(819e9, 1.0, "TPU v5 lite")
    assert bound == "hbm" and t == pytest.approx(1.0)
    t, bound = work.least_seconds(1.0, 197e12, "TPU v5 lite")
    assert bound == "flops" and t == pytest.approx(1.0)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("TPU v99")
