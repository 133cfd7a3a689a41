import pytest

from benchmark import flops


def test_lu_counts():
    assert flops.lu_flops(3) == pytest.approx(18.0)
    assert flops.lu_solve_flops(4, 2) == 64.0
    assert flops.dense_step_flops(32768) == pytest.approx(
        2 / 3 * 32768 ** 3 + 2 * 32768 ** 2)


def test_trailing_update_by_hand():
    # n=4, v=1: steps k=0..2 update 3x3, 2x2, 1x1 blocks with rank 1
    f, b = flops.trailing_update(4, 1)
    assert f == 2 * (9 + 4 + 1)
    assert b == 4 * ((2 * 9 + 2 * 3) + (2 * 4 + 2 * 2) + (2 * 1 + 2 * 1))
    # n=4, v=2: one update of a 2x2 block with rank 2
    assert flops.trailing_update(4, 2) == (2 * 4 * 2, 4 * (2 * 4 + 2 * 4))
    # summed over the steps, the update is ~2/3 n^3
    f, _ = flops.trailing_update(32768, 1024)
    assert f == pytest.approx(2 / 3 * 32768 ** 3, rel=0.05)


def test_roofline_share():
    kind = "TPU v5 lite"
    # 819 GB in one second at 819 GB/s is the whole roofline
    assert flops.roofline_share(0.0, 819e9, 1.0, kind) == pytest.approx(100)
    assert flops.roofline_share(197e12, 0.0, 2.0, kind) == pytest.approx(50)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        flops.peaks("cpu")
