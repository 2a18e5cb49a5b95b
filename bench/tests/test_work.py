import pytest

from bench import work


def test_gain_scoring_matches_hand_count():
    # B=1, k=1, l=2, one ladder size, one step:
    # column rates 3*1*2 + 2 = 8; closed forms 11*1*1*2 = 22;
    # gain elements 2*1*1*2*2 = 8; selection 3*1*2*2 + 4*1 = 16
    ops, nbytes = work.gain_scoring(B=1, k=1, l=2, n_sizes=1, steps=1)
    assert ops == 8 + 22 + 8 + 16
    assert nbytes == 2 * 1 * 2 * 4 + 3 * 4


def test_gain_scoring_scales_with_batch_and_steps():
    one = work.gain_scoring(B=1, k=10, l=7, n_sizes=14, steps=1)
    many = work.gain_scoring(B=1216, k=10, l=7, n_sizes=14, steps=37)
    assert many[0] == pytest.approx(one[0] * 1216 * 37)
    assert many[1] == pytest.approx(one[1] * 1216 * 37)


def test_least_seconds_names_the_binding_bound():
    peaks = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    assert work.least_seconds(2e12, 1e9, peaks) == (2.0, "flops")
    assert work.least_seconds(1e12, 3e9, peaks) == (3.0, "hbm")
