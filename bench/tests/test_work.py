"""bench/work.py's counts against shapes worked by hand."""

import pytest

from bench import work

V5E = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.mark.parametrize("h,v,n,flops,nbytes", [
    # one AUA round: H = 365 days, V = 3 variables, N = 1024 locations;
    # f_hist 365*3*1024 + f_now 3*1024 + d2 365*1024 floats, 4 B each
    (365, 3, 1024, 3 * 3 * 365 * 1024, 4 * (1_121_280 + 3_072 + 373_760)),
    # a whole campaign's locations at once: N = 4096
    (365, 3, 4096, 13_455_360, 4 * (4_485_120 + 12_288 + 1_495_040)),
    # the rehearsal's round: H = 40, N = 128
    (40, 3, 128, 46_080, 4 * (15_360 + 384 + 5_120)),
])
def test_anen_distance_counts(h, v, n, flops, nbytes):
    assert work.anen_distance(h, v, n) == (flops, nbytes)


def test_one_round_is_byte_bound_on_a_v5e():
    flops, nbytes = work.anen_distance(365, 3, 1024)
    assert (flops, nbytes) == (3_363_840, 5_992_448)
    assert work.least_seconds(flops, nbytes, V5E) == pytest.approx(
        5_992_448 / 819e9)


@pytest.mark.parametrize("flops,nbytes,bound", [
    (197e12, 1.0, 1.0),          # a second of compute at peak
    (1.0, 819e9, 1.0),           # a second of bytes at peak bandwidth
    (3_363_840, 5_992_448, 5_992_448 / 819e9),
])
def test_least_seconds_takes_the_larger_bound(flops, nbytes, bound):
    assert work.least_seconds(flops, nbytes, V5E) == pytest.approx(bound)
