import pytest

from bench import counts, peaks


@pytest.mark.parametrize("m,n,flops,nbytes", [
    (4, 3, 4 * 3 * 4, 4 * (12 + 9)),
    (9506, 9506, 9506 * 9506 * 9507, 4 * 2 * 9506 ** 2),
])
def test_gram_counts(m, n, flops, nbytes):
    assert counts.gram_flops(m, n) == flops
    assert counts.gram_bytes(m, n) == nbytes


def test_roofline_picks_the_larger_bound():
    kind = "TPU v5 lite"
    # compute-bound: the Gram of a 9506 square matrix
    t, bound = counts.roofline_s(counts.gram_flops(9506, 9506),
                                 counts.gram_bytes(9506, 9506), kind)
    assert bound == "compute"
    assert t == pytest.approx(9506 * 9506 * 9507 / 197e12)
    # memory-bound: one pass over 1 GB with few operations
    t, bound = counts.roofline_s(1e9, 1e9, kind)
    assert bound == "memory" and t == pytest.approx(1e9 / 819e9)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="no peak rates"):
        peaks.peak("cpu")
