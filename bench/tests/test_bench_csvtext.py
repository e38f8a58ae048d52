"""The column-wise CSV text writers: each cell reads back as its value."""

import numpy as np
import pytest

from bench import csvtext as ct


def cells(columns):
    return [line.split(",") for line in ct.join(columns).decode().split("\n")
            if line]


@pytest.mark.parametrize("decimals", [1, 2, 14])
def test_decimal_reads_back(decimals):
    x = np.array([0.0, 1.5, -74.0144, 40.7, -0.25, 123.456789])
    got = [float(r[0]) for r in cells([ct.decimal(x, 3, decimals)])]
    assert got == [round(v, decimals) for v in x.tolist()]


def test_fixed_point_and_integer():
    c = np.array([0, 5, 250, 5200, 17005, -30])
    rows = cells([ct.fixed_point(c, 3, 2), ct.integer(np.abs(c), 5)])
    assert [r[0] for r in rows] == ["0.00", "0.05", "2.50", "52.00",
                                    "170.05", "-0.30"]
    assert [int(r[1]) for r in rows] == np.abs(c).tolist()
    assert [r[1] for r in rows][:2] == ["0", "5"]  # no leading zeros


def test_digits_refuse_what_does_not_fit():
    with pytest.raises(ValueError):
        ct.digits(np.array([100]), 2)
    with pytest.raises(ValueError):
        ct.digits(np.array([-1]), 2)


def test_timestamps_cross_days_and_years():
    s = np.array([0, 86399, 86400 + 3661, 365 * 86400])
    rows = cells([ct.timestamps(s, "2015-01-01"), ct.choice([0, 1, 0, 1],
                                                             ("N", "Y"))])
    assert [r[0] for r in rows] == ["2015-01-01 00:00:00",
                                    "2015-01-01 23:59:59",
                                    "2015-01-02 01:01:01",
                                    "2016-01-01 00:00:00"]
    assert [r[1] for r in rows] == ["N", "Y", "N", "Y"]


def test_seeds_of_any_size():
    for seed in (-5, 0, 2**31 + 7, 2**70):
        a = ct.rng_for(seed).integers(0, 10**9, 4)
        assert (a == ct.rng_for(seed).integers(0, 10**9, 4)).all()
