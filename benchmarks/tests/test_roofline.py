"""roofline.py against hand arithmetic, one shape per kernel."""

import pytest

import roofline

V5E = {"hbm_bytes_per_s": 819e9}


def test_dense_zscan_bytes_and_share():
    # 100M rows x (6 columns x 4 B + 1 mask byte) = 2.5 GB; at 819 GB/s
    # that is 3.05250 ms, so a kernel taking 6.105 ms runs at 50.000%
    assert roofline.zscan_dense_bytes(100_000_000) == 2_500_000_000
    got = roofline.share(2.5e9, 6.105e-3, V5E)
    assert got == pytest.approx(50.0, rel=1e-4)


def test_gathered_zscan_bytes():
    # 18.2M candidates x (4 B index + 24 B columns + 1 B mask) = 527.8 MB
    assert roofline.zscan_gathered_bytes(18_200_000) == 527_800_000


def test_residual_bytes():
    # 100M rows, QuadClass (int64 as hi/lo words + valid) and AvgTone
    # (f64 as hi/lo floats + valid) and EventRootCode (int32 codes):
    # 100M x (9 + 9 + 4 + 1 mask) = 2.3 GB
    got = roofline.residual_bytes(100_000_000, ["Integer", "Double",
                                                "String"])
    assert got == 2_300_000_000


def test_peaks_table():
    p = roofline.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")


def test_no_time_gives_no_share():
    assert roofline.share(1e9, 0.0, V5E) is None
