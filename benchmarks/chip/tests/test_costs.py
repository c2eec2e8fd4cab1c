"""costs.pod_round against numbers worked by hand for the flagship shape
of BASELINE.json configs[1]: 100 x 999,999, k=3, n=8."""

import pytest

import costs


def test_flagship_bytes_and_ops_by_hand():
    got = costs.pod_round(100, 999_999, 4, secret_count=3, share_count=8)
    # input: 100 x 999,999 x 4 B = 399,999,600 B
    # share rows [8, 333,333] u32 written + read: 2 x 4 x 8 x 333,333 = 21,333,312 B
    # mask totals [999,999] u32 written + read:    2 x 4 x 999,999  =  7,999,992 B
    # aggregate [999,999] int64 written:               8 x 999,999  =  7,999,992 B
    assert got["hbm_bytes"] == 399_999_600 + 21_333_312 + 7_999_992 + 7_999_992
    # per element 4 + 35 + 8 + 47 + 6 = 100 ops; per summed element 250 + 3
    assert got["vpu_ops"] == 99_999_900 * 100 + 999_999 * 253
    assert got["elements"] == 99_999_900


def test_host_fed_input_doubles_the_input_bytes_and_chips_divide_rows():
    resident = costs.pod_round(100, 999_999, 4, 3, 8)
    host_fed = costs.pod_round(100, 999_999, 8, 3, 8)
    assert host_fed["hbm_bytes"] - resident["hbm_bytes"] == 399_999_600
    four = costs.pod_round(400, 999_999, 4, 3, 8, chips=4)
    assert four == resident


def test_peaks_are_sourced_and_an_unknown_device_is_an_error():
    row = costs.peaks("TPU v5 lite")
    assert row["hbm_bytes_per_s"] == 819e9 and "Google Cloud" in row["hbm_source"]
    assert row["int32_ops_per_s"] is None  # no published VPU peak: none invented
    with pytest.raises(KeyError):
        costs.peaks("TPU v9 imaginary")


def test_fused_kernel_by_hand_and_its_floor():
    got = costs.fused_mask_share(100, 999_999, secret_count=3, share_count=8)
    # reads 100 x 999,999 x 4 B; writes [8, 333,333] and [999,999] u32
    assert got["hbm_bytes"] == 399_999_600 + 10_666_656 + 3_999_996
    # 35 + 8 + 47 + 6 = 96 ops per element, 250 per column of the sum
    assert got["vpu_ops"] == 99_999_900 * 96 + 999_999 * 250
    # no int32 peak in the table: the floor is the HBM bound alone
    assert costs.floor_seconds(got, "TPU v5 lite") == pytest.approx(
        got["hbm_bytes"] / 819e9)
