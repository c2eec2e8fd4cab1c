"""costs/additive_chacha.py against numbers worked by hand, and the
reader that divides its floor by a round's device seconds."""

import types

import pytest

import harness
from costs import additive_chacha


def test_the_hbm_floor_counts_each_array_once():
    cost = additive_chacha.round(participants=600, dim=999_999, input_itemsize=4,
                                 share_count=3)
    # input 600 x 999,999 x 4 B; share rows [3, d] and the mask total [d]
    # written and read once as uint32; the aggregate [d] as int64
    assert cost["hbm_bytes"] == (2_399_997_600 + 2 * 4 * 3 * 999_999
                                 + 2 * 4 * 999_999 + 8 * 999_999) == 2_439_997_560
    assert cost["elements"] == 599_999_400
    assert cost["chacha_blocks"] == 600 * 125_000


def test_ops_an_element_follow_the_docstrings_arithmetic():
    # 80 quarter rounds of 20 ops and 16 adds a block of 8 draws
    assert additive_chacha.OPS_PER_ELEMENT["chacha"] == (80 * 20 + 16) // 8 == 202
    cost = additive_chacha.round(8, 16, 4, share_count=3)
    per_element = 4 + 202 + 25 + 8 + 2 * 145 + 3 * 3
    per_column = 3 * 6 + 3
    assert cost["vpu_ops"] == 8 * 16 * per_element + 16 * per_column
    # one more share row costs a draw and a fold an element
    more = additive_chacha.round(8, 16, 4, share_count=4)
    assert more["vpu_ops"] - cost["vpu_ops"] == 8 * 16 * (145 + 3) + 16 * 6


def test_rows_spread_over_chips_and_blocks_round_up():
    cost = additive_chacha.round(8, 12, 4, share_count=3, chips=4)
    assert cost["elements"] == 2 * 12 and cost["chacha_blocks"] == 2 * 2
    assert cost["hbm_bytes"] == 2 * 12 * 4 + 24 * 12 + 8 * 12 + 8 * 12


def fake_window(facts, busy):
    trace = types.SimpleNamespace(compute_per_round=lambda: busy)
    return harness.Window(facts=facts, chips=1, device_kind="TPU v5 lite",
                          setup_s=0.0, trace=trace)


def test_the_floor_share_is_floor_seconds_over_the_median_device_seconds():
    read = harness.load_module(harness.HERE, "layers", "fields.additive_hbm_floor_share").read
    facts = {"participants": 600, "dim": 999_999, "input_itemsize": 4, "share_count": 3,
             "cost_model": "additive_chacha_round"}
    floor_s = 2_439_997_560 / 819e9
    assert read(fake_window(facts, [1.0, 2.0, 4.0])) == pytest.approx(floor_s / 2.0)
    assert read(fake_window({**facts, "cost_model": "pod_round"}, [1.0])) is None
    assert read(fake_window(facts, [0.0])) is None
    untraced = harness.Window(facts=facts, chips=1, device_kind="TPU v5 lite", setup_s=0.0)
    assert read(untraced) is None
