"""Layer fields (field kernels), packed Shamir under ChaCha masks: the
time the chip's HBM needs for the bytes a round must move
(costs/packed_chacha.py::round, from shapes) over the compute seconds of
the round. The HBM bound only, so a lower bound of the round's roofline
share: the round is int32 work on the vector unit (the cipher, the
kernel's draws), for which peaks.json has no peak."""

import statistics

import costs
from costs import packed_chacha


def read(window):
    facts = window.facts
    if window.trace is None or facts.get("cost_model") != "packed_chacha_round":
        return None
    moved = packed_chacha.round(
        facts["participants"], facts["dim"], facts["input_itemsize"],
        facts["secret_count"], facts["share_count"], window.chips)
    busy = statistics.median(window.trace.compute_per_round())
    floor_s = moved["hbm_bytes"] / costs.peaks(window.device_kind)["hbm_bytes_per_s"]
    return floor_s / busy if busy else None
