"""Layer fields (field kernels), additive sharing under ChaCha masks: the
time the chip's HBM needs for the bytes a round must move
(costs.additive_chacha.round, from shapes) over the compute seconds of
the round. The HBM bound only, so a lower bound of the round's roofline
share: the round is int32 VPU work (the cipher, the draws), for which
peaks.json has no peak. It stands where a kernel's roofline share would:
the XLA step has no kernel of its own."""

import statistics

import costs
from costs import additive_chacha


def read(window):
    facts = window.facts
    if window.trace is None or facts.get("cost_model") != "additive_chacha_round":
        return None
    moved = additive_chacha.round(
        facts["participants"], facts["dim"], facts["input_itemsize"],
        facts["share_count"], window.chips)
    busy = statistics.median(window.trace.compute_per_round())
    floor_s = moved["hbm_bytes"] / costs.peaks(window.device_kind)["hbm_bytes_per_s"]
    return floor_s / busy if busy else None
