"""Layer fields (field kernels): the time the chip's HBM needs for the bytes a
round must move (costs.pod_round, from shapes) over the compute
seconds of the round. The HBM bound only: the model says the round is
bound by int32 VPU work, for which no peak is published."""

import statistics

import costs


def read(window):
    facts = window.facts
    if window.trace is None or facts.get("cost_model") != "pod_round":
        return None
    moved = costs.pod_round(
        facts["participants"], facts["dim"], facts["input_itemsize"],
        facts["secret_count"], facts["share_count"], window.chips)
    busy = statistics.median(window.trace.compute_per_round())
    floor_s = moved["hbm_bytes"] / costs.peaks(window.device_kind)["hbm_bytes_per_s"]
    return floor_s / busy if busy else None
