"""Layer fields (field kernels), packed Shamir under ChaCha masks: the
mask-free kernel's share of its roofline, in percent: costs.floor_seconds
of costs/packed_chacha.py::kernel over the kernel's seconds (the ops
named ``sda.mask_share*``, median over the traced rounds). With no
published int32 peak for the v5e the floor is the HBM bound alone, so this
is a lower bound of the share: the kernel is bound by its PRNG draws on
the vector unit."""

import statistics

import costs
from costs import packed_chacha

KERNEL = "sda.mask_share"


def read(window):
    facts = window.facts
    if window.trace is None or facts.get("cost_model") != "packed_chacha_round":
        return None
    seconds = statistics.median(
        window.trace.per_round(lambda name: name.startswith(KERNEL)))
    if not seconds:
        return None
    cost = packed_chacha.kernel(
        facts["participants"], facts["dim"], facts["secret_count"],
        facts["share_count"], window.chips)
    return 100.0 * costs.floor_seconds(cost, window.device_kind) / seconds
