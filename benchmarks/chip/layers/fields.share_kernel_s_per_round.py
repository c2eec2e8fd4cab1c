"""Layer fields (field kernels), packed Shamir under ChaCha masks: device
seconds per round of the fused Pallas kernel in its mask-free variant
(``fused_mask_share_combine(masked=False)``: ``t`` draws a column and
participant, no mask total) -- the ops named ``sda.mask_share*`` in the
trace, median over the traced rounds. None in an untraced run, under
another cost model (``sda.mask_share_roofline`` reads the masked variant)
and where no such op ran."""

import statistics

KERNEL = "sda.mask_share"


def read(window):
    facts = window.facts
    if window.trace is None or facts.get("cost_model") != "packed_chacha_round":
        return None
    seconds = statistics.median(
        window.trace.per_round(lambda name: name.startswith(KERNEL)))
    return seconds or None
