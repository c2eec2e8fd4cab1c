"""Layer fields (field kernels): the fused Pallas kernel's share of its
roofline, in percent. Kernel seconds are the ``sda.mask_share`` ops of the trace
(the kernel's ``named_scope``), median over the traced rounds; the
floor is costs.floor_seconds of costs.fused_mask_share. With no
published int32 peak for the v5e the floor is the HBM bound alone, so
this is a lower bound of the share: the model says the kernel is bound
by VPU work."""

import statistics

import costs

KERNEL = "sda.mask_share"


def read(window):
    facts = window.facts
    if window.trace is None or facts.get("cost_model") != "pod_round":
        return None
    seconds = statistics.median(
        window.trace.per_round(lambda name: name.startswith(KERNEL)))
    if not seconds:
        return None
    cost = costs.fused_mask_share(
        facts["participants"], facts["dim"], facts["secret_count"],
        facts["share_count"], window.chips)
    return 100.0 * costs.floor_seconds(cost, window.device_kind) / seconds
