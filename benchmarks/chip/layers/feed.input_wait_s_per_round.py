"""Layer feed (mesh drivers and the host->HBM feed), in a host-fed cell:
the time the chip waited for its input. Per traced round, the start of the
first device op inside the round's ``mesh.round`` minus the start of its
``pod.feed``: host layout + DMA of the round's matrix, which no device op
shows; median over the traced rounds.

Two clocks meet here: the op's start is the chip's clock (shifted by the
trace's ``profile_start_time``), the span's start the host's epoch clock.
The chip's ran 1.2-1.4 ms ahead of the host's in every trace so far
(PERF.md, Open questions; uncorrected in every trace metric), so this
reads that much short: 0.5 % of the 238 ms of ``packed-1m-hostfed``."""

import statistics


def read(window):
    trace = window.trace
    if trace is None:
        return None
    ops = trace.devices[min(trace.devices)]
    feeds = [start for name, start, _ in trace.annotations if name == "pod.feed"]
    waits = []
    for name, lo, hi in trace.annotations:
        if name != "mesh.round":
            continue
        fed = [start for start in feeds if lo <= start < hi]
        if not fed:
            continue
        first = min((start for _, start, _ in ops if fed[0] <= start < hi),
                    default=None)
        if first is not None:
            waits.append((first - fed[0]) / 1e9)
    return statistics.median(waits) if waits else None
