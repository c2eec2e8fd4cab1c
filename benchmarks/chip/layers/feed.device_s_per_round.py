"""Layer feed (mesh drivers and the host->HBM feed), in a host-fed cell:
seconds in which any op ran on the device inside a round -- the int64 ->
residue pass the feed brings with it, then the same relayout and
kernel as a resident round; median over the traced rounds."""

import statistics


def read(window):
    if window.trace is None:
        return None
    return statistics.median(window.trace.per_round())
