"""Layer fields (field kernels): seconds in which a compute op (anything but
a collective) ran on the device inside a round's host span; median
over the traced rounds, averaged over the chips."""

import statistics


def read(window):
    if window.trace is None:
        return None
    return statistics.median(window.trace.compute_per_round())
