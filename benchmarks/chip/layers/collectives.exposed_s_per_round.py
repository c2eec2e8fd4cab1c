"""Layer collectives: the part of a round's collective seconds during
which no compute ran on that chip; median over the traced rounds."""

import statistics


def read(window):
    if window.trace is None or window.chips < 2:
        return None
    return statistics.median(window.trace.exposed_collective_per_round())
