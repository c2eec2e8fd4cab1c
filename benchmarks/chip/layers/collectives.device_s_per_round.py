"""Layer collectives: seconds in which a collective op
(psum_scatter / all_gather / psum over 'p') ran inside a round; median
over the traced rounds, averaged over the chips. None on one chip."""

import statistics

from reduce import COLLECTIVE


def read(window):
    if window.trace is None or window.chips < 2:
        return None
    return statistics.median(window.trace.per_round(COLLECTIVE.search))
