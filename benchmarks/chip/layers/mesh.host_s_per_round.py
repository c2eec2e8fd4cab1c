"""Layer mesh (mesh drivers), in a resident cell: a round's wall seconds
minus the seconds the device was busy in it -- dispatch, and the wait for
``block_until_ready`` to return; median over the traced rounds."""

import statistics


def read(window):
    if window.trace is None:
        return None
    return statistics.median(window.trace.host_per_round())
