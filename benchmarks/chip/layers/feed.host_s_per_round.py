"""Layer feed (mesh drivers and the host->HBM feed), in a host-fed cell:
a round's wall seconds minus the seconds the device was busy in it -- XLA
laying the matrix out for the device on the host's threads, the transfer,
the read-back; median over the traced rounds."""

import statistics


def read(window):
    if window.trace is None:
        return None
    return statistics.median(window.trace.host_per_round())
