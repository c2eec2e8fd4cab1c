"""Layer feed (mesh drivers and the host->HBM feed), in a host-fed cell:
what a round still costs after the program's ``mesh.round`` has closed.
Per traced round, the end of ``bench.round`` minus the end of the
``mesh.round`` inside it: the strip (``pod.strip``), the read-back to
NumPy in the caller, the release of the round's buffers; median over the
traced rounds (the span log, host clock)."""

import statistics


def read(window):
    trace = window.trace
    if trace is None:
        return None
    ends = [end for name, _, end in trace.annotations if name == "mesh.round"]
    tails = []
    for lo, hi in trace.rounds:
        inside = [end for end in ends if lo <= end <= hi]
        if inside:
            tails.append((hi - max(inside)) / 1e9)
    return statistics.median(tails) if tails else None
