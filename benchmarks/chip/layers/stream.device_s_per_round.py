"""Layer stream (the streamed drivers' tile loop, ``mesh/streaming.py``):
seconds in which any op ran on the device inside a round -- per block the
int64 -> residue pass fused with the fold, the relayout, the kernel and the
accumulator adds, then one reconstruction; median over the traced rounds."""

import statistics


def read(window):
    if window.trace is None:
        return None
    return statistics.median(window.trace.per_round())
