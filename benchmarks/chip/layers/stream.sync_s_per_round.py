"""Layer stream (the streamed drivers' tile loop, ``mesh/streaming.py``):
seconds per round inside the program's ``stream.steps_sync`` spans -- the
calling thread blocked on a step: the back-pressure wait before a block is
made (for the step ``BLOCKS_IN_FLIGHT`` blocks back; nothing where
transfers are slower than steps) and the wait for the last step before the
finale, which in a transfer-bound round is the last block's transfer and
its step (the span log, host clock); the seconds in the window over the
``stream.round`` spans in it (reduce/spans.py)."""

from reduce import spans


def read(window):
    return spans.seconds_per_root(window, "stream.steps_sync", "stream.round")
