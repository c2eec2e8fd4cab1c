"""Layer stream (the streamed drivers' tile loop, ``mesh/streaming.py``):
seconds per round inside the program's ``stream.dispatch`` spans -- the
calls of the jitted accumulate step, one a block, from entry until the
asynchronous dispatch returns (the span log, host clock); the seconds in
the window over the ``stream.round`` spans in it (reduce/spans.py)."""

from reduce import spans


def read(window):
    return spans.seconds_per_root(window, "stream.dispatch", "stream.round")
