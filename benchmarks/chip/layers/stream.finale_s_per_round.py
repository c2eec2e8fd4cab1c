"""Layer stream (the streamed drivers' tile loop, ``mesh/streaming.py``):
seconds per round inside the program's ``stream.finale`` spans -- the
reconstruction and unmask of a dim tile, dispatched and blocked on (the
span log, host clock); the seconds in the window over the ``stream.round``
spans in it (reduce/spans.py)."""

from reduce import spans


def read(window):
    return spans.seconds_per_root(window, "stream.finale", "stream.round")
