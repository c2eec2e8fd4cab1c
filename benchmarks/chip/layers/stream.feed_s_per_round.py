"""Layer stream (the streamed drivers' tile loop, ``mesh/streaming.py``):
seconds per round inside the program's ``stream.feed`` spans -- getting
blocks onto the device: the wait for the block before to have landed (the
loop keeps one transfer in flight), then the provider's slice, any pad and
``jnp.asarray`` until it returns. The hand-over is asynchronous, so the
seconds here are the transfers of all blocks but the round's last (the span
log, host clock); the seconds in the window over the ``stream.round`` spans
in it (reduce/spans.py)."""

from reduce import spans


def read(window):
    return spans.seconds_per_root(window, "stream.feed", "stream.round")
