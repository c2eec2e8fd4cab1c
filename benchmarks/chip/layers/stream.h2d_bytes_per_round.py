"""Layer stream (the streamed drivers' tile loop, ``mesh/streaming.py``):
bytes of the blocks a round hands to the device, after any pad -- the
program's counters ``mesh.stream.bytes`` / ``mesh.stream.rounds``. Exact
integers from shapes: they repeat from run to run.

The counters are the process's, not the window's: the warm-up round of
set-up is in both. The quotient is a round's bytes because
``drivers/stream.py`` warms up with the cell's own matrix."""


def read(window):
    from sda_tpu.utils import metrics

    counters = metrics.counter_report("mesh.stream.")
    rounds = counters.get("mesh.stream.rounds")
    if not rounds:
        return None
    return counters["mesh.stream.bytes"] / rounds
