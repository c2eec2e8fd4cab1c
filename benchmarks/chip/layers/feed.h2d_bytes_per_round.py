"""Layer feed (mesh drivers and the host->HBM feed), in a host-fed cell:
bytes a round hands to ``jax.device_put``, after padding -- the program's
counters ``mesh.feed.bytes`` / ``mesh.feed.calls``. Exact integers from
shapes: they repeat from run to run.

The counters are the process's, not the window's: the warm-up rounds of
set-up are in both. The quotient is a round's bytes only while every round
of the process feeds one shape, which holds because ``drivers/pod.py``
warms up with the cell's own matrix. A driver that warms up at another
shape makes this a mean over both; it then needs the counters' change
over the window (a snapshot where the harness sets its span sink)."""


def read(window):
    from sda_tpu.utils import metrics

    counters = metrics.counter_report("mesh.feed.")
    calls = counters.get("mesh.feed.calls")
    if not calls:
        return None
    return counters["mesh.feed.bytes"] / calls
