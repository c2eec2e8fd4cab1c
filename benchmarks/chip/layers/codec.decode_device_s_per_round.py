"""Layer codec (``models/encoding.py``, ``models/federated.py``), on the
resident FedAvg round: device seconds per round of the ops traced under
``sda.decode`` -- the aggregate's centered lift, the mean in float32 and
the add to the global vector (with whatever the compiler fuses under that
root: the unmask's subtraction and the last adds of the reconstruction,
PERF.md §5); median over the traced rounds, from the ops' ``tf_op``
(reduce/scopes.py). None in an untraced run and where no op carries the
scope."""

from reduce import scopes


def read(window):
    return scopes.seconds_per_round(window, "sda.decode")
