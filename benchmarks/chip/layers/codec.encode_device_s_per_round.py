"""Layer codec (``models/encoding.py``, ``models/federated.py``), on the
resident FedAvg round: device seconds per round of the ops traced under
``sda.encode`` -- the float32 deltas ``client - global``, their fixed-point
residues and the pad to the pod's grain; median over the traced rounds,
from the ops' ``tf_op`` (reduce/scopes.py).

A device op carries one scope, its root's. Where the program opens the
scope (its sibling ``sda.decode`` is on the trace) and the compiler
leaves no op under it -- it fuses the encode into the fold of the rows
that reads it, root under ``sda.fold``, so that the residues are never
written: the encode's seconds are then ``fields.fold_s_per_round``'s --
this reads 0: no device second is the encode's own. None in an untraced
run and on a program with neither scope."""

from reduce import scopes


def read(window):
    own = scopes.seconds_per_round(window, "sda.encode")
    if own is not None:
        return own
    opened = scopes.seconds_per_round(window, "sda.decode")
    return None if opened is None else 0.0
