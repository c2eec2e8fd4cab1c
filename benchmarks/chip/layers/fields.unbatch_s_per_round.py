"""Layer fields (field kernels): device seconds per round of the ops
traced under ``sda.reconstruct.unbatch`` -- ``unbatch_columns``, the
reconstructed ``[k, B]`` columns taken back to ``[d]``; median over the
traced rounds, from the ops' ``tf_op`` (reduce/scopes.py).

A device op carries one scope, its root's. Where the program opens the
scope (its sibling ``sda.reconstruct.lagrange`` is on the trace) and the
compiler leaves no op under it -- the v5e's moves the layout change up
into the terms of the Lagrange product, whose ops carry the product's
scope (PERF.md §5) -- this reads 0: no device second is the unbatch's
own. None in an untraced run and on a program with neither scope (one
from before they existed)."""

from reduce import scopes


def read(window):
    own = scopes.seconds_per_round(window, "sda.reconstruct.unbatch")
    if own is not None:
        return own
    opened = scopes.seconds_per_round(window, "sda.reconstruct.lagrange")
    return None if opened is None else 0.0
