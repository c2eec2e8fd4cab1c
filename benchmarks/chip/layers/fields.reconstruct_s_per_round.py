"""Layer fields (field kernels): device seconds per round of the ops
traced under ``sda.reconstruct``, its children ``sda.reconstruct.lagrange``
and ``sda.reconstruct.unbatch`` included (an op under a child carries the
parent in its path too) -- the clerk rows to the masked totals; median
over the traced rounds, from the ops' ``tf_op`` (reduce/scopes.py). None
in an untraced run and where no op carries the scope."""

from reduce import scopes


def read(window):
    return scopes.seconds_per_round(window, "sda.reconstruct")
