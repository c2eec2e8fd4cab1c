"""Layer fields (field kernels), on the kernel path: device seconds per
round of the ops traced under ``sda.relayout`` -- the folded vector put
into the kernel's ``[k, B]`` column tiles (``batch_columns`` and the tile
pad), and under full masking the kernel's mask total taken back to
``[d]``; median over the traced
rounds, from the ops' ``tf_op`` (reduce/scopes.py). ``sda.mask.relayout``
is another scope (a whole path component). None in an untraced run and
where no op carries the scope (the XLA step)."""

from reduce import scopes


def read(window):
    return scopes.seconds_per_round(window, "sda.relayout")
