"""Layer fields (field kernels), under ChaCha masking: device seconds per
round of the ops traced under ``sda.mask.fold`` -- the fold of a block's
masks over its rows, the running sum between blocks, and the mask add
(on the kernel path one add of the masks' sum to the inputs' fold; on the
XLA step the add to every input row); median over the traced rounds,
from the ops' ``tf_op`` (reduce/scopes.py). None where no op carries the
scope: until PR 35 these ops sat directly under ``sda.mask``."""

from reduce import scopes


def read(window):
    return scopes.seconds_per_round(window, "sda.mask.fold")
