"""Layer fields (field kernels), under ChaCha masking: device seconds per
round of the ops traced under ``sda.mask.relayout`` -- the one layout
change of the mask expansion, uint32 residues word-major -> element order
through one one-hot matmul per byte on the matrix unit
(``chacha_jax.element_order``); median over the traced rounds, from the
ops' ``tf_op`` (reduce/scopes.py). None where no op carries the scope: a
program from before it existed (PR 30)."""

from reduce import scopes


def read(window):
    return scopes.seconds_per_round(window, "sda.mask.relayout")
