"""Layer fields (field kernels), under ChaCha masking: device seconds per
round of the op that reduces the 64-bit draws modulo the modulus
(``FieldOps.from_u64`` in ``_mask_stage``, scope ``sda.mask.reduce``);
median over the traced rounds, from the ops' ``tf_op`` (reduce/scopes.py).

A device op carries one scope, its root's. Where the compiler fuses the
reduction into the mask add and the mask fold -- as the v5e's does: one
fusion takes the paired words, reduces, adds and folds, and carries the
fold's ``sda.mask`` -- no op carries ``sda.mask.reduce``, and this reads
the ops directly under ``sda.mask`` outside ``sda.mask.chacha``: the
reduction with the add and the fold it was fused with (and the seed words,
microseconds). None where the program has neither scope (a program from
before they existed: its ``sda.mask`` ops hold the cipher too)."""

from reduce import scopes


def read(window):
    own = scopes.seconds_per_round(window, "sda.mask.reduce")
    if own is not None:
        return own
    if scopes.seconds_per_round(window, "sda.mask.chacha") is None:
        return None
    return scopes.seconds_per_round(window, "sda.mask",
                                    without=("sda.mask.chacha",))
