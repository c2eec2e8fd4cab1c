"""Layer fields (field kernels), under ChaCha masking: device seconds per
round of the ops traced under ``sda.mask.chacha`` -- the ChaCha20 block
function and the pairing of its words into 64-bit draws
(``chacha_jax.stream_u64_at`` in ``_mask_stage``); median over the traced
rounds, from the ops' ``tf_op`` (reduce/scopes.py). None where no op
carries the scope: a program from before the scope existed."""

from reduce import scopes


def read(window):
    return scopes.seconds_per_round(window, "sda.mask.chacha")
