"""Layer fields (field kernels), on the XLA step: device seconds per round
of the ops traced under ``sda.share`` (``_share_sum_stage``: the share
rows' uniform draws, the participant folds and, additive, the last row by
subtraction); median over the traced rounds, from the ops' ``tf_op``
(reduce/scopes.py). The scope is as old as the stage, so the parent
reports it too."""

from reduce import scopes


def read(window):
    return scopes.seconds_per_round(window, "sda.share")
