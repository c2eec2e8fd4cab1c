"""Layer fields (field kernels), on the kernel path: device seconds per
round of the ops traced under ``sda.fold`` -- the participant fold on the
native ``[S, d]`` layout in front of the kernel (the compiler fuses the
residue pass into it: ONE read of the input); median over the traced
rounds, from the ops' ``tf_op`` (reduce/scopes.py). None in an untraced
run and where no op carries the scope (the XLA step)."""

from reduce import scopes


def read(window):
    return scopes.seconds_per_round(window, "sda.fold")
