"""Layer stream (the streamed drivers' tile loop, ``mesh/streaming.py``):
device seconds per round of the ops traced under ``sda.stream.acc`` -- the
step's two accumulator adds, what streaming costs the device over a
monolithic round; median over the traced rounds, from the ops' ``tf_op``
(reduce/scopes.py). A device op carries one scope, its root's: where the
compiler fuses the adds into a neighbouring op, they are counted where that
op lands, and this reads nothing."""

from reduce import scopes


def read(window):
    return scopes.seconds_per_round(window, "sda.stream.acc")
