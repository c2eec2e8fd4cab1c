"""Median wall seconds of one host-fed pod round: the host matrix handed
to ``pod.aggregate`` until the aggregate is a NumPy array again. A
name of its own: over half of it is host work (layout, transfer), whose
run-to-run spread on a shared one-chip host is twenty times that of a
device-bound round and must not loosen the bound on ``round_s``."""


def read(window):
    return window.median_round_s
