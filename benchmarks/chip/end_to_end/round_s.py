"""Median wall seconds of one pod round: inputs handed to the entry
point until the aggregate is ready (blocked on; host-fed: on the
host). The sample count and the tail go to the log."""


def read(window):
    return window.median_round_s
