"""Median wall seconds of one federated round: aggregation created
until the revealed sum is decoded. A name of its own: host-clock
rounds are noisier than device rounds and must not loosen the bound
on ``round_s``."""


def read(window):
    return window.median_round_s
