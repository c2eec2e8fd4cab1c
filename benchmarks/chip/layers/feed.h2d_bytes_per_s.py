"""Layer feed (mesh drivers and the host->HBM feed), in a host-fed cell:
the rate at which a round's input reached the chip --
``feed.h2d_bytes_per_round`` over ``feed.input_wait_s_per_round``, host
layout included. ``device_put`` of a host array of 4 GiB or more falls from
about 1e10 to 2e8 here (PERF.md, Findings, PR 23).

It inherits both readers' caveats: the bytes are a round's only while
warm-up feeds the cell's shape, and the wait subtracts a host-clock span
start from a chip-clock op start, 1.2-1.4 ms short by the skew seen so far
(0.5 % high on this rate in ``packed-1m-hostfed``)."""

from pathlib import Path

from harness import load_module

HOME = Path(__file__).resolve().parents[1]


def read(window):
    moved = load_module(HOME, "layers", "feed.h2d_bytes_per_round").read(window)
    waited = load_module(HOME, "layers", "feed.input_wait_s_per_round").read(window)
    if not moved or not waited:
        return None
    return moved / waited
