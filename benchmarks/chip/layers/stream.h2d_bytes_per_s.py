"""Layer stream (the streamed drivers' tile loop, ``mesh/streaming.py``):
the rate at which a streamed round's blocks reached the chip --
``stream.h2d_bytes_per_round`` over the part of a ``stream.round`` in which
blocks are fed and folded: its seconds minus ``stream.finale`` and
``stream.readback``. Host layout, transfer and whatever of the steps the
transfers do not hide are all in the divisor, so this is the rate to hold
against ``feed.h2d_bytes_per_s`` of the monolithic host-fed round (9.76e9
B/s, ledger PR 32), not a DMA rate.

It stands where a kernel's roofline share would: this configuration brings
no new kernel (its step runs ``sda.mask_share``, whose share ``packed-1m``
reports), and ``costs/peaks.json`` has no PCIe peak to divide by."""

from pathlib import Path

from harness import load_module
from reduce import spans

HOME = Path(__file__).resolve().parents[1]


def read(window):
    moved = load_module(HOME, "layers", "stream.h2d_bytes_per_round").read(window)
    whole = spans.seconds_per_root(window, "stream.round", "stream.round")
    if not moved or whole is None:
        return None
    tail = sum(spans.seconds_per_root(window, name, "stream.round") or 0.0
               for name in ("stream.finale", "stream.readback"))
    return moved / (whole - tail) if whole > tail else None
