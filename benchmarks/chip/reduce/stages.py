"""Device seconds per round that no stage scope names, from the raw trace.

The program puts every device op of a round under one ``jax.named_scope``
of a closed list of **stage scopes** (docs/observability.md, "Stage
scopes"; :data:`STAGES` repeats the list, ``sda.mask``'s children are
:data:`MASK_CHILDREN`). A scope reader (``reduce/scopes.py``) sums what
carries its scope. This module measures what is left: the seconds of a
round in which an op ran on the device and none under the scopes asked
about did -- the roots the compiler makes itself (they carry no ``tf_op``
at all, or only the scope of the loop around them) and the loop's own
bookkeeping.

Arithmetic on **instants, not on ops**, because the ``XLA Ops`` line
nests: a ``while`` event encloses its body's, so filtering ops (the
``without=`` of ``scopes.per_round``) would count a ``while`` whole where
it carries the scope asked for and drop it whole where it does not. Per
round and chip, with ``A`` the instants at which an op of the first set
ran and ``B`` those of the second, ``|A \\ B| = |A u B| - |B|``: unions
of intervals (``reduce.union`` / ``clip`` / ``total``) on the events of
``scopes.device_events``, one parse of the ``.xplane.pb`` shared with the
scope readers; averaged over the chips, the median over the traced rounds.
"""

from __future__ import annotations

import statistics
from pathlib import Path

from . import clip, scopes, total, union

#: the stage scopes, as docs/observability.md lists them
STAGES = ("sda.residues", "sda.fold", "sda.blocks", "sda.mask", "sda.share",
          "sda.relayout", "sda.mask_share", "sda.clerk_combine",
          "sda.reconstruct", "sda.unmask", "sda.stream.acc")

#: the scopes that split ``sda.mask`` under ChaCha masking
MASK_CHILDREN = ("sda.mask.chacha", "sda.mask.reduce", "sda.mask.relayout",
                 "sda.mask.fold")


def under(*names):
    """A test of a ``tf_op``: does any of ``names`` stand in its path, as
    a whole component? Asked once per distinct ``tf_op``."""
    verdicts: dict = {}

    def test(tf_op: str) -> bool:
        verdict = verdicts.get(tf_op)
        if verdict is None:
            path = scopes.components(tf_op)
            verdict = verdicts[tf_op] = any(name in path for name in names)
        return verdict

    return test


def anything(_tf_op: str) -> bool:
    return True


def seconds(devices: dict, rounds: list, first, second=None) -> list:
    """For each round's host span, the seconds in which an op selected by
    ``first`` ran inside it and -- where ``second`` is given -- none
    selected by ``second`` did, averaged over the chips."""
    sums = [0.0] * len(rounds)
    for events in devices.values():
        b = union((s, e) for op, s, e in events if second(op)) if second else []
        a_or_b = union([(s, e) for op, s, e in events if first(op)] + b)
        for index, (lo, hi) in enumerate(rounds):
            sums[index] += total(clip(a_or_b, lo, hi)) - total(clip(b, lo, hi))
    return [ns / len(devices) / 1e9 for ns in sums]


def events_of(window, out: Path | None = None) -> dict | None:
    """``scopes.device_events`` of the trace this process has just
    written, or None in an untraced run and on a trace without device
    planes."""
    if window.trace is None:
        return None
    path = scopes.newest_trace(out or Path(__file__).resolve().parents[1] / "out")
    return scopes.device_events(path, window.chips) if path else None


def remainder_per_round(window, first, second, out: Path | None = None):
    """Median over the traced rounds of :func:`seconds`; None in an
    untraced run, and where no op is selected by ``second``: a program
    without the scopes the remainder is taken against."""
    devices = events_of(window, out)
    if not devices or not any(second(op) for events in devices.values()
                              for op, _, _ in events):
        return None
    return statistics.median(
        seconds(devices, window.trace.rounds, first, second))
