"""Seconds per round of a program span that has a root span: the seconds
under the span in the window over the root spans in it.

A driver's warm-up is outside the window (the harness sets its span sink
after set-up), so both are the window's own. The roots are counted from
the intervals a traced run keeps; a run without intervals (a rehearsal
off the chip, whose trace reduces to nothing) takes the rounds the window
attempted, each of which opens one root. A program without the span, or
without the root, reads nothing.
"""

from __future__ import annotations


def roots(window, root: str) -> int:
    """How many ``root`` spans closed in the window."""
    if root not in window.spans:
        return 0
    if window.trace is None:
        return window.attempted
    return sum(1 for name, _, _ in window.trace.annotations if name == root)


def seconds_per_root(window, name: str, root: str):
    seconds, count = window.spans.get(name), roots(window, root)
    if seconds is None or not count:
        return None
    return seconds / count
