"""Input elements (participants x dim) per second and chip at the
window's median pace: one round's elements over the median time from
one round's start to the next (the check between rounds included).
A median, not the window's total: one host hiccup of 100 ms moved the
total-based rate by 1 % between runs of the same code (my chip runs,
PR 23), and a rate that swings cannot carry a tight bound."""

import statistics


def read(window):
    per_round = window.facts.get("elements_per_round")
    starts = window.round_starts
    if not per_round or len(starts) < 2:
        return None
    period = statistics.median(b - a for a, b in zip(starts, starts[1:]))
    return per_round / (period * window.chips)
