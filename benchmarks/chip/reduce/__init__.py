"""From a profiler trace to numbers: device busy and idle time, per-op
time, collective time and its exposed part, and the idle gaps labelled
by what the host was doing.

Two inputs. The device's side is the ``.xplane.pb`` the JAX profiler
writes, read with ``jax.profiler.ProfileData``: one plane per chip,
``/device:TPU:<n>``, whose line ``XLA Ops`` carries one event per
executed HLO op (fusions, custom calls, copies, collectives), in
nanoseconds since ``profile_start_time`` of the plane ``Task
Environment`` (looked at by hand, PR 23). The host's side is the
harness's span log (harness.SpanLog): the program's spans and the
harness's own ``bench.round`` / ``bench.verify`` marks on the epoch
clock. :func:`load` puts both on the epoch clock; the chip's clock ran
about half a millisecond ahead of the host's in the traces looked at.

Everything below :func:`load` works on plain ``(name, start_ns,
end_ns)`` tuples, so the tests build their events by hand
(tests/test_reduce.py) and no recorded file bloats the tree.
"""

from __future__ import annotations

import re
from dataclasses import dataclass
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ENVIRONMENT_PLANE = "Task Environment"

#: HLO ops that move data between chips, by the op's own name
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)")

#: an op event is named by its whole HLO line:
#: ``%copy.4 = u32[300,333333,3]{1,2,0:T(4,128)} copy(...)``
HLO_LINE = re.compile(r"^%?(?P<op>\S+) = \(?(?P<shape>\w+\[[\d,]*\])?")


def short(event_name: str) -> str:
    """``copy.4 u32[300,333333,3]`` from an op event's HLO line: the
    op's name, which classifies it, and its (first) result shape."""
    match = HLO_LINE.match(event_name)
    if not match:
        return event_name
    return " ".join(part for part in match.groups() if part)


ROUND = "bench.round"
VERIFY = "bench.verify"


# -- interval arithmetic -------------------------------------------------------

def union(intervals) -> list:
    """Sorted, disjoint ``(start, end)`` covering the same instants."""
    merged = []
    for start, end in sorted((s, e) for s, e in intervals if e > s):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals) -> int:
    return sum(e - s for s, e in intervals)


def gaps(busy, lo, hi) -> list:
    """The idle ``(start, end)`` between the disjoint ``busy`` intervals
    inside ``[lo, hi]``."""
    out, cursor = [], lo
    for start, end in clip(busy, lo, hi):
        if start > cursor:
            out.append((cursor, start))
        cursor = max(cursor, end)
    if hi > cursor:
        out.append((cursor, hi))
    return out


def attribute(gap, annotations) -> dict:
    """``{span name: nanoseconds}`` of the idle ``gap``: every instant goes
    to the innermost host span open at it -- the shortest of those that
    cover it, on whatever thread -- and to ``unattributed`` where none was
    open."""
    lo, hi = gap
    open_ = [(end - start, name, start, end) for name, start, end in annotations
             if start < hi and end > lo]
    cuts = sorted({lo, hi} | {min(max(edge, lo), hi)
                              for _, _, start, end in open_
                              for edge in (start, end)})
    out: dict = {}
    for a, b in zip(cuts, cuts[1:]):
        covering = [(length, name) for length, name, start, end in open_
                    if start <= a and end >= b]
        name = min(covering)[1] if covering else "unattributed"
        out[name] = out.get(name, 0) + (b - a)
    return out


# -- the reduced trace ---------------------------------------------------------

@dataclass
class Reduced:
    """One traced window. Times in seconds unless a name says ``_ns``."""

    window_ns: tuple                       # (start, end) of the traced rounds
    devices: dict                          # chip index -> [(name, start, end)]
    annotations: list                      # [(name, start, end)] on the host
    rounds: list                           # [(start, end)] of bench.round

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) / 1e9

    def _busy(self, ops) -> list:
        return clip(union((s, e) for _, s, e in ops), *self.window_ns)

    @property
    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the chips."""
        per_chip = [total(self._busy(ops)) for ops in self.devices.values()]
        return sum(per_chip) / len(per_chip) / 1e9

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def _per_round(self, per_chip_intervals: list) -> list:
        """Seconds of each chip's disjoint intervals inside each round's
        host span, averaged over the chips."""
        return [sum(total(clip(busy, lo, hi)) for busy in per_chip_intervals)
                / len(per_chip_intervals) / 1e9 for lo, hi in self.rounds]

    def per_round(self, select=lambda name: True) -> list:
        """For each traced round, the seconds in which a selected op ran
        inside the round's host span, averaged over the chips."""
        return self._per_round([union((s, e) for n, s, e in ops if select(n))
                                for ops in self.devices.values()])

    def compute_per_round(self) -> list:
        """:meth:`per_round` of everything but the collectives."""
        return self.per_round(lambda name: not COLLECTIVE.search(name))

    def host_per_round(self) -> list:
        """For each traced round, its wall seconds on the host minus the
        seconds in which any op ran on the device inside it."""
        return [(hi - lo) / 1e9 - busy
                for (lo, hi), busy in zip(self.rounds, self.per_round())]

    def exposed_collective_per_round(self) -> list:
        """For each round, the collective seconds during which no other
        op ran on that chip, averaged over the chips."""
        exposed = []
        for ops in self.devices.values():
            compute = union((s, e) for n, s, e in ops if not COLLECTIVE.search(n))
            collective = union((s, e) for n, s, e in ops if COLLECTIVE.search(n))
            # what is left of each collective interval between compute ops
            exposed.append([gap for lo, hi in collective
                            for gap in gaps(compute, lo, hi)])
        return self._per_round(exposed)

    def op_totals(self) -> list:
        """``[[name, seconds]]`` by op name, averaged over the chips,
        largest first. Trailing instance numbers are kept: they tell the
        round's fusions apart."""
        sums: dict = {}
        for ops in self.devices.values():
            for name, start, end in ops:
                lo, hi = max(start, self.window_ns[0]), min(end, self.window_ns[1])
                if hi > lo:
                    sums[name] = sums.get(name, 0) + (hi - lo)
        chips = len(self.devices)
        return sorted(([n, ns / chips / 1e9] for n, ns in sums.items()),
                      key=lambda row: -row[1])

    def gap_totals(self) -> list:
        """``[[label, seconds]]``: idle time of the first chip by the
        host span open during each gap, largest first."""
        first = self.devices[min(self.devices)]
        sums: dict = {}
        for gap in gaps(self._busy(first), *self.window_ns):
            for name, ns in attribute(gap, self.annotations).items():
                sums[name] = sums.get(name, 0) + ns
        return sorted(([n, ns / 1e9] for n, ns in sums.items()),
                      key=lambda row: -row[1])

    def breakdown(self) -> dict:
        return {"device_ops": self.op_totals()[:10],
                "idle_gaps": self.gap_totals()[:10]}


# -- reading the file ----------------------------------------------------------

def load(path: Path, chips: int, host_spans: list) -> Reduced | None:
    """The reduced trace of one ``.xplane.pb`` and the span log of the
    same window, or None where the file holds no device plane (a CPU
    rehearsal) or the log no ``bench.round``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    devices, epoch_ns = {}, None
    for plane in data.planes:
        match = DEVICE_PLANE.match(plane.name)
        if match and int(match.group(1)) < chips:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[int(match.group(1))] = [
                        (short(e.name), int(e.start_ns),
                         int(e.start_ns + e.duration_ns))
                        for e in line.events]
        elif plane.name == ENVIRONMENT_PLANE:
            epoch_ns = int(dict(plane.stats)["profile_start_time"])
    rounds = sorted((s, e) for n, s, e in host_spans if n == ROUND)
    if not devices or not rounds or epoch_ns is None:
        return None
    devices = {chip: [(n, s + epoch_ns, e + epoch_ns) for n, s, e in ops]
               for chip, ops in devices.items()}
    ends = [e for n, _, e in host_spans if n in (ROUND, VERIFY)]
    return Reduced(window_ns=(rounds[0][0], max(ends)), devices=devices,
                   annotations=host_spans, rounds=rounds)


def reduce_run(trace_dir: Path, chips: int, host_spans: list) -> Reduced | None:
    """The newest ``.xplane.pb`` under a profiler directory, reduced."""
    files = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    return load(files[-1], chips, host_spans) if files else None
