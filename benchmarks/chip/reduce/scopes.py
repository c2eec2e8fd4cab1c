"""Device seconds per round by ``jax.named_scope``, from the raw trace.

An XLA step's device ops are anonymous fusions (``fusion.272``); what
tells them apart is the scope they were traced under, which the profiler
keeps as the ``tf_op`` stat of an op's **event metadata**
(``jit(_local_round)/sda.mask/sda.mask.chacha/...:``).
``jax.profiler.ProfileData`` does not walk event metadata (PERF.md, Open
questions, PR 24), so this module reads the ``.xplane.pb`` itself: the
protobuf wire format by hand, the few fields it needs of
``tsl/profiler/protobuf/xplane.proto`` (importing the installed
``xplane_pb2`` pulls in TensorFlow, half a minute).

Same clock, same rounds and same arithmetic as ``reduce.load``: the
``XLA Ops`` line of each ``/device:TPU:<n>`` plane, event start =
line timestamp + offset, shifted by ``profile_start_time`` onto the epoch
clock of the harness's ``bench.round`` marks; per round and scope the
union of the op intervals inside the round's host span, averaged over the
chips. A scope is a whole component of the ``tf_op`` path: ``sda.mask``
does not match an op under ``sda.mask_share``, and an op under
``sda.mask/sda.mask.chacha`` counts for both of those. A union, because
the line nests: a ``while`` op's event encloses its body's.
"""

from __future__ import annotations

import functools
import statistics
from pathlib import Path

from . import (DEVICE_PLANE, ENVIRONMENT_PLANE, OPS_LINE, clip, total, union)

TF_OP = "tf_op"
START_TIME = "profile_start_time"


# -- protobuf wire format ------------------------------------------------------

def _varint(buf, pos: int):
    value = shift = 0
    while True:
        byte = buf[pos]
        pos += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, pos
        shift += 7


def fields(buf):
    """``(field number, value)`` of one message: an int for a varint or a
    fixed-width field, a memoryview for a length-delimited one."""
    buf = memoryview(buf)
    pos, end = 0, len(buf)
    while pos < end:
        tag, pos = _varint(buf, pos)
        number, kind = tag >> 3, tag & 7
        if kind == 0:
            value, pos = _varint(buf, pos)
        elif kind == 2:
            size, pos = _varint(buf, pos)
            value, pos = buf[pos:pos + size], pos + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value, pos = int.from_bytes(buf[pos:pos + size], "little"), pos + size
        else:
            raise ValueError(f"wire type {kind} is not in xplane.proto")
        yield number, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _name(buf) -> str:
    """Field 2 of an XPlane or an XLine, without reading past it: the name
    decides whether the rest is worth parsing."""
    return next((_text(f) for n, f in fields(buf) if n == 2), "")


def _signed(value: int) -> int:
    return value - (1 << 64) if value >= 1 << 63 else value


# -- the few messages of xplane.proto -------------------------------------------

def _stat(buf, stat_names: dict):
    """XStat -> (stat name, value): a string, an integer, or None for a
    double or bytes value (none is read here)."""
    name = value = None
    for number, field in fields(buf):
        if number == 1:
            name = stat_names.get(field)
        elif number in (3, 4):            # uint64_value, int64_value
            value = _signed(field) if number == 4 else field
        elif number == 5:                 # str_value
            value = _text(field)
        elif number == 7:                 # ref_value: a string kept as a stat's name
            value = stat_names.get(field)
    return name, value


def _map_entry(buf):
    key = value = None
    for number, field in fields(buf):
        if number == 1:
            key = field
        elif number == 2:
            value = field
    return key, value


def _plane(buf) -> dict:
    """XPlane -> name, its lines (raw), event-metadata id -> ``tf_op``, and
    its own stats by name."""
    name, lines, metadata, stat_names, stats = "", [], [], {}, []
    for number, field in fields(buf):
        if number == 2:
            name = _text(field)
        elif number == 3:
            lines.append(field)
        elif number == 4:
            metadata.append(field)
        elif number == 5:
            key, value = _map_entry(field)
            stat_names[key] = _name(value)   # XStatMetadata.name is field 2 too
        elif number == 6:
            stats.append(field)
    tf_op = {}
    for entry in metadata:
        key, value = _map_entry(entry)
        for number, field in fields(value):
            if number == 5:               # XEventMetadata.stats
                stat, text = _stat(field, stat_names)
                if stat == TF_OP and isinstance(text, str):
                    tf_op[key] = text
    return {"name": name, "lines": lines, "tf_op": tf_op,
            "stats": dict(_stat(s, stat_names) for s in stats)}


def _line(buf):
    """XLine -> (name, [(metadata id, start_ns, end_ns)]) on the line's
    own clock, as ``ProfileData`` gives its events."""
    name, timestamp_ns, raw = "", 0, []
    for number, field in fields(buf):
        if number == 2:
            name = _text(field)
        elif number == 3:
            timestamp_ns = _signed(field)
        elif number == 4:
            raw.append(field)
    events = []
    for event in raw:
        metadata_id = offset_ps = duration_ps = 0
        for number, field in fields(event):
            if number == 1:
                metadata_id = field
            elif number == 2:
                offset_ps = _signed(field)
            elif number == 3:
                duration_ps = _signed(field)
        start = timestamp_ns + offset_ps / 1000.0
        events.append((metadata_id, int(start), int(start + duration_ps / 1000.0)))
    return name, events


# -- scopes ----------------------------------------------------------------------

def components(tf_op: str) -> list:
    """``jit(f)/sda.mask/sda.mask.chacha/add:`` -> its path components,
    the op's own name (the last, with its ``:type``) left out."""
    return tf_op.split(":")[0].split("/")[:-1]


@functools.lru_cache(maxsize=1)          # five readers, one parse
def device_events(path: Path, chips: int) -> dict | None:
    """``{chip: [(tf_op, start_ns, end_ns)]}`` of the ``XLA Ops`` lines on
    the epoch clock, or None where the file holds no device plane or no
    ``profile_start_time``."""
    devices, epoch_ns = {}, None
    for number, field in fields(Path(path).read_bytes()):
        if number != 1:                   # XSpace.planes
            continue
        name = _name(field)
        match = DEVICE_PLANE.match(name)
        if match and int(match.group(1)) < chips:
            plane = _plane(field)
            for raw in plane["lines"]:
                if _name(raw) == OPS_LINE:
                    devices[int(match.group(1))] = [
                        (plane["tf_op"].get(mid, ""), start, end)
                        for mid, start, end in _line(raw)[1]]
        elif name == ENVIRONMENT_PLANE:
            start_time = _plane(field)["stats"].get(START_TIME)
            epoch_ns = None if start_time is None else int(start_time)
    if not devices or epoch_ns is None:
        return None
    return {chip: [(op, s + epoch_ns, e + epoch_ns) for op, s, e in events]
            for chip, events in devices.items()}


def per_round(devices: dict, rounds: list, scope: str, without: tuple = ()) -> list:
    """For each round's host span, the seconds in which an op under
    ``scope`` -- and under none of ``without`` -- ran inside it, averaged
    over the chips."""
    def selected(tf_op: str) -> bool:
        path = components(tf_op)
        return scope in path and not any(other in path for other in without)

    busy = [union((s, e) for op, s, e in events if selected(op))
            for events in devices.values()]
    return [sum(total(clip(b, lo, hi)) for b in busy) / len(busy) / 1e9
            for lo, hi in rounds]


def newest_trace(out: Path) -> Path | None:
    """The newest ``.xplane.pb`` under the benchmark's ``out/``: the one
    this process has just written (a traced run writes one)."""
    files = sorted(Path(out).glob("*/plugins/profile/*/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    return files[-1] if files else None


def seconds_per_round(window, scope: str, without: tuple = (),
                      out: Path | None = None):
    """Median over the traced rounds of ``window`` of the device seconds
    under ``scope`` (and under none of ``without``); None in an untraced
    run, on a trace without device planes, and where no op carries the
    scope (a program without it)."""
    if window.trace is None:
        return None
    path = newest_trace(out or Path(__file__).resolve().parents[1] / "out")
    if path is None:
        return None
    devices = device_events(path, window.chips)
    if not devices:
        return None
    seconds = per_round(devices, window.trace.rounds, scope, without)
    return statistics.median(seconds) if any(seconds) else None
