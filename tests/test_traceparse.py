"""Profiler-trace parsing (utils/traceparse.py): device-lane detection
and per-module durations out of a ``jax.profiler`` capture."""

import gzip
import json

import jax
import numpy as np

from sda_tpu.utils import traceparse


def synthetic_trace():
    """A Chrome trace shaped like an XProf capture: one TPU device lane
    (pid 2) plus host lanes that must be ignored."""
    return {"traceEvents": [
        {"ph": "M", "name": "process_name", "pid": 1,
         "args": {"name": "/host:CPU"}},
        {"ph": "M", "name": "process_name", "pid": 2,
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "name": "process_name", "pid": 3,
         "args": {"name": "python"}},
        # device lane: 3 executions of the round module + an unrelated op
        {"ph": "X", "pid": 2, "tid": 1, "name": "jit_round_fn",
         "ts": 0, "dur": 900.0},
        {"ph": "X", "pid": 2, "tid": 1, "name": "jit_round_fn",
         "ts": 1000, "dur": 1000.0},
        {"ph": "X", "pid": 2, "tid": 1, "name": "jit_round_fn",
         "ts": 2100, "dur": 1100.0},
        {"ph": "X", "pid": 2, "tid": 1, "name": "jit_tiny_fetch",
         "ts": 3300, "dur": 5.0},
        # host event with a jit-ish name: wrong lane, must not count
        {"ph": "X", "pid": 1, "tid": 9, "name": "jit_round_fn",
         "ts": 0, "dur": 99999.0},
    ]}


def test_device_lane_detection_and_stats():
    tr = synthetic_trace()
    assert traceparse.device_lane_pids(tr) == {2: "/device:TPU:0"}
    stats = traceparse.device_module_stats(tr)
    assert set(stats) == {"jit_round_fn", "jit_tiny_fetch"}
    assert stats["jit_round_fn"]["count"] == 3
    assert stats["jit_round_fn"]["median_us"] == 1000.0
    assert stats["jit_round_fn"]["total_us"] == 3000.0
    assert traceparse.dominant_module(stats) == "jit_round_fn"

    # even-length lists take the midpoint average
    tr["traceEvents"].append({"ph": "X", "pid": 2, "tid": 1,
                              "name": "jit_round_fn", "ts": 4000, "dur": 100.0})
    stats = traceparse.device_module_stats(tr)
    assert stats["jit_round_fn"]["count"] == 4
    assert stats["jit_round_fn"]["median_us"] == 950.0  # (900+1000)/2


def test_no_device_lane_is_empty_not_error():
    tr = {"traceEvents": [
        {"ph": "M", "name": "process_name", "pid": 1,
         "args": {"name": "/host:CPU"}},
        {"ph": "X", "pid": 1, "tid": 1, "name": "jit_x", "ts": 0, "dur": 1.0},
    ]}
    assert traceparse.device_module_stats(tr) == {}
    assert traceparse.dominant_module({}) is None


def test_load_latest_trace_roundtrip(tmp_path):
    d = tmp_path / "plugins" / "profile" / "2026_01_01"
    d.mkdir(parents=True)
    tr = synthetic_trace()
    with gzip.open(d / "host.trace.json.gz", "wt") as f:
        json.dump(tr, f)
    loaded = traceparse.load_latest_trace(str(tmp_path))
    assert loaded == tr
    assert traceparse.load_latest_trace(str(tmp_path / "empty")) is None


def test_real_cpu_capture_has_no_device_lane(tmp_path):
    """A real jax.profiler capture on the CPU backend parses cleanly and
    reports no accelerator lane."""
    fn = jax.jit(lambda x: (x @ x).sum())
    x = jax.numpy.ones((64, 64))
    jax.block_until_ready(fn(x))
    logdir = str(tmp_path / "trace")
    with jax.profiler.trace(logdir):
        jax.block_until_ready(fn(x))
    tr = traceparse.load_latest_trace(logdir)
    assert tr is not None and "traceEvents" in tr
    assert traceparse.dominant_module(traceparse.device_module_stats(tr)) is None
