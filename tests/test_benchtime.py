"""utils.benchtime — the chained-dispatch marginal timer bench.py reports.

bench.py's headline flows through marginal_seconds, so its chain sizing
and fallback arithmetic get direct coverage.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sda_tpu.utils.benchtime import chain_seconds, marginal_seconds


def _dispatch(work=2048):
    x = jnp.arange(work, dtype=jnp.float32)

    def d(i):
        return jnp.sin(x + i).sum()

    return d


def test_chain_seconds_scales_with_reps():
    d = _dispatch(1 << 18)
    chain_seconds(d, 1)  # warm: first call pays op compilation
    t1 = chain_seconds(d, 1)
    t40 = chain_seconds(d, 40)
    assert t1 > 0
    # 40 serialized reps must exceed 1: catches a regression that
    # ignores the reps argument
    assert t40 > t1


def test_marginal_seconds_respects_max_reps_and_reports_chain():
    per, info = marginal_seconds(_dispatch(), target_seconds=0.2, max_reps=7)
    assert per > 0
    chain = info["chain"]
    # r2 is clamped by max_reps even though max(10, ...) wants more
    assert chain["r2"] <= 7
    assert 1 <= chain["r1"] <= chain["r2"]
    assert info["probe_s"] > 0
    assert info["fixed_overhead_s"] >= 0


def test_marginal_seconds_fallback_when_difference_is_noise():
    # max_reps=1 forces r1 == r2 == 1: the (t2-t1)/(r2-r1) form is
    # undefined, so the helper must fall back to t2/r2 instead of
    # dividing by zero or returning a negative time
    per, info = marginal_seconds(_dispatch(), target_seconds=0.1, max_reps=1)
    assert per > 0
    assert info["chain"]["r1"] == info["chain"]["r2"] == 1


def test_marginal_time_is_sane_for_known_workload():
    # marginal per-rep must be below the time of a full 1-rep chain
    # (which includes fixed overhead) for any real dispatch
    d = _dispatch(1 << 16)
    per, info = marginal_seconds(d, target_seconds=0.5, max_reps=32)
    assert per <= info["probe_s"] * 1.5 + 1e-3


def test_pallas_knobs_are_env_only(monkeypatch):
    # library runtime must not depend on the mutable committed sweep
    # artifact (ADVICE r3): without env vars the defaults apply even when
    # a knobs record exists on disk
    from sda_tpu.utils import benchtime

    monkeypatch.setattr(benchtime, "_knobs_record",
                        lambda: {"p_block": 64, "tile": 4096,
                                 "stream_pc": 100})
    for var in ("SDA_PALLAS_PBLOCK", "SDA_PALLAS_TILE",
                "SDA_PALLAS_TILE_SOURCE", "SDA_BENCH_STREAM_PC"):
        monkeypatch.delenv(var, raising=False)
    assert benchtime.pallas_knobs() == (16, None)
    assert benchtime.stream_pc_knob() == 64
    assert not benchtime.tile_from_sweep()


def test_export_knobs_to_env_opts_in_and_marks_source(monkeypatch):
    from sda_tpu.utils import benchtime

    monkeypatch.setattr(benchtime, "_knobs_record",
                        lambda: {"p_block": 64, "tile": 4096,
                                 "stream_pc": 100})
    for var in ("SDA_PALLAS_PBLOCK", "SDA_PALLAS_TILE",
                "SDA_PALLAS_TILE_SOURCE", "SDA_BENCH_STREAM_PC"):
        monkeypatch.delenv(var, raising=False)
    benchtime.export_knobs_to_env()
    assert benchtime.pallas_knobs() == (64, 4096)
    assert benchtime.stream_pc_knob() == 100
    # record-sourced tile is marked so small shapes may clamp it
    assert benchtime.tile_from_sweep()


def test_export_knobs_never_overrides_explicit_env(monkeypatch):
    from sda_tpu.utils import benchtime

    monkeypatch.setattr(benchtime, "_knobs_record",
                        lambda: {"p_block": 64, "tile": 4096,
                                 "stream_pc": 100})
    monkeypatch.setenv("SDA_PALLAS_TILE", "1024")
    monkeypatch.setenv("SDA_PALLAS_PBLOCK", "8")
    monkeypatch.setenv("SDA_BENCH_STREAM_PC", "50")
    monkeypatch.delenv("SDA_PALLAS_TILE_SOURCE", raising=False)
    benchtime.export_knobs_to_env()
    assert benchtime.pallas_knobs() == (8, 1024)
    assert benchtime.stream_pc_knob() == 50
    # the explicit tile is NOT sweep-sourced: it must be honored unclamped
    assert not benchtime.tile_from_sweep()
