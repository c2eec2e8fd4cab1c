"""The device rule (utils/backend.py): JAX selects the platform, the
program never switches it, and an entry point that needs the chip fails
without one — no probe subprocess, no fallback."""

import subprocess
import sys

import jax
import pytest

from sda_tpu.utils import backend


def test_device_record_is_what_jax_reports():
    rec = backend.device_record()
    first = jax.devices()[0]
    assert rec == {"platform": first.platform,
                   "device_kind": first.device_kind,
                   "device_count": len(jax.devices())}
    assert rec["platform"] == "cpu"  # conftest pinned it


def test_chip_check_raises_on_cpu():
    with pytest.raises(RuntimeError, match="needs a TPU"):
        backend.require_tpu()


def test_chip_check_passes_on_a_tpu(monkeypatch):
    class _Chip:
        platform = "tpu"
        device_kind = "TPU v5 lite"

    monkeypatch.setattr(jax, "devices", lambda *a: [_Chip()] * 4)
    assert backend.require_tpu() == {
        "platform": "tpu", "device_kind": "TPU v5 lite", "device_count": 4}


def test_no_subprocess_and_no_platform_switch(monkeypatch):
    """The rule itself: asking for the device spawns nothing and leaves
    ``jax_platforms`` as the environment set it."""
    def _no_spawn(*a, **kw):
        raise AssertionError("the device rule must not spawn a process")

    monkeypatch.setattr(subprocess, "run", _no_spawn)
    monkeypatch.setattr(subprocess, "Popen", _no_spawn)
    before = jax.config.jax_platforms
    backend.device_record()
    with pytest.raises(RuntimeError):
        backend.require_tpu()
    backend.arm_compile_cache()
    assert jax.config.jax_platforms == before
    for gone in ("probe_tpu", "select_platform", "use_platform"):
        assert not hasattr(backend, gone)


def test_host_tier_line_keeps_its_platform_and_leaves_jax_alone(
        monkeypatch, capsys):
    """``sda-sim``'s host-tier drills ran nothing on JAX's device: on a
    chip host their line still says ``platform: "cpu"`` (obs/regress keys
    history on it) and no backend is initialised to print it."""
    import argparse
    import json

    from sda_tpu.cli import sim

    class _Chip:
        platform = "tpu"
        device_kind = "TPU v5 lite"

    args = argparse.Namespace(trace_out=None)
    monkeypatch.setattr(jax, "devices", lambda *a: [_Chip()])
    sim._emit(args, {"metric": "pod", "value": 1}, device=True)
    line = json.loads(capsys.readouterr().out)
    assert (line["platform"], line["device_kind"], line["device_count"]) \
        == ("tpu", "TPU v5 lite", 1)

    def _untouched(*a):
        raise AssertionError("a host-tier drill must not ask JAX")

    monkeypatch.setattr(jax, "devices", _untouched)
    sim._emit(args, {"metric": "drill", "platform": "cpu"})
    sim._emit(args, {"metric": "chaos drill"})
    for raw in capsys.readouterr().out.splitlines():
        line = json.loads(raw)
        assert line["platform"] == "cpu" and "device_kind" not in line


def test_entry_points_fail_without_a_chip():
    """``chip_smoke.py`` is one process that exits non-zero, result-less,
    when JAX finds no TPU."""
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, os.path.join(root, "chip_smoke.py")],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert r.stdout.strip() == "", r.stdout
    assert "needs a TPU" in r.stderr, r.stderr[-500:]
