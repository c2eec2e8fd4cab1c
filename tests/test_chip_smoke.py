"""chip_smoke.py's phases at toy size on the CPU: the same entry points
and verdicts the chip run uses, so a phase that stops parsing its record
is caught here and not on the chip."""

import os
import sys

import jax
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import chip_smoke  # noqa: E402


def test_pod_round_phase_exact_and_names_its_device(capsys):
    result = chip_smoke.pod_round(8, 99)
    assert result["exact"] is True and result["pallas"] is False
    assert result["platform"] == "cpu" and result["device_kind"] == "cpu"
    assert result["device_count"] >= 1
    assert result["mode"].startswith("simpod mesh")
    assert capsys.readouterr().out == ""  # the phase returns, main prints


def test_streaming_chacha_phase_exact():
    result = chip_smoke.pod_round(8, 99, mask="chacha", streaming=True)
    assert result["exact"] is True and result["mode"] == "streaming"


def test_streamed_blocks_phase_exact_and_refuses_a_cpu_kernel():
    # stream.packed_pallas at toy size: three blocks, the last ragged; the
    # kernel's PRNG is the chip's, so its phase fails here as it must
    result = chip_smoke.pod_round(8, 99, streaming=True, participants_chunk=3)
    assert result["exact"] is True and result["mode"] == "streaming"
    assert result["pallas"] is False and result["round_is_warm"] is False
    with pytest.raises(chip_smoke.PhaseFailed, match="rc=1"):
        chip_smoke.pod_round(8, 99, pallas=True, streaming=True,
                             participants_chunk=3)


def test_additive_chacha_phase_exact_on_the_xla_step():
    result = chip_smoke.pod_round(8, 99, clerks=3, sharing="additive",
                                  mask="chacha")
    assert result["exact"] is True and result["pallas"] is False
    assert result["mode"].startswith("simpod mesh")


def test_packed_chacha_pallas_phase_refuses_a_cpu_and_is_exact_on_the_xla_step():
    # pod.flagship.packed_chacha_pallas at toy size: the kernel's PRNG is
    # the chip's, so the phase fails here as it must; the same scheme and
    # masks on the XLA step reveal the plain sum
    with pytest.raises(chip_smoke.PhaseFailed, match="rc=1"):
        chip_smoke.pod_round(8, 99, pallas=True, mask="chacha")
    result = chip_smoke.pod_round(8, 99, mask="chacha")
    assert result["exact"] is True and result["pallas"] is False


def test_additive_sharing_refuses_the_kernel_before_any_round():
    with pytest.raises(chip_smoke.PhaseFailed, match="rc=1"):
        chip_smoke.pod_round(8, 99, clerks=3, sharing="additive", pallas=True)


def test_pallas_pod_phase_refuses_a_cpu():
    with pytest.raises(chip_smoke.PhaseFailed, match="rc=1"):
        chip_smoke.pod_round(8, 99, pallas=True)


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 virtual devices")
def test_model_scale_phase_interprets_on_cpu_and_says_so():
    result = chip_smoke.model_scale_round(
        dim=4_800, participants=8, shards="4x2", rounds=2)
    assert result["exact"] is True and result["retraces"] == 0
    assert result["pallas"] is True
    # no Mosaic here: the record must say the kernel was interpreted,
    # which is exactly what chip_smoke.main() refuses on the chip
    assert result["pallas_interpret"] is True
    assert result["mesh"] == [4, 2]


def test_federated_phase_exact_over_async_http_sqlite():
    result = chip_smoke.federated_rounds("linear", 4, 1)
    assert result["exact"] is True
    assert result["client_failures"] == 0 and result["leaks"] == 0
    assert result["rounds_run"] == 1


def test_inexact_or_failed_record_fails_the_phase(monkeypatch):
    monkeypatch.setattr(chip_smoke, "run_sim", lambda argv: {
        "rc": 0, "exact": False, "pallas": False, "mode": "simpod mesh (1, 1)"})
    with pytest.raises(chip_smoke.PhaseFailed, match="exact=False"):
        chip_smoke.pod_round(8, 99)


def test_main_has_no_cpu_mode(capsys):
    with pytest.raises(RuntimeError, match="needs a TPU"):
        chip_smoke.main()
    assert capsys.readouterr().out == ""
