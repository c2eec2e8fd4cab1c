"""drivers/pod_additive.py: what it builds, what it refuses, and the set-up
check of the program's mask streams against the reference's ChaCha20."""

import json
import types

import pytest

import harness

SPEC = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
CONFIG = json.loads((harness.HERE / "configs" / "pod-additive3-chacha.json").read_text())


@pytest.fixture(scope="module")
def driver():
    import sys

    sys.path.insert(0, str(harness.ROOT))
    import sda_tpu  # noqa: F401  (x64 before jax is used)

    return harness.load_module(harness.HERE, "drivers", "pod_additive")


@pytest.fixture(scope="module")
def reference():
    return harness.load_module(harness.HERE, "references", "additive_chacha")


def devices():
    import jax

    return jax.devices()[:1]


def test_the_configuration_builds_the_pod_a_user_would(driver):
    pod = driver.build_pod(CONFIG, 999_999, devices())
    assert type(pod.scheme).__name__ == "AdditiveSharing"
    assert (pod.scheme.share_count, pod.modulus) == (3, 536870233)
    assert type(pod.masking).__name__ == "ChaChaMasking"
    assert (pod.masking.seed_bitsize, pod.masking.dimension) == (128, 999_999)
    assert pod.pallas_active is False and pod._sp is not None  # XLA step, uint32 path
    assert pod.padded_shape(600, 999_999) == (600, 1_000_000)
    assert pod.mesh.devices.shape == (1, 1) and pod.scan_chunk == 8


@pytest.mark.parametrize("change, match", [
    ({"scheme": {"kind": "packed_shamir", "secret_count": 3, "share_count": 8}}, "additive sharing"),
    ({"scheme": "additive"}, "additive sharing"),
    ({"masking": "full"}, "ChaCha seed masks"),
    ({"masking": {"kind": "full"}}, "ChaCha seed masks"),
    ({"mesh": "4x1"}, "default_mesh_shape"),
    ({"use_pallas": True}, "use_pallas false"),
])
def test_a_file_it_cannot_build_is_refused(driver, change, match):
    with pytest.raises(ValueError, match=match):
        driver.build_pod({**CONFIG, **change}, 96, devices())


def test_host_fed_traffic_is_refused(driver):
    cell = types.SimpleNamespace(config=CONFIG, home=harness.HERE, traffic={
        "participants": 8, "dim": 96, "value_bits": 20, "input": "host"})
    with pytest.raises(ValueError, match="resident"):
        driver.setup(cell, 1, devices(), True)


def test_the_stream_check_passes_on_the_program_and_catches_a_departure(driver, reference):
    import jax

    pod = driver.build_pod(CONFIG, 4_000, devices())
    key = jax.random.PRNGKey(2**31 + 11)
    driver.check_streams(pod, reference, key, 4_000)   # three windows of 1024
    driver.check_streams(pod, reference, key, 96)      # narrower than a window

    shifted = types.SimpleNamespace(mask_stream=lambda seed, first, count, modulus:
                                    reference.mask_stream(seed, first + 1, count, modulus))
    with pytest.raises(RuntimeError, match="departs from the plain ChaCha20"):
        driver.check_streams(pod, shifted, key, 4_000)
    wide = types.SimpleNamespace(scheme=pod.scheme, modulus=pod.modulus, masking=types.SimpleNamespace(
        seed_bitsize=96))
    # a 96-bit seed fills three words: the reference keyed on three agrees
    driver.check_streams(wide, reference, key, 96)


def test_the_cell_states_what_the_driver_reports(driver):
    cell = harness.load_cell(harness.ROOT, "additive-chacha-1m")
    cell.traffic = {**cell.traffic, **cell.traffic["rehearsal"]}
    state = driver.setup(cell, 5, devices(), True)
    try:
        assert state.facts == {
            "participants": 16, "dim": 96, "padded": [16, 96], "elements_per_round": 16 * 96,
            "input_itemsize": 4, "share_count": 3, "scan_chunk": 8, "mesh": [1, 1],
            "pallas_active": False, "cost_model": "additive_chacha_round"}
        state.round(0)
        state.verify(0)
        assert state.finish() == 0
    finally:
        state.close()
    entry = next(w for w in SPEC["workloads"] if w["name"] == "additive-chacha-1m")
    assert entry["chips"] == 1 and entry["traffic"] == "resident-600x1m"
    assert "kernel is bypassed" in entry["why"] and "seed expansion" in entry["why"]
