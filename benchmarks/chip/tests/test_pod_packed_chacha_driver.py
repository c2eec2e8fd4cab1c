"""drivers/pod_packed_chacha.py: what it builds and what it refuses; the
cell's rehearsal end to end; the five readers that came with the cell on a
window made by hand and on a program without their scope; and
costs/packed_chacha.py against numbers worked by hand. No assertion here
pins an entry's position in BENCHMARK.json: the next cell is not trapped."""

import json
import types

import pytest

import costs
import harness
import reduce
from costs import packed_chacha
from reduce import scopes

SPEC = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
CONFIG = json.loads((harness.HERE / "configs" / "pod-packed8-chacha.json").read_text())
CELL = "packed-chacha-1m"
MS = 1_000_000  # nanoseconds
NEW = ("fields.mask_relayout_s_per_round", "fields.mask_fold_s_per_round",
       "fields.share_kernel_s_per_round", "sda.share_kernel_roofline",
       "fields.packed_chacha_hbm_floor_share")
JOINED = ("mesh.host_s_per_round", "fields.device_s_per_round", "device.idle_share",
          "fields.mask_chacha_s_per_round", "fields.mask_reduce_s_per_round",
          "fields.chacha_blocks_per_s")
FACTS = {"participants": 1200, "dim": 999_999, "input_itemsize": 4, "secret_count": 3,
         "share_count": 8, "cost_model": "packed_chacha_round"}


@pytest.fixture(scope="module")
def driver():
    import sys

    sys.path.insert(0, str(harness.ROOT))
    import sda_tpu  # noqa: F401  (x64 before jax is used)

    return harness.load_module(harness.HERE, "drivers", "pod_packed_chacha")


def devices():
    import jax

    return jax.devices()[:1]


def read(metric, window):
    return harness.load_module(harness.HERE, "layers", metric).read(window)


# -- the driver ------------------------------------------------------------------

def test_the_configuration_builds_the_pod_a_user_would(driver):
    pod = driver.build_pod(CONFIG, 999_999, devices(), interpret=True)
    scheme = pod.scheme
    assert type(scheme).__name__ == "PackedShamirSharing"
    assert (scheme.secret_count, scheme.share_count, scheme.privacy_threshold,
            scheme.reconstruction_threshold, pod.modulus) == (3, 8, 4, 7, 536870233)
    assert type(pod.masking).__name__ == "ChaChaMasking"
    assert (pod.masking.seed_bitsize, pod.masking.dimension) == (128, 999_999)
    assert pod.pallas_active is True and pod._sp is not None
    assert pod.padded_shape(1200, 999_999) == (1200, 1_000_008)  # the grain lcm(3, 8)
    assert pod.mesh.devices.shape == (1, 1)


@pytest.mark.parametrize("change, match", [
    ({"scheme": {"kind": "additive", "share_count": 3, "modulus": 536870233}}, "packed Shamir"),
    ({"scheme": "packed_shamir"}, "packed Shamir"),
    ({"scheme": {**CONFIG["scheme"], "privacy_threshold": 3}}, "the program derives"),
    ({"masking": "full"}, "ChaCha seed masks"),
    ({"masking": {"kind": "full"}}, "ChaCha seed masks"),
    ({"mesh": "4x1"}, "default_mesh_shape"),
    ({"use_pallas": False}, "use_pallas true"),
])
def test_a_file_it_cannot_build_is_refused(driver, change, match):
    with pytest.raises(ValueError, match=match):
        driver.build_pod({**CONFIG, **change}, 96, devices(), interpret=True)


def test_host_fed_traffic_is_refused(driver):
    cell = types.SimpleNamespace(config=CONFIG, home=harness.HERE, traffic={
        "participants": 8, "dim": 96, "value_bits": 20, "input": "host"})
    with pytest.raises(ValueError, match="resident"):
        driver.setup(cell, 1, devices(), True)


def test_the_rehearsal_runs_end_to_end_and_states_the_cells_facts(driver):
    cell = harness.load_cell(harness.ROOT, CELL)
    assert cell.traffic["participants"] == 1200 and cell.traffic["dim"] == 999_999
    assert cell.traffic["trace_rounds"] == 6 and cell.traffic["input"] == "resident"
    cell.traffic = {**cell.traffic, **cell.traffic["rehearsal"]}
    state = driver.setup(cell, 2**31 + 5, devices(), True)  # the stream check passed
    try:
        assert state.facts == {
            "participants": 16, "dim": 96, "padded": [16, 96], "elements_per_round": 16 * 96,
            "input_itemsize": 4, "secret_count": 3, "share_count": 8, "privacy_threshold": 4,
            "mesh": [1, 1], "pallas_active": True, "cost_model": "packed_chacha_round"}
        for index in range(2):
            state.round(index)
            state.verify(index)
        assert state.finish() == 0
        state.expected = state.expected + 1  # a wrong sum is counted, not passed
        state.verify(2)
        assert state.finish() == 1
    finally:
        state.close()


def test_the_entries_the_cell_brought():
    entry = next(w for w in SPEC["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "pod-packed8-chacha", "resident-1200x1m-chip1", 1)
    config = next(c for c in SPEC["configs"] if c["name"] == "pod-packed8-chacha")
    assert config["source"] == CONFIG["source"] and config["reduced"] == CONFIG["reduced"]
    assert CONFIG["architecture"] is None and len(CONFIG["guarantees"]) == 3
    metrics = {m["name"]: m for m in SPEC["end_to_end"] + SPEC["per_layer"]}
    for name in ("round_s", "elements_per_s_per_chip") + JOINED:
        assert CELL in metrics[name]["workloads"], name
    for name in ("mesh.dispatch_s_per_round", "sda.mask_share_roofline",
                 "fields.hbm_floor_share", "fields.share_s_per_round"):
        assert CELL not in metrics[name]["workloads"], name
    for name in NEW:
        assert metrics[name]["workloads"] == [CELL] and metrics[name]["layer"] == "fields"
        assert metrics[name]["source"] == "device_trace"
        assert (harness.HERE / "layers" / f"{name}.py").is_file()
    assert [metrics[name]["moves"] for name in NEW] == [
        "round_s", "round_s", "round_s", "elements_per_s_per_chip", "elements_per_s_per_chip"]
    assert (metrics["sda.share_kernel_roofline"]["unit"],
            metrics["sda.share_kernel_roofline"]["better"]) == ("%", "higher")


# -- the readers -----------------------------------------------------------------

def traced_window(monkeypatch, scoped=True, facts=FACTS):
    """Three rounds of 300 ms. In each, on the device: the mask pass's
    ``while`` 10..210 enclosing one block's cipher 10..100, reduction
    100..110, layout change 110..180 and fold 180..200; the add of the
    masks' sum 210..212; the kernel 215..265; reconstruction 265..275.
    ``scoped`` False: the same ops as the parent names them, the fold and
    the add directly under ``sda.mask``."""
    fold = "sda.mask/sda.mask.fold" if scoped else "sda.mask"
    rounds, ops, events = [], [], []
    for r in range(3):
        t = 300 * r * MS
        rounds.append((t, t + 290 * MS))
        for name, tf_op, start, end in [
                ("while.3", "jit(r)/sda.mask/while:", 10, 210),
                ("fusion.1", "jit(r)/sda.mask/while/body/sda.mask.chacha/xor:", 10, 100),
                ("fusion.2", "jit(r)/sda.mask/while/body/sda.mask.reduce/add:", 100, 110),
                ("fusion.3", "jit(r)/sda.mask/while/body/sda.mask.relayout/dot_general:", 110, 180),
                ("fusion.4", f"jit(r)/sda.mask/while/body/{fold}/reduce_sum:", 180, 200),
                ("fusion.5", f"jit(r)/{fold}/add:", 210, 212),
                ("sda.mask_share.1 u32[8,333440]", "jit(r)/sda.mask_share/pallas_call:", 215, 265),
                ("fusion.6", "jit(r)/sda.reconstruct/dot_general:", 265, 275)]:
            ops.append((name, t + start * MS, t + end * MS))
            events.append((tf_op, t + start * MS, t + end * MS))
    trace = reduce.Reduced(window_ns=(0, 900 * MS), devices={0: ops},
                           annotations=[("bench.round", lo, hi) for lo, hi in rounds],
                           rounds=rounds)
    monkeypatch.setattr(scopes, "newest_trace", lambda out: "made by hand")
    monkeypatch.setattr(scopes, "device_events", lambda path, chips: {0: events})
    return harness.Window(facts=facts, chips=1, device_kind="TPU v5 lite", setup_s=1.0,
                          attempted=3, trace=trace)


def test_the_new_readers_give_the_right_quotients(monkeypatch):
    window = traced_window(monkeypatch)
    assert read("fields.mask_relayout_s_per_round", window) == pytest.approx(0.070)
    assert read("fields.mask_fold_s_per_round", window) == pytest.approx(0.022)
    assert read("fields.share_kernel_s_per_round", window) == pytest.approx(0.050)
    kernel_floor_s = (4 * 1200 * 999_999 + 4 * 8 * 333_333) / 819e9
    assert read("sda.share_kernel_roofline", window) == pytest.approx(100 * kernel_floor_s / 0.050)
    round_floor_s = packed_chacha.round(1200, 999_999, 4, 3, 8)["hbm_bytes"] / 819e9
    # device seconds of a round: the union 10..212, 215..275
    assert read("fields.packed_chacha_hbm_floor_share", window) == pytest.approx(
        round_floor_s / 0.262)
    # the joined readers read the same scopes in this cell
    assert read("fields.mask_chacha_s_per_round", window) == pytest.approx(0.090)
    assert read("fields.mask_reduce_s_per_round", window) == pytest.approx(0.010)
    assert read("fields.device_s_per_round", window) == pytest.approx(0.262)


@pytest.mark.parametrize("metric", NEW)
def test_a_new_reader_returns_none_without_its_scope_or_counter(monkeypatch, metric):
    """The parent's program (no ``sda.mask.fold``), another cell's cost
    model, a window with no kernel op, and an untraced run."""
    parent = traced_window(monkeypatch, scoped=False)
    if metric == "fields.mask_fold_s_per_round":
        assert read(metric, parent) is None
    other = traced_window(monkeypatch, facts={**FACTS, "cost_model": "pod_round"})
    if metric not in ("fields.mask_relayout_s_per_round", "fields.mask_fold_s_per_round"):
        assert read(metric, other) is None
    bare = traced_window(monkeypatch)
    bare.trace.devices[0][:] = [op for op in bare.trace.devices[0] if op[0] == "fusion.6"]
    monkeypatch.setattr(scopes, "device_events", lambda path, chips: {
        0: [("jit(r)/sda.reconstruct/dot_general:", 265 * MS, 275 * MS)]})
    if metric != "fields.packed_chacha_hbm_floor_share":
        assert read(metric, bare) is None
    untraced = harness.Window(facts=FACTS, chips=1, device_kind="TPU v5 lite", setup_s=1.0)
    assert read(metric, untraced) is None


# -- the costs -------------------------------------------------------------------

def test_the_kernel_floor_counts_the_residues_once_and_the_share_rows():
    cost = packed_chacha.kernel(participants=1200, dim=999_999, secret_count=3, share_count=8)
    # 1200 x 999,999 x 4 B read, [8, 333,333] share rows written, no mask total
    assert cost["hbm_bytes"] == 4_799_995_200 + 4 * 8 * 333_333 == 4_810_661_856
    # what costs.fused_mask_share counts, less the mask's draw and its add
    assert packed_chacha.KERNEL_OPS_PER_ELEMENT == {"share_randomness": 47, "fold": 6}
    assert cost["vpu_ops"] == 1200 * 999_999 * 53 + 999_999 * 250
    masked = costs.fused_mask_share(1200, 999_999, 3, 8)
    assert masked["hbm_bytes"] - cost["hbm_bytes"] == 4 * 999_999
    assert masked["vpu_ops"] - cost["vpu_ops"] == 1200 * 999_999 * (35 + 8)


def test_the_round_floor_counts_each_array_once():
    cost = packed_chacha.round(participants=1200, dim=999_999, input_itemsize=4,
                               secret_count=3, share_count=8)
    # the input once; share rows [8, d/3] and the masks' sum [d] written and
    # read once as uint32; the aggregate [d] as int64
    assert cost["hbm_bytes"] == (4_799_995_200 + 2 * 4 * 8 * 333_333
                                 + 2 * 4 * 999_999 + 8 * 999_999) == 4_837_328_496
    assert cost["elements"] == 1_199_998_800 and cost["chacha_blocks"] == 1200 * 125_000
    per_element = (4 + 202 + 25 + 3 + 3) + (47 + 6)
    assert cost["vpu_ops"] == 1_199_998_800 * per_element + 999_999 * 253
    quarter = packed_chacha.round(1200, 999_999, 4, 3, 8, chips=4)
    assert quarter["elements"] == 300 * 999_999
    # a lower bound: over the chip's HBM rate the floor is 5.9 ms a round
    assert cost["hbm_bytes"] / 819e9 == pytest.approx(0.0059064, rel=1e-4)
