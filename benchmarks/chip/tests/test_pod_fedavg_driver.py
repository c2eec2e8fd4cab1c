"""drivers/pod_fedavg.py: what it builds and what it refuses; the cell's
rehearsal end to end; references/fedavg.py against numbers worked by hand;
the three ``codec.*`` readers on a window made by hand and on a program
without their scopes or counters. No assertion here pins an entry's
position in BENCHMARK.json: the next cell is not trapped."""

import json
import types

import numpy as np
import pytest

import harness
import reduce
from reduce import scopes

SPEC = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
CONFIG = json.loads((harness.HERE / "configs" / "pod-fedavg-packed8.json").read_text())
CELL = "fedavg-f32-1m"
MS = 1_000_000  # nanoseconds
NEW = ("codec.encode_device_s_per_round", "codec.decode_device_s_per_round",
       "codec.host_bytes_per_round")
JOINED = ("mesh.host_s_per_round", "fields.device_s_per_round", "device.idle_share",
          "fields.fold_s_per_round", "fields.relayout_s_per_round",
          "fields.reconstruct_s_per_round", "fields.unscoped_s_per_round",
          "fields.hbm_floor_share", "sda.mask_share_roofline")
FACTS = {"participants": 1200, "dim": 999_999, "input_itemsize": 4, "secret_count": 3,
         "share_count": 8, "cost_model": "pod_round"}
P = 536870233


@pytest.fixture(scope="module")
def driver():
    import sys

    sys.path.insert(0, str(harness.ROOT))
    import sda_tpu  # noqa: F401  (x64 before jax is used)

    return harness.load_module(harness.HERE, "drivers", "pod_fedavg")


@pytest.fixture(scope="module")
def reference():
    return harness.load_module(harness.HERE, "references", "fedavg")


def devices():
    import jax

    return jax.devices()[:1]


def read(metric, window):
    return harness.load_module(harness.HERE, "layers", metric).read(window)


# -- the driver ------------------------------------------------------------------

def test_the_configuration_builds_the_pod_and_the_codec_a_user_would(driver):
    pod, codec = driver.build_pod(CONFIG, devices(), interpret=True)
    scheme = pod.scheme
    assert type(scheme).__name__ == "PackedShamirSharing"
    assert (scheme.secret_count, scheme.share_count, scheme.privacy_threshold,
            scheme.reconstruction_threshold, pod.modulus) == (3, 8, 4, 7, P)
    assert type(pod.masking).__name__ == "FullMasking"
    assert pod.pallas_active is True and pod._sp is not None
    assert pod.padded_shape(1200, 999_999) == (1200, 999_999)  # no pad at the cell's size
    assert pod.mesh.devices.shape == (1, 1)
    stated = CONFIG["codec"]
    assert (codec.modulus, codec.fractional_bits, codec.max_summands, codec.clip,
            codec.q_max) == (P, 16, 1200, 2.0, 131072) == (
        stated["modulus"], stated["fractional_bits"], stated["max_summands"],
        stated["clip"], stated["q_max"])
    assert codec.q_max <= (P // 2 - 1) // 1200 == 223695  # 1200 summands never wrap


@pytest.mark.parametrize("change, match", [
    ({"scheme": {"kind": "additive", "share_count": 3, "modulus": P}}, "packed_shamir"),
    ({"scheme": {**CONFIG["scheme"], "privacy_threshold": 3}}, "the program derives"),
    ({"masking": {"kind": "chacha", "seed_bitsize": 128}}, "full masking"),
    ({"mesh": "4x1"}, "default_mesh_shape"),
    ({"use_pallas": False}, "use_pallas true"),
    ({"codec": {**CONFIG["codec"], "modulus": (1 << 31) - 1}}, "two moduli"),
    ({"codec": {**CONFIG["codec"], "clip": 4.0}}, "exceeds the exactness capacity"),
])
def test_a_file_it_cannot_build_is_refused(driver, change, match):
    with pytest.raises(ValueError, match=match):
        driver.build_pod({**CONFIG, **change}, devices(), interpret=True)


@pytest.mark.parametrize("change", [{"input": "host"}, {"dtype": "int64"}])
def test_other_traffic_is_refused(driver, change):
    cell = types.SimpleNamespace(config=CONFIG, home=harness.HERE, traffic={
        "participants": 8, "dim": 96, "dtype": "float32", "input": "resident", **change})
    with pytest.raises(ValueError, match="float32 weights resident"):
        driver.setup(cell, 1, devices(), True)


def test_a_tree_without_the_device_decode_fails_at_once(driver, monkeypatch):
    """The parent commit with these files: nothing is built, nothing is put
    on the device, the process ends with a message and a code that is not 0."""
    from sda_tpu.models import FixedPointCodec

    monkeypatch.delattr(FixedPointCodec, "decode_mean_device")
    with pytest.raises(SystemExit, match="decode_mean_device"):
        driver.setup(None, 1, None, True)   # no cell, no device: it does not get there


def test_the_rehearsal_runs_end_to_end_and_states_the_cells_facts(driver):
    from sda_tpu.utils import metrics

    cell = harness.load_cell(harness.ROOT, CELL)
    assert cell.traffic["participants"] == 1200 and cell.traffic["dim"] == 999_999
    assert cell.traffic["trace_rounds"] == 6 and cell.traffic["input"] == "resident"
    assert cell.traffic["dtype"] == "float32"
    cell.traffic = {**cell.traffic, **cell.traffic["rehearsal"]}
    metrics.reset_counters()
    state = driver.setup(cell, 2**31 + 5, devices(), True)  # the integer check passed
    try:
        assert state.facts == {
            "participants": 16, "dim": 96, "padded": [16, 96], "elements_per_round": 16 * 96,
            "input_itemsize": 4, "secret_count": 3, "share_count": 8,
            "mesh": [1, 1], "pallas_active": True, "cost_model": "pod_round"}
        assert state.clients.shape == (16, 96) and str(state.clients.dtype) == "float32"
        beyond = np.abs(np.asarray(state.clients) - np.asarray(state.global_vec)) > 2.0
        assert 0.02 < beyond.mean() < 0.08   # about 4.6 % beyond the clip
        for index in range(2):
            state.round(index)
            state.verify(index)
            assert str(state.out.dtype) == "float32" and state.out.shape == (96,)
        assert state.finish() == 0
        window = harness.Window(facts=state.facts, chips=1, device_kind="cpu", setup_s=1.0)
        assert read("codec.host_bytes_per_round", window) == 0.0  # three rounds, no byte
        # a result off by a dropped row's worth is counted, not passed
        state.expected = state.expected + np.float32(2.0 / 16)
        state.verify(2)
        assert state.finish() == 1
    finally:
        state.close()


def test_the_entries_the_cell_brought():
    entry = next(w for w in SPEC["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "pod-fedavg-packed8", "resident-f32-1200x1m", 1)
    config = next(c for c in SPEC["configs"] if c["name"] == "pod-fedavg-packed8")
    assert config["source"] == CONFIG["source"] and config["reduced"] == CONFIG["reduced"]
    assert config["source"].endswith("README.md#L3-L15")
    assert CONFIG["architecture"] is None and len(CONFIG["guarantees"]) == 4
    assert (CONFIG["driver"], CONFIG["reference"]) == ("pod_fedavg", "fedavg")
    metrics = {m["name"]: m for m in SPEC["end_to_end"] + SPEC["per_layer"]}
    for name in ("round_s", "elements_per_s_per_chip") + JOINED:
        assert CELL in metrics[name]["workloads"], name
    for name in ("hostfed_round_s", "mesh.dispatch_s_per_round",
                 "fields.unbatch_s_per_round", "fields.mask_chacha_s_per_round"):
        assert CELL not in metrics[name]["workloads"], name
    for name in NEW:
        assert metrics[name]["workloads"] == [CELL] and metrics[name]["layer"] == "codec"
        assert (metrics[name]["moves"], metrics[name]["better"]) == ("round_s", "lower")
        assert (harness.HERE / "layers" / f"{name}.py").is_file()
    assert [metrics[name]["source"] for name in NEW] == [
        "device_trace", "device_trace", "program_counter"]
    assert [metrics[name]["unit"] for name in NEW] == ["s", "s", "bytes"]


# -- the reference ---------------------------------------------------------------

def test_the_reference_by_hand(reference):
    """Two clients, five elements, 4 fractional bits, clip 2: NaN -> 0, the
    clip both ways, ties to even, a sum that is negative (lifted from above
    p // 2) -- and the mean added in float64."""
    global_vec = np.float32([0.5, -1.0, 0.0, 0.25, 1.0])
    clients = np.float32([[np.nan, 5.0, 0.09375, -0.25, 1.0],      # deltas nan, 6, 3/32, -1/2, 0
                          [0.5625, -9.0, 0.15625, -2.0, 1.03125]])  # 1/16, -8, 5/32, -9/4, 1/32
    # x 16: nan -> 0, 1 | 32 (clipped), -32 | 1.5 -> 2, 2.5 -> 2 | -8, -32 | 0, 0.5 -> 0
    total = reference.integer_sum(global_vec, clients, P, 2.0, 4, rows=1)
    np.testing.assert_array_equal(total, [1, 0, 4, P - 40, 0])
    assert total.dtype == np.int64
    exact, mean = reference.new_global(global_vec, total, 2, P, 4)
    np.testing.assert_array_equal(mean, [1 / 32, 0, 4 / 32, -40 / 32, 0])
    np.testing.assert_array_equal(exact, [0.53125, -1.0, 0.125, -1.0, 1.0])
    limit = reference.tolerance(global_vec, mean)
    np.testing.assert_array_equal(limit, 2.0 ** -23 * np.float64(
        [0.5 + 1 / 16, 1.0, 0.25, 0.25 + 2.5, 1.0]))
    # any blocking, and jax.numpy, give the same integers
    import jax.numpy as jnp

    for rows in (2, 100):
        np.testing.assert_array_equal(
            reference.integer_sum(global_vec, clients, P, 2.0, 4, rows=rows), total)
    np.testing.assert_array_equal(np.asarray(reference.integer_sum(
        jnp.asarray(global_vec), jnp.asarray(clients), P, 2.0, 4, xp=jnp)), total)


def test_the_comparison_passes_a_rounding_and_refuses_what_it_must(reference):
    rng = np.random.default_rng(5)
    global_vec = rng.uniform(-1, 1, 4096).astype(np.float32)
    clients = (global_vec + rng.normal(size=(48, 4096))).astype(np.float32)
    total = reference.integer_sum(global_vec, clients, P, 2.0, 16)
    exact, mean = reference.new_global(global_vec, total, 48, P, 16)
    want, limit = exact.astype(np.float32), reference.tolerance(global_vec, mean)
    assert reference.outside(want, want, limit) == (0, 0, 0.0)
    # one unit of the last place off: a rounding, inside
    ulp = np.nextafter(want, np.float32(np.inf))
    outside, differ, share = reference.outside(ulp, want, limit)
    assert (outside, differ) == (0, 4096) and 0.3 < share <= 1.0
    # a dropped row, a leaked mask residue, a sum off by a hundred units
    dropped = reference.new_global(global_vec, reference.integer_sum(
        global_vec, clients[1:], P, 2.0, 16), 48, P, 16)[0]
    leaked = reference.new_global(global_vec, (total + 123456789) % P, 48, P, 16)[0]
    nudged = reference.new_global(global_vec, (total + 100) % P, 48, P, 16)[0]
    for wrong in (dropped, leaked, nudged):
        outside, _, share = reference.outside(wrong.astype(np.float32), want, limit)
        assert outside > 2048 and share > 100
    assert reference.outside(np.full_like(want, np.nan), want, limit)[0] == 4096
    # the precision below the configuration's: an encode in bfloat16
    import jax.numpy as jnp

    coarse = np.asarray(reference.integer_sum(
        jnp.asarray(global_vec), jnp.asarray(clients), P, 2.0, 16, xp=jnp,
        dtype=jnp.bfloat16))
    outside, _, share = reference.outside(
        reference.new_global(global_vec, coarse, 48, P, 16)[0].astype(np.float32),
        want, limit)
    assert outside > 4000 and share > 1000


# -- the readers -----------------------------------------------------------------

def traced_window(monkeypatch, encode_fused=True, scoped=True):
    """Three rounds of 100 ms. In each, on the device: the fold 5..15 (with
    the encode fused under its root, or the encode as an op of its own
    1..5), the kernel 15..50, the reconstruction 50..60 and the decode's
    root 60..61. ``scoped`` False: a program from before the two scopes."""
    rounds, ops, events = [], [], []
    for r in range(3):
        t = 100 * r * MS
        rounds.append((t, t + 90 * MS))
        timeline = [
            ("fusion.6", "jit(program)/sda.fold/reduce:", 5, 15),
            ("sda.mask_share.1 u32[8,333824]", "jit(program)/sda.mask_share/pallas_call:", 15, 50),
            ("fusion.9", "jit(program)/sda.reconstruct/sda.reconstruct.lagrange/mul:", 50, 60)]
        if scoped:
            timeline.append(("multiply_add_fusion", "jit(program)/sda.decode/add:", 60, 61))
            if not encode_fused:
                timeline.append(("fusion.1", "jit(program)/sda.encode/round:", 1, 5))
        for name, tf_op, start, end in timeline:
            ops.append((name, t + start * MS, t + end * MS))
            events.append((tf_op, t + start * MS, t + end * MS))
    trace = reduce.Reduced(window_ns=(0, 300 * MS), devices={0: ops},
                           annotations=[("bench.round", lo, hi) for lo, hi in rounds],
                           rounds=rounds)
    monkeypatch.setattr(scopes, "newest_trace", lambda out: "made by hand")
    monkeypatch.setattr(scopes, "device_events", lambda path, chips: {0: events})
    return harness.Window(facts=FACTS, chips=1, device_kind="TPU v5 lite", setup_s=1.0,
                          attempted=3, trace=trace)


def test_the_scope_readers_on_a_window_made_by_hand(monkeypatch):
    fused = traced_window(monkeypatch)
    # no op carries sda.encode, its sibling is on the trace: 0, not None
    assert read("codec.encode_device_s_per_round", fused) == 0.0
    assert read("codec.decode_device_s_per_round", fused) == pytest.approx(0.001)
    assert read("fields.fold_s_per_round", fused) == pytest.approx(0.010)
    apart = traced_window(monkeypatch, encode_fused=False)
    assert read("codec.encode_device_s_per_round", apart) == pytest.approx(0.004)
    # the joined readers read this cell with the functions they have
    assert read("fields.device_s_per_round", apart) == pytest.approx(0.060)
    assert read("sda.mask_share_roofline", apart) == pytest.approx(
        100 * (4 * 1200 * 999_999 + 4 * 8 * 333_333 + 4 * 999_999) / 819e9 / 0.035)
    assert read("fields.hbm_floor_share", apart) == pytest.approx(
        (4 * 1200 * 999_999 + 8 * 8 * 333_333 + 16 * 999_999) / 819e9 / 0.060)


@pytest.mark.parametrize("metric", NEW[:2])
def test_a_scope_reader_returns_none_without_the_scopes(monkeypatch, metric):
    assert read(metric, traced_window(monkeypatch, scoped=False)) is None
    untraced = harness.Window(facts=FACTS, chips=1, device_kind="TPU v5 lite", setup_s=1.0)
    assert read(metric, untraced) is None


def test_the_counter_reader():
    from sda_tpu.utils import metrics

    window = harness.Window(facts=FACTS, chips=1, device_kind="TPU v5 lite", setup_s=1.0)
    metrics.reset_counters()
    assert read("codec.host_bytes_per_round", window) is None   # a program without them
    metrics.count("models.fedavg.rounds", 4)
    metrics.count("models.fedavg.host_bytes", 0)
    assert read("codec.host_bytes_per_round", window) == 0.0
    metrics.count("models.fedavg.host_bytes", 4 * (16 * 96 * 4 + 96 * 8))
    assert read("codec.host_bytes_per_round", window) == 16 * 96 * 4 + 96 * 8
    metrics.reset_counters()
