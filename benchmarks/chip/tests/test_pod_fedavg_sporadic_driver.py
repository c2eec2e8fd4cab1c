"""drivers/pod_fedavg_sporadic.py: the schedule it draws, what it refuses,
the cell's rehearsal end to end (every set of the schedule, one program);
references/fedavg_reported.py against numbers worked by hand; the two
counter readers. No assertion here pins an entry's position in
BENCHMARK.json: the next cell is not trapped."""

import json
import types

import numpy as np
import pytest

import harness

SPEC = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
CONFIG = json.loads(
    (harness.HERE / "configs" / "pod-fedavg-packed8-sporadic.json").read_text())
PARENT = json.loads((harness.HERE / "configs" / "pod-fedavg-packed8.json").read_text())
CELL = "fedavg-sporadic-1m"
NEW = ("codec.reported_rows_per_round", "mesh.round_builds")
JOINED = ("mesh.host_s_per_round", "fields.device_s_per_round", "device.idle_share",
          "fields.fold_s_per_round", "fields.relayout_s_per_round",
          "fields.reconstruct_s_per_round", "fields.unscoped_s_per_round",
          "fields.hbm_floor_share", "sda.mask_share_roofline",
          "codec.encode_device_s_per_round", "codec.decode_device_s_per_round",
          "codec.host_bytes_per_round")
P = 536870233


@pytest.fixture(scope="module")
def driver():
    import sys

    sys.path.insert(0, str(harness.ROOT))
    import sda_tpu  # noqa: F401  (x64 before jax is used)

    return harness.load_module(harness.HERE, "drivers", "pod_fedavg_sporadic")


@pytest.fixture(scope="module")
def reference():
    return harness.load_module(harness.HERE, "references", "fedavg_reported")


def devices():
    import jax

    return jax.devices()[:1]


def read(metric, window):
    return harness.load_module(harness.HERE, "layers", metric).read(window)


# -- the schedule ----------------------------------------------------------------

@pytest.mark.parametrize("seed", [1, 2**31 + 6353])
def test_the_schedule_is_the_seeds_and_its_counts_are_distinct(driver, seed):
    sets = driver.reporter_sets(seed, 1200, 32, 923)
    assert sets.shape == (32, 1200) and sets.dtype == bool
    counts = sets.sum(axis=1)
    assert len(set(counts)) == 32 and counts.min() >= 923 and counts.max() <= 1200
    np.testing.assert_array_equal(sets, driver.reporter_sets(seed, 1200, 32, 923))
    assert (sets != driver.reporter_sets(seed + 1, 1200, 32, 923)).any()
    # one count a stratum of the range, so the schedule's mean holds still
    edges = np.ceil(np.linspace(923, 1201, 33)).astype(int)
    assert ((np.sort(counts) >= edges[:-1]) & (np.sort(counts) < edges[1:])).all()
    assert abs(counts.mean() - 1061.5) < 2
    assert (counts != np.sort(counts)).any()   # shuffled: the window's first sets are no trend
    # every row is out of some set and in most: no row is a fixture
    assert sets.all(axis=0).sum() < 200 and sets.any(axis=0).all()


# -- the driver ------------------------------------------------------------------

def test_the_configuration_is_the_fixed_cohorts_plus_the_reporters():
    for key in ("scheme", "masking", "use_pallas", "mesh", "layout", "codec",
                "reduced", "environment", "architecture"):
        assert CONFIG[key] == PARENT[key], key
    assert CONFIG["architecture"] is None
    assert (CONFIG["driver"], CONFIG["reference"]) == (
        "pod_fedavg_sporadic", "fedavg_reported")
    assert CONFIG["source"].endswith("server/src/snapshot.rs#L4-L47")
    assert len(CONFIG["guarantees"]) == 5 and "no bit" in CONFIG["guarantees"][4]
    assert "1902.01046" in CONFIG["assumed"]["report_rate"]
    assert {k: v for k, v in CONFIG["assumed"].items() if k != "report_rate"} \
        == PARENT["assumed"]


@pytest.mark.parametrize("change, match", [
    ({"input": "host"}, "float32 weights resident"),
    ({"dtype": "int64"}, "float32 weights resident"),
    ({"sets": 6}, "6 sets of distinct counts do not fit"),
])
def test_other_traffic_is_refused(driver, change, match):
    cell = types.SimpleNamespace(config=CONFIG, home=harness.HERE, traffic={
        "participants": 16, "dim": 96, "dtype": "float32", "input": "resident",
        "sets": 4, "over_selection": 1.3, **change})
    with pytest.raises(ValueError, match=match):
        driver.setup(cell, 1, devices(), True)


def test_a_tree_whose_round_takes_no_reported_fails_at_once(driver, monkeypatch):
    """The parent commit with these files: nothing is built, nothing is put
    on the device, the process ends with a message and a code that is not 0."""
    import sda_tpu.models

    monkeypatch.setattr(
        sda_tpu.models, "pod_fedavg_round",
        lambda pod, codec, global_vec, client_vecs, key=None: None)
    with pytest.raises(SystemExit, match="reported=") as raised:
        driver.setup(None, 1, None, True)   # no cell, no device: it does not get there
    assert raised.value.code not in (0, None)


def test_the_rehearsal_meets_every_set_with_one_program(driver):
    from sda_tpu.utils import metrics

    cell = harness.load_cell(harness.ROOT, CELL)
    assert (cell.traffic["participants"], cell.traffic["dim"]) == (1200, 999_999)
    assert (cell.traffic["sets"], cell.traffic["over_selection"]) == (32, 1.3)
    assert cell.traffic["trace_rounds"] == 6 and cell.traffic["input"] == "resident"
    cell.traffic = {**cell.traffic, **cell.traffic["rehearsal"]}
    metrics.reset_counters()
    state = driver.setup(cell, 2**31 + 5, devices(), True)  # the integer check passed
    try:
        counts = state.reported.sum(axis=1)
        assert state.reported.shape == (4, 16) and len(set(counts)) == 4
        assert counts.min() >= 12   # int(16 / 1.3)
        assert state.facts == {
            "participants": 16, "dim": 96, "padded": [16, 96],
            "elements_per_round": counts.mean() * 96, "reporter_sets": 4,
            "reporters": [counts.min(), counts.max()],
            "input_itemsize": 4, "secret_count": 3, "share_count": 8,
            "mesh": [1, 1], "pallas_active": True, "cost_model": "pod_round"}
        assert state.clients.shape == (16, 96) and str(state.clients.dtype) == "float32"
        assert [v.shape for v in state.expected + state.limits] == [(96,)] * 8
        # set-up built two programs: the integer check's round and the cell's
        window = harness.Window(facts=state.facts, chips=1, device_kind="cpu", setup_s=1.0)
        assert read("mesh.round_builds", window) == 2
        for index in range(9):   # every set, twice; set 0 a third time
            state.round(index)
            state.verify(index)
            assert str(state.out.dtype) == "float32" and state.out.shape == (96,)
        assert state.finish() == 0
        assert read("mesh.round_builds", window) == 2
        assert read("codec.host_bytes_per_round", window) == 16.0   # who reported, a byte a row
        rounds = [0, 0, 1, 2, 3, 0, 1, 2, 3, 0]   # the warm-up, then i mod 4
        assert read("codec.reported_rows_per_round", window) == counts[rounds].mean()
        # a program obtained after set-up is a failed round
        import jax

        jax.jit(lambda x: x * 3 + 1)(np.arange(5)).block_until_ready()
        assert state.finish() == 1
        state.warm = state.compiles.requests
        # a round held to another set's vector is counted, not passed
        state.verify(1)   # state.out is set 0's
        assert state.finish() == 1
    finally:
        state.close()


def test_the_entries_the_cell_brought():
    entry = next(w for w in SPEC["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "pod-fedavg-packed8-sporadic", "resident-f32-sporadic-1200x1m", 1)
    config = next(c for c in SPEC["configs"] if c["name"] == CONFIG["name"])
    assert config["source"] == CONFIG["source"] and config["reduced"] == CONFIG["reduced"]
    assert config["file"].endswith("configs/pod-fedavg-packed8-sporadic.json")
    metrics = {m["name"]: m for m in SPEC["end_to_end"] + SPEC["per_layer"]}
    for name in ("round_s", "elements_per_s_per_chip") + JOINED:
        assert CELL in metrics[name]["workloads"], name
    for name in ("hostfed_round_s", "mesh.dispatch_s_per_round",
                 "fields.unbatch_s_per_round", "fields.mask_chacha_s_per_round"):
        assert CELL not in metrics[name]["workloads"], name
    for name in NEW:
        assert metrics[name]["workloads"] == [CELL]
        assert metrics[name]["source"] == "program_counter"
        assert (harness.HERE / "layers" / f"{name}.py").is_file()
    assert [(metrics[n]["layer"], metrics[n]["moves"], metrics[n]["unit"]) for n in NEW] == [
        ("codec", "elements_per_s_per_chip", "rows"), ("mesh", "round_s", "count")]


# -- the reference ---------------------------------------------------------------

def test_the_reference_by_hand(reference):
    """Three clients of which the second did not report, five elements, 4
    fractional bits, clip 2: NaN -> 0, the clip both ways, ties to even, a
    sum that is negative -- and the mean over TWO rows, added in float64."""
    global_vec = np.float32([0.5, -1.0, 0.0, 0.25, 1.0])
    clients = np.float32([[np.nan, 5.0, 0.09375, -0.25, 1.0],      # deltas nan, 6, 3/32, -1/2, 0
                          [np.nan, np.inf, -np.inf, 3e38, 7.0],    # did not report
                          [0.5625, -9.0, 0.15625, -2.0, 1.03125]])  # 1/16, -8, 5/32, -9/4, 1/32
    reported = np.array([True, False, True])
    total = reference.integer_sum(global_vec, clients, reported, P, 2.0, 4, rows=1)
    np.testing.assert_array_equal(total, [1, 0, 4, P - 40, 0])
    assert total.dtype == np.int64
    exact, mean = reference.new_global(global_vec, total, 2, P, 4)
    np.testing.assert_array_equal(mean, [1 / 32, 0, 4 / 32, -40 / 32, 0])
    np.testing.assert_array_equal(exact, [0.53125, -1.0, 0.125, -1.0, 1.0])
    np.testing.assert_array_equal(
        reference.tolerance(global_vec, mean),
        2.0 ** -23 * np.float64([0.5 + 1 / 16, 1.0, 0.25, 0.25 + 2.5, 1.0]))
    # any blocking, and jax.numpy, give the same integers; nobody: the vector holds
    import jax.numpy as jnp

    for rows in (2, 100):
        np.testing.assert_array_equal(reference.integer_sum(
            global_vec, clients, reported, P, 2.0, 4, rows=rows), total)
    np.testing.assert_array_equal(np.asarray(reference.integer_sum(
        jnp.asarray(global_vec), jnp.asarray(clients), jnp.asarray(reported),
        P, 2.0, 4, xp=jnp)), total)
    nobody = reference.integer_sum(global_vec, clients, np.zeros(3, bool), P, 2.0, 4)
    np.testing.assert_array_equal(nobody, 0)
    np.testing.assert_array_equal(
        reference.new_global(global_vec, nobody, 0, P, 4)[0], global_vec)


def test_the_comparison_refuses_a_round_that_ignores_who_reported(reference):
    rng = np.random.default_rng(5)
    global_vec = rng.uniform(-1, 1, 4096).astype(np.float32)
    clients = (global_vec + rng.normal(size=(48, 4096))).astype(np.float32)
    reported = np.arange(48) % 6 != 0            # 40 of 48
    args = (P, 2.0, 16)
    total = reference.integer_sum(global_vec, clients, reported, *args)
    exact, mean = reference.new_global(global_vec, total, 40, P, 16)
    want, limit = exact.astype(np.float32), reference.tolerance(global_vec, mean)
    assert reference.outside(want, want, limit) == (0, 0, 0.0)
    ulp = np.nextafter(want, np.float32(np.inf))   # a rounding: inside
    assert reference.outside(ulp, want, limit)[0] == 0
    everyone = reference.integer_sum(global_vec, clients, np.ones(48, bool), *args)
    wrong = {
        "summed every row": reference.new_global(global_vec, everyone, 40, P, 16)[0],
        "divided by the buffer's rows": reference.new_global(global_vec, total, 48, P, 16)[0],
        "both": reference.new_global(global_vec, everyone, 48, P, 16)[0],
    }
    for what, vector in wrong.items():
        outside, _, share = reference.outside(vector.astype(np.float32), want, limit)
        assert outside > 2048 and share > 1000, what
    # the precision below the configuration's: an encode in bfloat16
    import jax.numpy as jnp

    coarse = np.asarray(reference.integer_sum(
        jnp.asarray(global_vec), jnp.asarray(clients), jnp.asarray(reported),
        *args, xp=jnp, dtype=jnp.bfloat16))
    outside, _, share = reference.outside(
        reference.new_global(global_vec, coarse, 40, P, 16)[0].astype(np.float32),
        want, limit)
    assert outside > 4000 and share > 1000


# -- the readers -----------------------------------------------------------------

def test_the_counter_readers():
    from sda_tpu.utils import metrics

    window = harness.Window(facts={}, chips=1, device_kind="TPU v5 lite", setup_s=1.0)
    metrics.reset_counters()
    for name in NEW:                 # a program without the counters: the parent
        assert read(name, window) is None
    metrics.count("models.fedavg.rounds", 4)
    assert read("codec.reported_rows_per_round", window) is None   # a fixed cohort's cell
    metrics.count("models.fedavg.reported_rows", 4 * 1061)
    assert read("codec.reported_rows_per_round", window) == 1061.0
    metrics.count("mesh.round.builds", 2)
    assert read("mesh.round_builds", window) == 2
    metrics.reset_counters()
