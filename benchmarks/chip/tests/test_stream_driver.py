"""drivers/stream.py: what it builds, what it refuses, its inputs and its
set-up at toy size; the ``stream.*`` readers on a window built by hand and
on a window of a program that has none of their spans; and the entries of
``BENCHMARK.json`` that name them."""

import json
import types

import numpy as np
import pytest

import harness
import reduce

MS = 1_000_000  # nanoseconds
SPEC = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
CONFIG = json.loads((harness.HERE / "configs" / "stream-packed8.json").read_text())
SPAN_METRICS = {"stream.feed_s_per_round": "stream.feed",
                "stream.dispatch_s_per_round": "stream.dispatch",
                "stream.sync_s_per_round": "stream.steps_sync",
                "stream.finale_s_per_round": "stream.finale",
                "stream.readback_s_per_round": "stream.readback"}
TRACE_METRICS = ("stream.device_s_per_round", "stream.idle_share",
                 "stream.acc_s_per_round")
ALL = (*SPAN_METRICS, *TRACE_METRICS, "stream.h2d_bytes_per_round",
       "stream.h2d_bytes_per_s")


@pytest.fixture(scope="module")
def driver():
    import sys

    sys.path.insert(0, str(harness.ROOT))
    import sda_tpu  # noqa: F401  (x64 before jax is used)

    return harness.load_module(harness.HERE, "drivers", "stream")


def devices():
    import jax

    return jax.devices()[:1]


def read(metric, window):
    return harness.load_module(harness.HERE, "layers", metric).read(window)


# -- the driver ----------------------------------------------------------------

def test_the_configuration_builds_the_aggregator_a_user_would(driver):
    from sda_tpu.mesh import streaming

    agg = driver.build_aggregator({**CONFIG, "use_pallas": False})
    assert type(agg).__name__ == "StreamingAggregator"
    scheme = agg.scheme
    assert (scheme.secret_count, scheme.share_count, scheme.privacy_threshold,
            scheme.prime_modulus) == (3, 8, 4, 536870233)
    assert type(agg.masking).__name__ == "FullMasking"
    assert agg.participants_chunk == 300 and agg.dim_chunk == 3 * (1 << 20)
    assert agg.pallas_active is False and agg._sp is not None and not agg.uniform_tail
    assert CONFIG["blocks_in_flight"] == streaming.BLOCKS_IN_FLIGHT == 2
    # the file's own call: the kernel, interpreted off the chip
    kernel = driver.build_aggregator(CONFIG, interpret=True)
    assert kernel.pallas_active is True and kernel.participants_chunk == 300


@pytest.mark.parametrize("change, match", [
    ({"masking": "chacha"}, "full masking"),
    ({"dim_chunk": 4096}, "dim_chunk"),
    ({"layout": "4 chips"}, "one chip without a mesh"),
    ({"blocks_in_flight": 4}, "blocks in flight"),
    ({"scheme": {**CONFIG["scheme"], "kind": "additive"}}, "packed_shamir"),
    ({"scheme": {**CONFIG["scheme"], "privacy_threshold": 3}}, "the program derives"),
])
def test_a_file_it_cannot_hold_is_refused(driver, change, match):
    with pytest.raises(ValueError, match=match):
        driver.build_aggregator({**CONFIG, **change})


def test_a_program_that_states_no_bound_is_refused(driver, monkeypatch):
    from sda_tpu.mesh import streaming

    monkeypatch.delattr(streaming, "BLOCKS_IN_FLIGHT")
    with pytest.raises(ValueError, match="BLOCKS_IN_FLIGHT is None"):
        driver.build_aggregator(CONFIG, interpret=True)


def test_resident_traffic_and_a_mesh_are_refused(driver):
    traffic = {"participants": 8, "dim": 96, "value_bits": 20, "input": "resident"}
    cell = types.SimpleNamespace(config=CONFIG, home=harness.HERE, traffic=traffic)
    with pytest.raises(ValueError, match="host matrix"):
        driver.setup(cell, 1, devices(), True)
    cell.traffic = {**traffic, "input": "host"}
    with pytest.raises(ValueError, match="one chip"):
        driver.setup(cell, 1, devices() * 4, True)


def test_the_inputs_come_from_the_seed_alone(driver):
    big = 2**31 + 12345                      # the driver's seeds are large
    a = driver.host_inputs(big, 250, 33, 20)
    b = driver.host_inputs(big, 250, 33, 20)
    assert a.dtype == np.int64 and a.shape == (250, 33) and a.flags.c_contiguous
    assert np.array_equal(a, b) and 0 <= a.min() and a.max() < 1 << 20
    assert a.max() > 1 << 19 and len(np.unique(a[:, 0])) > 200
    assert not np.array_equal(a, driver.host_inputs(big + 1, 250, 33, 20))
    # rows come in threads' blocks: none repeats another's stream
    assert not np.array_equal(a[:100], a[100:200])


def test_the_cell_states_what_the_driver_reports(driver):
    cell = harness.load_cell(harness.ROOT, "packed-1m-streamed")
    assert (cell.traffic["participants"], cell.traffic["dim"]) == (1200, 999_999)
    assert cell.traffic["trace_rounds"] == 4 and cell.traffic["input"] == "host"
    cell.traffic = {**cell.traffic, **cell.traffic["rehearsal"]}
    from sda_tpu.utils import metrics

    metrics.reset_counters()
    state = driver.setup(cell, 2**31 + 5, devices(), True)
    try:
        # the toy shape streams three blocks, the last one ragged
        assert state.facts == {
            "participants": 8, "dim": 96, "elements_per_round": 8 * 96,
            "input_itemsize": 8, "bytes_per_round": 8 * 96 * 8,
            "participants_chunk": 3, "dim_chunk": 3 * (1 << 20),
            "blocks_per_round": 3, "blocks_in_flight": 2, "secret_count": 3,
            "share_count": 8, "pallas_active": True}
        assert sorted(state.agg._steps) == [(2, 96), (3, 96)]
        state.round(0)
        state.verify(0)
        assert isinstance(state.out, np.ndarray) and state.finish() == 0
        state.expected = state.expected + 1      # an inexact round is counted
        state.verify(0)
        assert state.finish() == 1
        # warm-up and one round: what stream.h2d_bytes_per_round divides
        window = harness.Window(facts=state.facts, chips=1, device_kind="cpu", setup_s=1.0)
        assert read("stream.h2d_bytes_per_round", window) == 8 * 96 * 8
    finally:
        state.close()
    entry = next(w for w in SPEC["workloads"] if w["name"] == "packed-1m-streamed")
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "stream-packed8", "hostfed-1200x1m", 1)
    assert "four 300-row blocks" in entry["why"] and "bypasses" in entry["why"]


# -- the readers -----------------------------------------------------------------

def streamed_window():
    """Two streamed rounds of 1000 ms, each ``bench.round`` [0, 900) with
    ``stream.round`` 10..890 inside it: four blocks (``stream.feed`` 2 ms,
    ``stream.dispatch`` 3 ms), ``stream.steps_sync`` 180 ms before the third
    and the fourth block and 400 ms before the finale, ``stream.finale``
    800..810, ``stream.readback`` 810..890. A step's ops run 40 ms after
    each block has landed, the reconstruction 5 ms inside the finale."""
    rounds, annotations, ops = [], [], []
    for r in range(2):
        t = 1000 * r * MS
        rounds.append((t, t + 900 * MS))
        annotations += [("bench.round", t, t + 900 * MS),
                        ("stream.round", t + 10 * MS, t + 890 * MS),
                        ("bench.verify", t + 900 * MS, t + 950 * MS)]
        cursor = t + 10 * MS
        for block in range(4):
            if block >= 2:
                annotations.append(("stream.steps_sync", cursor, cursor + 180 * MS))
                cursor += 180 * MS
            annotations += [("stream.feed", cursor, cursor + 2 * MS),
                            ("stream.dispatch", cursor + 2 * MS, cursor + 5 * MS)]
            cursor += 5 * MS
            landed = t + (190 * (block + 1)) * MS
            ops += [("fusion.2 u32[999999]", landed, landed + 30 * MS),
                    ("sda.mask_share.1 u32[8,333568]", landed + 30 * MS, landed + 40 * MS)]
        annotations += [("stream.steps_sync", cursor, cursor + 400 * MS),
                        ("stream.finale", t + 800 * MS, t + 810 * MS),
                        ("stream.readback", t + 810 * MS, t + 890 * MS)]
        ops.append(("fusion.77 s64[999999]", t + 802 * MS, t + 807 * MS))
    trace = reduce.Reduced(window_ns=(0, 1950 * MS), devices={0: ops},
                           annotations=annotations, rounds=rounds)
    seconds: dict = {}
    for name, start, end in annotations:
        seconds[name] = seconds.get(name, 0.0) + (end - start) / 1e9
    # one round raised before it opened its root: attempted counts it, the
    # roots do not
    return harness.Window(facts={}, chips=1, device_kind="TPU v5 lite",
                          setup_s=1.0, attempted=3, spans=seconds, trace=trace)


@pytest.fixture
def stream_counters():
    """What two rounds and the warm-up of four [300, 999999] int64 blocks
    count."""
    from sda_tpu.utils import metrics

    metrics.reset_counters()
    for _ in range(3):
        metrics.count("mesh.stream.rounds")
        for _ in range(4):
            metrics.count("mesh.stream.blocks")
            metrics.count("mesh.stream.bytes", 300 * 999_999 * 8)
    yield
    metrics.reset_counters()


def test_the_span_readers_divide_by_the_roots_in_the_window(stream_counters):
    window = streamed_window()
    want = {"stream.feed_s_per_round": 0.008, "stream.dispatch_s_per_round": 0.012,
            "stream.sync_s_per_round": 0.760, "stream.finale_s_per_round": 0.010,
            "stream.readback_s_per_round": 0.080}
    for metric, seconds in want.items():
        assert read(metric, window) == pytest.approx(seconds), metric
    # the children sum to the root: 0.87 of 0.88 s here
    assert sum(want.values()) == pytest.approx(0.870)
    assert read("stream.h2d_bytes_per_round", window) == 9_599_990_400
    assert read("stream.h2d_bytes_per_s", window) == pytest.approx(
        9_599_990_400 / (0.880 - 0.010 - 0.080))
    # a run that keeps no intervals counts the attempted rounds
    window.trace, window.attempted = None, 2
    assert read("stream.sync_s_per_round", window) == pytest.approx(0.760)


def test_the_trace_readers_on_a_window_built_by_hand():
    window = streamed_window()
    assert read("stream.device_s_per_round", window) == pytest.approx(4 * 0.040 + 0.005)
    assert read("stream.idle_share", window) == pytest.approx(1 - 2 * 0.165 / 1.950)
    # no trace file behind a hand-built window: the scope reader reads nothing
    assert read("stream.acc_s_per_round", window) is None


@pytest.mark.parametrize("metric", ALL)
def test_a_program_without_the_spans_reads_nothing(metric):
    from sda_tpu.utils import metrics

    metrics.reset_counters()
    traced = streamed_window()
    keep = ("bench.round", "bench.verify")
    traced.trace.annotations = [a for a in traced.trace.annotations if a[0] in keep]
    traced.spans = {k: v for k, v in traced.spans.items() if k in keep}
    if metric not in ("stream.device_s_per_round", "stream.idle_share"):
        assert read(metric, traced) is None
    untraced = harness.Window(facts={}, chips=1, device_kind="cpu", setup_s=1.0,
                              attempted=3, spans={"bench.round": 0.27})
    assert read(metric, untraced) is None


def test_the_accumulator_scope_is_read_from_a_trace_file(tmp_path):
    """``stream.acc_s_per_round`` through ``reduce/scopes.py`` on events
    under ``sda.stream.acc``, and silent where no op carries the scope."""
    from reduce import scopes

    window = streamed_window()
    t0 = window.trace.rounds[0][0]
    events = {0: [("jit(step)/sda.stream.acc/add:", t0 + 240 * MS, t0 + 241 * MS),
                  ("jit(step)/sda.stream.acc/add:", t0 + 440 * MS, t0 + 441 * MS),
                  ("jit(step)/sda.mask_share/pallas_call:", t0 + 230 * MS, t0 + 240 * MS)]}
    per_round = scopes.per_round(events, window.trace.rounds, "sda.stream.acc")
    assert per_round == pytest.approx([0.002, 0.0])
    assert scopes.per_round(events, window.trace.rounds, "sda.stream") == [0.0, 0.0]


def test_the_new_entries_name_their_cell_layers_and_sources():
    entries = {m["name"]: m for m in SPEC["per_layer"]}
    for metric in ALL:
        entry = entries[metric]
        assert entry["workloads"] == ["packed-1m-streamed"]
        assert entry["moves"] == "hostfed_round_s"
        assert entry["layer"] == ("device" if metric == "stream.idle_share" else "stream")
        assert (harness.HERE / "layers" / f"{metric}.py").is_file()
    assert {entries[m]["source"] for m in SPAN_METRICS} == {"program_span"}
    assert {entries[m]["source"] for m in TRACE_METRICS} == {"device_trace"}
    assert entries["stream.h2d_bytes_per_round"]["source"] == "program_counter"
    assert entries["stream.h2d_bytes_per_s"]["unit"] == "bytes/s"
    # the new entries stand at the end of their lists, in the issue's order
    assert [m["name"] for m in SPEC["per_layer"]][-10:] == [
        *SPAN_METRICS, "stream.h2d_bytes_per_round", *TRACE_METRICS,
        "stream.h2d_bytes_per_s"]
    assert SPEC["workloads"][-1]["name"] == "packed-1m-streamed"
    assert SPEC["configs"][-1]["name"] == "stream-packed8"
    moved = next(m for m in SPEC["end_to_end"] if m["name"] == "hostfed_round_s")
    assert moved["workloads"] == ["packed-1m-hostfed", "packed-1m-streamed"]
    config = SPEC["configs"][-1]
    assert config["source"] == CONFIG["source"] and config["reduced"] == CONFIG["reduced"]
    assert CONFIG["architecture"] is None and len(CONFIG["guarantees"]) == 4
