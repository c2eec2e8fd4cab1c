"""The readers of the pod's own spans and counters (``pod.feed`` /
``pod.dispatch`` / ``pod.wait`` / ``mesh.round``, ``mesh.feed.*``) on
events built by hand, against numbers worked by hand -- and on a window
of a program that has none of them, where each reads nothing."""

import pytest

import harness
import reduce

MS = 1_000_000  # nanoseconds
HOST_FED = ("feed.put_s_per_round", "feed.wait_s_per_round",
            "feed.input_wait_s_per_round", "feed.h2d_bytes_per_round",
            "feed.h2d_bytes_per_s", "feed.readback_s_per_round")
ALL = HOST_FED + ("mesh.dispatch_s_per_round",)


def read(metric, window):
    return harness.load_module(harness.HERE, "layers", metric).read(window)


def host_fed_window():
    """Three host-fed rounds of 100 ms, each ``bench.round`` [0, 90) with
    ``mesh.round`` 2..75 inside it (so it ends 15 ms before the round
    does): ``pod.feed`` 5..20, ``pod.dispatch`` 20..23, ``pod.wait``
    23..75, then ``pod.strip`` 75..76. The chip's first op starts at 60;
    the key fold before the round and the strip's slice after it are
    device ops too, outside ``mesh.round``."""
    rounds, annotations, ops = [], [], []
    for r in range(3):
        t = 100 * r * MS
        rounds.append((t, t + 90 * MS))
        annotations += [("bench.round", t, t + 90 * MS),
                        ("mesh.round", t + 2 * MS, t + 75 * MS),
                        ("pod.feed", t + 5 * MS, t + 20 * MS),
                        ("pod.dispatch", t + 20 * MS, t + 23 * MS),
                        ("pod.wait", t + 23 * MS, t + 75 * MS),
                        ("pod.strip", t + 75 * MS, t + 76 * MS),
                        ("bench.verify", t + 90 * MS, t + 95 * MS)]
        ops += [("fusion.9 u32[2]", t + 1 * MS, t + 1 * MS + 1000),
                ("add_select_fusion.189 u32[8,6]", t + 60 * MS, t + 70 * MS),
                ("sda.mask_share.1 u32[8,64]", t + 70 * MS, t + 74 * MS),
                ("slice.2 s64[6]", t + 76 * MS, t + 77 * MS)]
    trace = reduce.Reduced(window_ns=(0, 295 * MS), devices={0: ops},
                           annotations=annotations, rounds=rounds)
    seconds: dict = {}
    for name, start, end in annotations:
        seconds[name] = seconds.get(name, 0.0) + (end - start) / 1e9
    return harness.Window(facts={}, chips=1, device_kind="TPU v5 lite",
                          setup_s=1.0, attempted=3, spans=seconds, trace=trace)


@pytest.fixture
def fed_counters():
    """What three rounds and the warm-up of a [8, 6] int64 feed count."""
    from sda_tpu.utils import metrics

    metrics.reset_counters()
    for _ in range(4):
        metrics.count("mesh.feed.calls")
        metrics.count("mesh.feed.bytes", 8 * 6 * 8)
        metrics.count("mesh.feed.pad_bytes", 0)
    yield
    metrics.reset_counters()


def test_span_readers_give_seconds_per_round():
    window = host_fed_window()
    assert read("feed.put_s_per_round", window) == pytest.approx(0.015)
    assert read("feed.wait_s_per_round", window) == pytest.approx(0.052)
    assert read("mesh.dispatch_s_per_round", window) == pytest.approx(0.003)


def test_input_wait_is_first_op_in_the_round_minus_the_feeds_start():
    # 60 - 5: the key fold at 1 ms and the slice at 76 ms lie outside
    assert read("feed.input_wait_s_per_round", host_fed_window()) \
        == pytest.approx(0.055)


def test_readback_is_what_the_round_costs_after_mesh_round_closed():
    window = host_fed_window()
    assert read("feed.readback_s_per_round", window) == pytest.approx(0.015)
    # the median, not the mean: one slow release does not move it
    name, lo, hi = window.trace.annotations[0]
    window.trace.annotations[0] = (name, lo, hi + 40 * MS)
    window.trace.rounds[0] = (lo, hi + 40 * MS)
    assert read("feed.readback_s_per_round", window) == pytest.approx(0.015)


def test_bytes_come_from_the_programs_counters(fed_counters):
    window = host_fed_window()
    assert read("feed.h2d_bytes_per_round", window) == 384
    assert read("feed.h2d_bytes_per_s", window) == pytest.approx(384 / 0.055)


def test_the_four_parts_add_up_to_the_round():
    """put + dispatch + wait + read-back leave out only what the round
    does before ``pod.feed`` opens: 5 of 90 ms here."""
    window = host_fed_window()
    parts = sum(read(metric, window) for metric in (
        "feed.put_s_per_round", "mesh.dispatch_s_per_round",
        "feed.wait_s_per_round", "feed.readback_s_per_round"))
    assert parts == pytest.approx(0.085)


@pytest.mark.parametrize("metric", ALL)
def test_a_program_without_the_spans_and_counters_reads_nothing(metric):
    """The parent of the PR that added them: the reader returns None and
    does not raise, traced or not, and the line leaves the metric out."""
    from sda_tpu.utils import metrics

    metrics.reset_counters()
    traced = host_fed_window()
    traced.spans = {"bench.round": 0.27, "mesh.round": 0.219}
    traced.trace.annotations[:] = [
        a for a in traced.trace.annotations if not a[0].startswith("pod.")]
    if metric == "feed.readback_s_per_round":  # reads marks the parent has
        assert read(metric, traced) == pytest.approx(0.015)
    else:
        assert read(metric, traced) is None
    untraced = harness.Window(facts={}, chips=1, device_kind="cpu",
                              setup_s=1.0, attempted=3,
                              spans={"bench.round": 0.27})
    assert read(metric, untraced) is None


def test_the_new_entries_name_their_cells_layers_and_sources():
    import json

    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    entries = {m["name"]: m for m in spec["per_layer"]}
    for metric in HOST_FED:
        assert entries[metric]["workloads"] == ["packed-1m-hostfed"]
        assert entries[metric]["layer"] == "feed"
        assert entries[metric]["moves"] == "hostfed_round_s"
    dispatch = entries["mesh.dispatch_s_per_round"]
    assert dispatch["workloads"] == ["packed-1m", "packed-1m-mesh4"]
    assert (dispatch["layer"], dispatch["moves"]) == ("mesh", "round_s")
    assert {entries[m]["source"] for m in ALL} == {
        "program_span", "program_counter", "device_trace"}
