"""The trace reduction on events built by hand: two chips, three rounds,
a collective that is half hidden behind compute, gaps under host spans --
and reduce.load on a profile written here, for the clock it puts both
sides on."""

import pytest

import reduce

MS = 1_000_000  # nanoseconds


def traced():
    """Round r spans [100r, 100r + 80) ms on the host, verify follows for
    5 ms. On each chip a round is: fusion 10..40, all-reduce 30..50 (10 ms
    under the fusion, 10 ms exposed), custom call 50..70. ``mesh.round``
    is open 5..75 inside the round."""
    rounds, annotations, ops = [], [], []
    for r in range(3):
        t = 100 * r * MS
        rounds.append((t, t + 80 * MS))
        annotations += [("bench.round", t, t + 80 * MS),
                        ("mesh.round", t + 5 * MS, t + 75 * MS),
                        ("bench.verify", t + 80 * MS, t + 85 * MS)]
        ops += [("fusion.1 u32[8]", t + 10 * MS, t + 40 * MS),
                ("all-reduce.2 u32[8]", t + 30 * MS, t + 50 * MS),
                ("sda.mask_share.1 u32[8,64]", t + 50 * MS, t + 70 * MS)]
    return reduce.Reduced(window_ns=(0, 285 * MS), devices={0: ops, 1: list(ops)},
                          annotations=annotations, rounds=rounds)


def test_interval_arithmetic():
    assert reduce.union([(5, 9), (0, 3), (2, 4), (9, 9)]) == [(0, 4), (5, 9)]
    assert reduce.clip([(0, 4), (5, 9)], 3, 6) == [(3, 4), (5, 6)]
    assert reduce.gaps([(2, 4), (6, 8)], 0, 10) == [(0, 2), (4, 6), (8, 10)]


def test_busy_union_and_idle_share():
    trace = traced()
    # 10..70 ms of every 100 ms round is busy: 60 ms x 3 rounds
    assert trace.busy_s == pytest.approx(0.180)
    assert trace.window_s == pytest.approx(0.285)
    assert trace.idle_share == pytest.approx(1 - 0.180 / 0.285)


def test_per_round_compute_collective_and_exposed():
    trace = traced()
    collective = trace.per_round(reduce.COLLECTIVE.search)
    compute = trace.per_round(lambda name: not reduce.COLLECTIVE.search(name))
    assert collective == pytest.approx([0.020] * 3)
    assert compute == pytest.approx([0.050] * 3)   # 30 ms fusion + 20 ms kernel
    assert trace.per_round() == pytest.approx([0.060] * 3)
    assert trace.host_per_round() == pytest.approx([0.020] * 3)   # 80 ms wall
    # 30..40 ms runs under the fusion; 40..50 ms is exposed
    assert trace.exposed_collective_per_round() == pytest.approx([0.010] * 3)


def test_gaps_go_to_the_innermost_host_span():
    gap_seconds = dict(traced().gap_totals())
    # per round: 0..5 and 75..80 under bench.round only, 5..10 and 70..75
    # under mesh.round, 80..85 under bench.verify, 85..100 under nothing
    # (the last round's tail lies outside the window)
    assert gap_seconds["mesh.round"] == pytest.approx(0.030)
    assert gap_seconds["bench.round"] == pytest.approx(0.030)
    assert gap_seconds["bench.verify"] == pytest.approx(0.015)
    assert gap_seconds["unattributed"] == pytest.approx(0.030)
    assert sum(gap_seconds.values()) == pytest.approx(0.285 - 0.180)


def test_breakdown_is_bounded_and_ranked():
    trace = traced()
    trace.devices[0] += [(f"copy.{i} u32[4]", 71 * MS, 72 * MS) for i in range(20)]
    breakdown = trace.breakdown()
    assert len(breakdown["device_ops"]) == 10 and len(breakdown["idle_gaps"]) <= 10
    assert breakdown["device_ops"][0] == ["fusion.1 u32[8]", pytest.approx(0.090)]


def test_op_names_are_classified_by_the_op_not_its_operands():
    line = ("%fusion.2 = u32[4]{0:T(128)} fusion(u32[4]{0} %all-gather.1), "
            "kind=kLoop")
    assert reduce.short(line) == "fusion.2 u32[4]"
    assert not reduce.COLLECTIVE.search(reduce.short(line))
    kernel = ("%sda.mask_share.1 = (u32[8,333824]{1,0:T(8,128)S(1)}, "
              "u32[3,333824]{1,0}) custom-call(s32[1]{0} %bitcast.5)")
    assert reduce.short(kernel) == "sda.mask_share.1 u32[8,333824]"
    assert reduce.COLLECTIVE.search(reduce.short("%all-reduce.7 = u32[8]{0} all-reduce(%x)"))


def test_load_needs_a_device_plane_and_a_round_mark(tmp_path):
    """A CPU profile has no ``/device:TPU`` plane: nothing is reduced, and
    the harness then leaves every trace metric out."""
    import jax
    import jax.numpy as jnp

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = options.host_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    jnp.ones(8).sum().block_until_ready()
    jax.profiler.stop_trace()
    assert reduce.reduce_run(tmp_path, 1, [("bench.round", 0, 10)]) is None
    assert reduce.reduce_run(tmp_path / "nothing-here", 1, []) is None
