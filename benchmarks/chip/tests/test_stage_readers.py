"""The stage readers (ISSUE 38) on a trace built by hand, **with an
enclosing ``while``**: the four that sum a stage scope, and the two
remainders (``reduce/stages.py``), which are arithmetic on instants -- a
``while`` that carries the scope asked about is not counted whole, one
that carries none is not dropped whole. And the six entries of
``BENCHMARK.json``, found by name."""

import json

import pytest

import harness
from reduce import scopes, stages
from test_scopes import (EPOCH, device_plane, environment_plane, fake_window,
                         write_trace)

SPEC = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
PACKED = ["packed-1m", "packed-1m-mesh4", "packed-chacha-1m"]
ENTRIES = {
    "fields.fold_s_per_round": PACKED,
    "fields.relayout_s_per_round": PACKED,
    "fields.reconstruct_s_per_round": PACKED,
    "fields.unbatch_s_per_round": PACKED,
    "fields.mask_other_s_per_round": ["additive-chacha-1m", "packed-chacha-1m"],
    "fields.unscoped_s_per_round": ["packed-1m", "packed-1m-mesh4",
                                    "additive-chacha-1m", "packed-chacha-1m"],
}

J = "jit(_local_round)/"
#: the kernel path under ChaCha masks: the mask expansion's scan stands under
#: ``sda.mask``, so its ``while`` carries that scope and encloses the body
KERNEL_PATH = [
    ("jit(_threefry_fold_in)/xor:", 1_000, 1_100, False),       # the driver's key fold
    (J + "sda.fold/reduce:", 1_200, 2_000, False),
    (J + "sda.mask/while:", 2_000, 6_000, False),               # encloses the next five
    (J + "sda.mask/while/body/closed_call/sda.mask.chacha/vmap()/add:", 2_000, 3_000, False),
    (None, 3_000, 3_400, False),                                # the words stacked in place: no tf_op
    (J + "sda.mask/while/body/closed_call/sda.mask.reduce/sub:", 3_400, 3_600, False),
    (J + "sda.mask/while/body/closed_call/sda.mask.relayout/dot_general:", 3_600, 4_600, False),
    (J + "sda.mask/while/body/closed_call/sda.mask.fold/reduce:", 4_800, 5_500, False),
    (J + "sda.mask/sda.mask.fold/add:", 6_000, 6_100, False),
    (J + "sda.relayout/reshape:", 6_100, 6_400, False),
    (J + "sda.mask_share/pallas_call:", 6_400, 7_400, False),
    (J + "sda.relayout/slice:", 7_400, 7_500, False),
    (J + "sda.reconstruct/sda.reconstruct.lagrange/mul:", 7_500, 8_300, False),
    (J + "sda.reconstruct/sda.reconstruct.unbatch/transpose:", 8_300, 8_500, False),
    (None, 8_500, 8_600, False),                                # a copy the compiler made
    (J + "sda.unmask/sub:", 8_600, 8_900, False),
]
#: the XLA step: the scan of ``_scan_combine`` stands under no stage, so its
#: ``while`` carries none, and what its body leaves unnamed is unscoped
XLA_STEP = [
    (J + "sda.blocks/reshape:", 1_000, 1_500, False),
    (J + "while:", 1_500, 8_000, False),                        # encloses the next six
    (J + "while/body/closed_call/sda.mask/vmap()/xor:", 1_500, 1_600, False),  # the seed words
    (J + "while/body/closed_call/sda.mask/sda.mask.chacha/vmap()/add:", 1_600, 3_600, False),
    (None, 3_600, 4_200, False),                                # the words stacked in place
    (J + "while/body/closed_call/sda.mask/sda.mask.fold/add:", 4_200, 5_000, False),
    (J + "while/body/closed_call/sda.share/reduce:", 5_000, 7_000, False),
    (J + "while/body/dynamic_slice:", 7_000, 7_100, False),     # the scan's own slicing
    (J + "sda.reconstruct/reduce:", 8_000, 8_200, False),
    (J + "sda.unmask/sub:", 8_200, 8_300, False),
]
ROUND = [(EPOCH + 500, EPOCH + 9_500)]


def read_layer(name, window):
    return harness.load_module(harness.HERE, "layers", name).read(window)


@pytest.fixture
def traced(tmp_path, monkeypatch):
    def use(ops, rounds=ROUND, chips=1):
        path = write_trace(tmp_path / str(len(list(tmp_path.iterdir()))),
                           [device_plane(chip, ops) for chip in range(chips)]
                           + [environment_plane()])
        monkeypatch.setattr(scopes, "newest_trace", lambda out: path)
        return fake_window(rounds, chips=chips)
    return use


def test_the_four_scope_readers_sum_what_carries_their_scope(traced):
    window = traced(KERNEL_PATH)
    assert read_layer("fields.fold_s_per_round", window) == pytest.approx(800e-9)
    # sda.mask.relayout is another scope: a whole path component
    assert read_layer("fields.relayout_s_per_round", window) == pytest.approx(400e-9)
    # the parent scope counts its children's ops
    assert read_layer("fields.reconstruct_s_per_round", window) == pytest.approx(1_000e-9)
    assert read_layer("fields.unbatch_s_per_round", window) == pytest.approx(200e-9)


def test_unbatch_reads_zero_where_the_compiler_left_no_op_under_the_scope(traced):
    hoisted = [op for op in KERNEL_PATH if "sda.reconstruct.unbatch" not in (op[0] or "")]
    window = traced(hoisted)
    assert read_layer("fields.unbatch_s_per_round", window) == 0.0
    assert read_layer("fields.reconstruct_s_per_round", window) == pytest.approx(800e-9)
    # a program from before the two scopes: sda.reconstruct alone
    parent = [(J + "sda.reconstruct/mul:", 7_500, 8_500, False)]
    window = traced(parent)
    assert read_layer("fields.unbatch_s_per_round", window) is None
    assert read_layer("fields.reconstruct_s_per_round", window) == pytest.approx(1_000e-9)
    assert read_layer("fields.fold_s_per_round", window) is None
    assert read_layer("fields.relayout_s_per_round", window) is None


def test_the_remainders_do_not_count_an_enclosing_while_whole(traced):
    window = traced(KERNEL_PATH)
    # under sda.mask: the while 2000..6000 and the add 6000..6100; the four
    # children cover 2000..3000, 3400..4600, 4800..5500 and 6000..6100
    assert read_layer("fields.mask_other_s_per_round", window) == pytest.approx(1_100e-9)
    # filtering ops instead would count the while whole
    events = scopes.device_events(scopes.newest_trace(None), 1)
    assert scopes.per_round(events, ROUND, "sda.mask", without=stages.MASK_CHILDREN) \
        == pytest.approx([4_000e-9])
    # the while carries a stage scope, so nothing inside it is unscoped: what
    # is left is the driver's key fold and the compiler's copy
    assert read_layer("fields.unscoped_s_per_round", window) == pytest.approx(200e-9)


def test_the_remainders_do_not_drop_an_enclosing_while_whole(traced):
    window = traced(XLA_STEP)
    # the while carries no stage: the stack 3600..4200 and the scan's slicing
    # 7000..7100 and its idle tail 7100..8000 are instants of the while alone
    assert read_layer("fields.unscoped_s_per_round", window) == pytest.approx(1_600e-9)
    # directly under sda.mask: the seed words; the stack carries no tf_op
    assert read_layer("fields.mask_other_s_per_round", window) == pytest.approx(100e-9)


def test_stage_seconds_and_the_unscoped_remainder_close_on_the_busy_seconds(traced):
    for ops in (KERNEL_PATH, XLA_STEP):
        traced(ops)
        events = scopes.device_events(scopes.newest_trace(None), 1)
        staged = stages.seconds(events, ROUND, stages.under(*stages.STAGES))
        unscoped = stages.seconds(events, ROUND, stages.anything,
                                  stages.under(*stages.STAGES))
        busy = stages.seconds(events, ROUND, stages.anything)
        assert staged[0] + unscoped[0] == pytest.approx(busy[0])


def test_seconds_are_clipped_to_each_round_and_averaged_over_the_chips(traced):
    rounds = [(EPOCH + 500, EPOCH + 3_200), (EPOCH + 3_200, EPOCH + 9_500)]
    window = traced(KERNEL_PATH, rounds=rounds, chips=2)
    events = scopes.device_events(scopes.newest_trace(None), 2)
    other = stages.seconds(events, rounds, stages.under("sda.mask"),
                           stages.under(*stages.MASK_CHILDREN))
    assert other == pytest.approx([200e-9, 900e-9])  # 3000..3200 | the rest
    assert read_layer("fields.mask_other_s_per_round", window) == pytest.approx(550e-9)
    one_chip = {0: events[0], 1: []}                 # the second chip ran nothing
    assert stages.seconds(one_chip, rounds, stages.under("sda.fold")) \
        == pytest.approx([400e-9, 0.0])


def test_the_remainders_fall_silent_without_their_scopes_and_without_a_trace(traced, tmp_path):
    assert stages.events_of(fake_window(ROUND), out=tmp_path / "none") is None
    bare = [("jit(f)/add:", 1_000, 2_000, False), (None, 2_000, 3_000, False)]
    window = traced(bare)
    assert read_layer("fields.unscoped_s_per_round", window) is None
    assert read_layer("fields.mask_other_s_per_round", window) is None
    # full masking: sda.mask without a child
    full = [(J + "sda.mask/threefry2x32:", 1_000, 2_000, False)]
    window = traced(full)
    assert read_layer("fields.mask_other_s_per_round", window) is None
    assert read_layer("fields.unscoped_s_per_round", window) == 0.0
    untraced = harness.Window(facts={}, chips=1, device_kind="TPU v5 lite", setup_s=0.0)
    for name in ENTRIES:
        assert read_layer(name, untraced) is None


def test_under_matches_whole_path_components():
    assert stages.under("sda.mask")(J + "sda.mask/sda.mask.fold/add:")
    assert not stages.under("sda.mask")(J + "sda.mask_share/pallas_call:")
    assert not stages.under("sda.relayout")(J + "sda.mask/sda.mask.relayout/or:")
    assert not stages.under(*stages.STAGES)("") and stages.anything("")
    assert set(stages.MASK_CHILDREN).isdisjoint(stages.STAGES)


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_the_entry_is_found_by_name_with_its_layer_cells_and_file(name):
    entry = next(m for m in SPEC["per_layer"] if m["name"] == name)
    assert entry == {"name": name, "unit": "s", "better": "lower",
                     "source": "device_trace", "layer": "fields",
                     "moves": "round_s", "workloads": ENTRIES[name]}
    assert (harness.HERE / "layers" / f"{name}.py").is_file()
    moved = next(m for m in SPEC["end_to_end"] if m["name"] == "round_s")
    assert set(entry["workloads"]) <= set(moved["workloads"])
