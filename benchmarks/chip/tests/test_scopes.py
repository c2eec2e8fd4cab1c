"""reduce/scopes.py on a trace built by hand: the protobuf wire format of
the few xplane.proto messages it reads, the scope rule (a whole path
component), the union over a nesting line, and the readers' silence where
a program has no such scope."""

import types

import pytest

import harness
from reduce import scopes

EPOCH = 1_700_000_000_000_000_000  # profile_start_time, ns


# -- a protobuf writer, as small as the reader -----------------------------------

def varint(value: int) -> bytes:
    value &= (1 << 64) - 1
    out = bytearray()
    while True:
        out.append((value & 0x7F) | (0x80 if value > 0x7F else 0))
        value >>= 7
        if not value:
            return bytes(out)


def field(number: int, value) -> bytes:
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    if isinstance(value, str):
        value = value.encode()
    return varint(number << 3 | 2) + varint(len(value)) + value


def map_entry(key: int, message: bytes) -> bytes:
    return field(1, key) + field(2, message)


#: stat metadata ids
TF_OP, HLO_CATEGORY, START, SCOPE_AS_NAME = 1, 2, 3, 4


def device_plane(index: int, ops: list, line_ns: int = 1_000) -> bytes:
    """``ops``: (tf_op or None, start_ns, end_ns, by_reference) on the
    plane's own clock; one event metadata per op."""
    plane = field(1, index) + field(2, f"/device:TPU:{index}")
    plane += field(5, map_entry(TF_OP, field(1, TF_OP) + field(2, "tf_op")))
    plane += field(5, map_entry(HLO_CATEGORY, field(1, HLO_CATEGORY) + field(2, "hlo_category")))
    plane += field(5, map_entry(
        SCOPE_AS_NAME, field(1, SCOPE_AS_NAME) + field(2, "jit(f)/sda.share/xor:")))
    events = b""
    for op_id, (tf_op, start, end, by_reference) in enumerate(ops, start=1):
        stats = field(5, field(1, HLO_CATEGORY) + field(5, "fusion"))
        if tf_op is not None:
            value = field(7, SCOPE_AS_NAME) if by_reference else field(5, tf_op)
            stats += field(5, field(1, TF_OP) + value)
        metadata = field(1, op_id) + field(2, f"%fusion.{op_id} = u32[8]") + stats
        plane += field(4, map_entry(op_id, metadata))
        events += field(4, field(1, op_id) + field(2, (start - line_ns) * 1000)
                        + field(3, (end - start) * 1000))
    plane += field(3, field(1, 7) + field(2, "XLA Modules") + field(3, line_ns)
                   + field(4, field(1, 1) + field(2, 0) + field(3, 10**9)))
    plane += field(3, field(1, 8) + field(2, "XLA Ops") + field(3, line_ns) + events)
    return plane


def environment_plane() -> bytes:
    return (field(2, "Task Environment")
            + field(5, map_entry(START, field(1, START) + field(2, "profile_start_time")))
            + field(6, field(1, START) + field(3, EPOCH)))


def write_trace(directory, planes: list):
    path = directory / "trace-x" / "plugins" / "profile" / "2026_01_01" / "host.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(b"".join(field(1, plane) for plane in planes))
    return path


CHACHA = "jit(_local_round)/while/body/closed_call/sda.mask/sda.mask.chacha/vmap(jit(chacha_block_words))/add:"
FOLD = "jit(_local_round)/while/body/closed_call/sda.mask/reduce:"
KERNEL = "jit(_local_round)/sda.mask_share/pallas_call:"

#: a ``while`` event (no tf_op) enclosing a body of three ops, then the kernel
OPS = [(None, 2_000, 9_000, False),
       (CHACHA, 2_000, 4_000, False),
       (CHACHA, 3_500, 5_000, False),       # overlaps the first: a union
       (FOLD, 5_000, 6_000, False),
       ("", 6_000, 7_500, True),            # tf_op kept as a stat's name
       (KERNEL, 9_000, 9_500, False)]


def test_components_are_whole_path_elements_without_the_ops_own_name():
    assert scopes.components(CHACHA) == [
        "jit(_local_round)", "while", "body", "closed_call", "sda.mask",
        "sda.mask.chacha", "vmap(jit(chacha_block_words))"]
    assert "sda.mask" not in scopes.components(KERNEL)
    assert scopes.components("") == [] and scopes.components("out:") == []


def test_device_events_land_on_the_epoch_clock_with_their_tf_op(tmp_path):
    path = write_trace(tmp_path, [device_plane(0, OPS), device_plane(1, OPS[:2]),
                                  environment_plane()])
    events = scopes.device_events(path, 1)
    assert set(events) == {0}  # the second chip is not this cell's
    assert events[0][1] == (CHACHA, EPOCH + 2_000, EPOCH + 4_000)
    assert events[0][0][0] == "" and events[0][4][0] == "jit(f)/sda.share/xor:"
    assert set(scopes.device_events(path, 2)) == {0, 1}


def test_a_trace_without_device_planes_or_start_time_reads_as_none(tmp_path):
    assert scopes.device_events(write_trace(tmp_path / "a", [environment_plane()]), 1) is None
    assert scopes.device_events(write_trace(tmp_path / "b", [device_plane(0, OPS)]), 1) is None


def test_per_round_is_a_union_inside_each_rounds_span_averaged_over_chips(tmp_path):
    path = write_trace(tmp_path, [device_plane(0, OPS), device_plane(1, OPS[:2]),
                                  environment_plane()])
    rounds = [(EPOCH + 1_000, EPOCH + 4_500), (EPOCH + 4_500, EPOCH + 10_000)]
    one = scopes.device_events(path, 1)
    assert scopes.per_round(one, rounds, "sda.mask.chacha") == [2_500e-9, 500e-9]
    assert scopes.per_round(one, rounds, "sda.mask") == [2_500e-9, 1_500e-9]
    assert scopes.per_round(one, rounds, "sda.mask", without=("sda.mask.chacha",)) == [0.0, 1_000e-9]
    assert scopes.per_round(one, rounds, "sda.share") == [0.0, 1_500e-9]
    assert scopes.per_round(one, rounds, "sda.mask_share") == [0.0, 500e-9]
    two = scopes.device_events(path, 2)  # chip 1 ran one cipher op of 2000 ns
    assert scopes.per_round(two, rounds, "sda.mask.chacha") == [2_250e-9, 250e-9]


def fake_window(rounds, chips=1):
    return harness.Window(facts={}, chips=chips, device_kind="TPU v5 lite", setup_s=0.0,
                          trace=types.SimpleNamespace(rounds=rounds))


def test_seconds_per_round_is_the_median_and_none_where_nothing_carries_the_scope(tmp_path):
    write_trace(tmp_path, [device_plane(0, OPS), environment_plane()])
    rounds = [(EPOCH + 1_000, EPOCH + 4_500), (EPOCH + 4_500, EPOCH + 10_000)]
    window = fake_window(rounds)
    assert scopes.seconds_per_round(window, "sda.mask.chacha", out=tmp_path) == pytest.approx(1_500e-9)
    assert scopes.seconds_per_round(window, "sda.mask.reduce", out=tmp_path) is None
    assert scopes.seconds_per_round(window, "sda.mask.chacha", out=tmp_path / "none") is None
    untraced = harness.Window(facts={}, chips=1, device_kind="TPU v5 lite", setup_s=0.0)
    assert scopes.seconds_per_round(untraced, "sda.mask.chacha", out=tmp_path) is None


def test_the_newest_trace_is_the_one_read(tmp_path):
    import os

    old = write_trace(tmp_path / "out", [device_plane(0, OPS[:2]), environment_plane()])
    new = old.parents[4] / "trace-y" / "plugins" / "profile" / "2026_01_02" / "h.xplane.pb"
    new.parent.mkdir(parents=True)
    new.write_bytes(old.read_bytes())
    os.utime(old, (1, 1))
    assert scopes.newest_trace(tmp_path / "out") == new


# -- the five readers on such a trace ----------------------------------------------

def read_layer(name, window):
    return harness.load_module(harness.HERE, "layers", name).read(window)


@pytest.fixture
def traced(tmp_path, monkeypatch):
    def use(ops):
        path = write_trace(tmp_path / str(len(list(tmp_path.iterdir()))),
                           [device_plane(0, ops), environment_plane()])
        monkeypatch.setattr(scopes, "newest_trace", lambda out: path)
        return fake_window([(EPOCH + 1_000, EPOCH + 10_000)])
    return use


def test_the_scope_readers_on_a_program_with_the_scopes(traced):
    window = traced(OPS)
    assert read_layer("fields.mask_chacha_s_per_round", window) == pytest.approx(3_000e-9)
    assert read_layer("fields.share_s_per_round", window) == pytest.approx(1_500e-9)
    # no op carries sda.mask.reduce (fused into the fold): the ops directly
    # under sda.mask, the cipher's left out
    assert read_layer("fields.mask_reduce_s_per_round", window) == pytest.approx(1_000e-9)
    own = OPS + [("jit(f)/sda.mask/sda.mask.reduce/jit(remainder)/rem:", 7_500, 7_700, False)]
    assert read_layer("fields.mask_reduce_s_per_round", traced(own)) == pytest.approx(200e-9)


def test_the_scope_readers_fall_silent_on_a_program_without_the_scopes(traced):
    parent = [(None, 2_000, 9_000, False),
              ("jit(_local_round)/while/body/closed_call/sda.mask/vmap()/gather:", 2_000, 5_000, False),
              (FOLD, 5_000, 6_000, False), ("", 6_000, 7_500, True)]
    window = traced(parent)
    assert read_layer("fields.mask_chacha_s_per_round", window) is None
    assert read_layer("fields.mask_reduce_s_per_round", window) is None
    assert read_layer("fields.chacha_blocks_per_s", window) is None
    assert read_layer("fields.share_s_per_round", window) == pytest.approx(1_500e-9)


def test_blocks_per_second_divides_the_counters_by_the_cipher_seconds(traced, monkeypatch):
    from sda_tpu.utils import metrics

    window = traced(OPS)
    monkeypatch.setattr(metrics, "counter_report", lambda prefix="": {})
    assert read_layer("fields.chacha_blocks_per_s", window) is None  # no counter: the parent
    monkeypatch.setattr(metrics, "counter_report", lambda prefix="": {
        "mesh.mask.chacha_calls": 3, "mesh.mask.chacha_blocks": 3 * 6_000})
    assert read_layer("fields.chacha_blocks_per_s", window) == pytest.approx(6_000 / 3_000e-9)
