"""The configuration ``stream-packed8`` at toy size on the CPU: a cohort
streamed through ``StreamingAggregator`` in blocks, held to the blocked
plain reference of the chip benchmark
(``benchmarks/chip/references/modsum_blocks.py``) and to the round written
out in Python integers; the round's root span, its children and counters;
and the bounds the tile loop keeps: two blocks in flight, one of them in
transfer."""

import gc
import importlib.util
import json
import weakref
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sda_tpu import obs
from sda_tpu.fields import numtheory
from sda_tpu.mesh import StreamingAggregator, streaming
from sda_tpu.protocol import FullMasking, PackedShamirSharing
from sda_tpu.utils import metrics, phase_report

from util import external_bits

CHIP = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
CONFIG = json.loads((CHIP / "configs" / "stream-packed8.json").read_text())
STEPS = ("pallas", "xla")
CHUNK = 4
#: rows: under one chunk, exactly one chunk's worth, chunk + 1, and three
#: blocks with a ragged last one
COHORTS = (2, CHUNK, CHUNK + 1, 2 * CHUNK + 2)
DIM = 24
PER_BLOCK = ("stream.feed", "stream.dispatch")
PER_TILE = ("stream.finale", "stream.readback")


def _reference():
    spec = importlib.util.spec_from_file_location(
        "modsum_blocks", CHIP / "references" / "modsum_blocks.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REFERENCE = _reference()


def _scheme() -> PackedShamirSharing:
    """The configuration's scheme block as ``schemes.packed_shamir``
    builds it."""
    want = CONFIG["scheme"]
    k, n = want["secret_count"], want["share_count"]
    t, p, w2, w3 = numtheory.generate_packed_params(k, n, want["prime_bits"])
    assert (t, p) == (want["privacy_threshold"], want["prime_modulus"])
    return PackedShamirSharing(k, n, t, p, w2, w3)


def _aggregator(step: str, chunk: int = CHUNK) -> StreamingAggregator:
    """The configuration's constructor call; the kernel interpreted and
    fed external bits off the chip."""
    scheme = _scheme()
    interpreted = dict(pallas_interpret=True,
                       pallas_external_bits_fn=external_bits)
    agg = StreamingAggregator(
        scheme, FullMasking(scheme.prime_modulus), participants_chunk=chunk,
        use_pallas=step == "pallas", **(interpreted if step == "pallas" else {}))
    assert agg.pallas_active == (step == "pallas")
    return agg


def _inputs(rows: int, seed: int = 5) -> np.ndarray:
    """int64 of every kind a host matrix may hold: 20-bit values, negative
    ones, values >= p and the ends of int64."""
    p = CONFIG["scheme"]["prime_modulus"]
    rng = np.random.default_rng([seed, rows])
    x = rng.integers(0, 1 << 20, size=(rows, DIM), dtype=np.int64)
    x[:, 1] = -x[:, 1] - 1
    x[:, 2] += p
    x[:, 3] = rng.integers(-2**62, 2**62, size=rows, dtype=np.int64)
    x[0, 4], x[-1, 5] = np.iinfo(np.int64).max, np.iinfo(np.int64).min
    return x


def _plain_sum(x: np.ndarray) -> np.ndarray:
    p = CONFIG["scheme"]["prime_modulus"]
    return np.array([sum(int(v) for v in column) % p for column in x.T],
                    dtype=np.int64)


def _provider(x):
    return lambda p0, p1, d0, d1: x[p0:p1, d0:d1]


# -- the cell's configuration at toy size ----------------------------------------

@pytest.mark.parametrize("rows", COHORTS)
@pytest.mark.parametrize("step", STEPS)
def test_the_streamed_round_reveals_the_blocked_plain_sum(step, rows):
    x = _inputs(rows)
    out = _aggregator(step).aggregate(x, jax.random.PRNGKey(rows))
    assert out.dtype == np.int64 and isinstance(out, np.ndarray)
    want = REFERENCE.on_host_blocks(_provider(x), rows, DIM, CONFIG[
        "scheme"]["prime_modulus"], 3)
    np.testing.assert_array_equal(out, want)
    np.testing.assert_array_equal(out, _plain_sum(x))


@pytest.mark.parametrize("rows", COHORTS)
@pytest.mark.parametrize("step", STEPS)
def test_the_streamed_round_equals_the_round_written_out(step, rows):
    x = _inputs(rows, seed=9)
    out = _aggregator(step).aggregate(x, jax.random.PRNGKey(rows + 100))
    plain = REFERENCE.plain_streamed_round(
        x, CONFIG["scheme"], CHUNK, np.random.default_rng(rows))
    assert plain["blocks"] == -(-rows // CHUNK)
    np.testing.assert_array_equal(out, plain["aggregate"])


@pytest.mark.parametrize("block_rows", [1, 3, 7, 100])
def test_the_blocked_sum_is_the_sum_whatever_the_blocking(block_rows):
    x = _inputs(7)
    p = CONFIG["scheme"]["prime_modulus"]
    got = REFERENCE.on_host_blocks(_provider(x), 7, DIM, p, block_rows)
    np.testing.assert_array_equal(got, _plain_sum(x))
    assert got.dtype == np.int64 and 0 <= got.min() and got.max() < p


def test_the_blocked_sum_refuses_an_empty_block_and_a_misshapen_one():
    x = _inputs(4)
    with pytest.raises(ValueError, match="at least one row"):
        REFERENCE.on_host_blocks(_provider(x), 4, DIM, 433, 0)
    with pytest.raises(ValueError, match="shape"):
        REFERENCE.on_host_blocks(lambda *_: x[:1], 4, DIM, 433, 2)


@pytest.mark.parametrize("clerks", [None, (7, 1, 2, 3, 4, 5, 6), (1, 2, 3, 4, 5, 6, 7)])
@pytest.mark.parametrize("block_rows", [1, 4])
def test_the_round_written_out_reveals_the_sum_from_any_seven_clerks(block_rows, clerks):
    x = _inputs(5, seed=3)
    # the program's own roots of unity, and the first of the right order
    program = _scheme()
    stated = {**CONFIG["scheme"], "omega_secrets": program.omega_secrets,
              "omega_shares": program.omega_shares}
    for scheme in (CONFIG["scheme"], stated):
        plain = REFERENCE.plain_streamed_round(
            x, scheme, block_rows, np.random.default_rng(1), clerks=clerks)
        np.testing.assert_array_equal(plain["aggregate"], _plain_sum(x))
        assert len(plain["clerk_rows"]) == 8
        assert len(plain["clerk_rows"][0]) == DIM // 3


def test_the_round_written_out_is_upstreams_golden_scheme_too():
    golden = {"secret_count": 3, "share_count": 8, "privacy_threshold": 4,
              "prime_modulus": 433, "omega_secrets": 354, "omega_shares": 150}
    x = np.arange(40, dtype=np.int64).reshape(4, 10) - 7   # dim off the grain
    plain = REFERENCE.plain_streamed_round(x, golden, 3, np.random.default_rng(2))
    np.testing.assert_array_equal(plain["aggregate"], x.sum(axis=0) % 433)
    with pytest.raises(ValueError, match="7 distinct clerks"):
        REFERENCE.plain_streamed_round(x, golden, 3, np.random.default_rng(2),
                                       clerks=(0, 1, 2))
    with pytest.raises(ValueError, match="order"):
        REFERENCE.scheme_points({**golden, "omega_secrets": 150})


# -- spans and counters -------------------------------------------------------------

@pytest.fixture(scope="module", params=STEPS)
def one_round(request):
    """One warm streamed round of three blocks, the last ragged."""
    agg = _aggregator(request.param)
    x = _inputs(2 * CHUNK + 2)
    agg.aggregate(x, jax.random.PRNGKey(0))   # compiles both block shapes
    obs.reset_all()
    out = agg.aggregate(x, jax.random.PRNGKey(1))
    return {"agg": agg, "inputs": x, "out": out,
            "spans": obs.finished_spans(),
            "counters": metrics.counter_report("mesh.stream."),
            "phases": phase_report()}


def test_the_round_is_one_root_span_over_its_phases(one_round):
    spans = one_round["spans"]
    roots = [s for s in spans if s.name == "stream.round"]
    assert len(roots) == 1 and roots[0].parent_id is None
    root = roots[0]
    agg, x = one_round["agg"], one_round["inputs"]
    assert root.attributes == {
        "participants": x.shape[0], "dimension": DIM,
        "participants_chunk": CHUNK, "dim_chunk": agg.dim_chunk, "tiles": 3}
    children = [s for s in spans if s is not root]
    assert all(s.parent_id == root.span_id and s.trace_id == root.trace_id
               for s in children)
    names = [s.name for s in children]
    # three blocks: the third waits for the first step, then all for the last
    assert names == ["stream.feed", "stream.dispatch"] * 2 + [
        "stream.steps_sync", "stream.feed", "stream.dispatch",
        "stream.steps_sync", "stream.finale", "stream.readback"]
    assert sum(s.duration_s for s in children) <= root.duration_s
    assert set(one_round["phases"]) == {"stream.round", "stream.steps_sync",
                                        *PER_BLOCK, *PER_TILE}
    assert one_round["phases"]["stream.round"]["count"] == 1


def test_the_feed_spans_and_counters_carry_exact_bytes(one_round):
    x = one_round["inputs"]
    feeds = [s for s in one_round["spans"] if s.name == "stream.feed"]
    shapes = [[CHUNK, DIM], [CHUNK, DIM], [2, DIM]]
    assert [s.attributes for s in feeds] == [
        {"bytes": rows * dim * 8, "dtype": "int64", "shape": [rows, dim]}
        for rows, dim in shapes]
    assert one_round["counters"] == {
        "mesh.stream.rounds": 1, "mesh.stream.blocks": 3,
        "mesh.stream.bytes": x.nbytes}
    np.testing.assert_array_equal(one_round["out"], _plain_sum(x))


def test_a_padded_dim_tile_counts_the_bytes_after_the_pad():
    agg = _aggregator("xla")
    x = _inputs(3)[:, :DIM - 1]           # 23 columns pad to the grain, 24
    metrics.reset_counters()
    out = agg.aggregate(x, jax.random.PRNGKey(4))
    np.testing.assert_array_equal(out, _plain_sum(x))
    assert metrics.counter_report("mesh.stream.") == {
        "mesh.stream.rounds": 1, "mesh.stream.blocks": 1,
        "mesh.stream.bytes": 3 * DIM * 8}


@pytest.mark.parametrize("step", STEPS)
def test_the_accumulator_adds_carry_their_scope(step):
    agg = _aggregator(step)
    field = agg._field
    args = (jnp.zeros((CHUNK, DIM), jnp.int64), jax.random.PRNGKey(0),
            jax.random.PRNGKey(1), jnp.int32(0), jnp.int32(0),
            jnp.zeros((8, DIM // 3), field.dtype), jnp.zeros((DIM,), field.dtype))
    text = agg._step_fn((CHUNK, DIM)).lower(*args).as_text(debug_info=True)
    assert "sda.stream.acc" in text
    # what the kernel's step names stays as it was
    assert ("sda.mask_share" in text) == (step == "pallas")


# -- at most two blocks in flight ---------------------------------------------------

class _Lazy:
    """A step's undonated handle on a runtime that finishes nothing until
    it is waited for: the worst an asynchronous device may do."""

    def __init__(self, runtime, index):
        self.runtime, self.index = runtime, index

    def block_until_ready(self):
        # steps run in order: waiting for one finishes all before it, and
        # a step has run only once its block has landed
        self.runtime.finished = max(self.runtime.finished, self.index + 1)
        for block in self.runtime.blocks[:self.index + 1]:
            block.block_until_ready()
        return self


class _LazyBlock:
    """A block whose transfer ends only when it is waited for."""

    dtype, nbytes = np.dtype(np.int64), 0

    def __init__(self, runtime, shape):
        self.runtime, self.shape, self.landed = runtime, shape, False

    def block_until_ready(self):
        if not self.landed:
            self.landed = True
            self.runtime.in_transfer -= 1
        return self


class _LazyOwner:
    """What ``_drive_stream`` needs of an aggregator, on the lazy runtime:
    a block is live from ``make_block`` until its step has finished, and
    in transfer until somebody has waited for it."""

    participants_chunk, dim_chunk, _grain = 3, 12, 3
    _field = type("F", (), {"dtype": np.uint32})

    def __init__(self):
        self.made = self.finished = self.most_live = 0
        self.in_transfer = self.most_in_transfer = 0
        self.blocks, self._steps, self._finals = [], {}, {}

    def make_block(self, p0, p1, d0, d1, d_size):
        self.most_live = max(self.most_live, self.made - self.finished + 1)
        self.made += 1
        self.in_transfer += 1
        self.most_in_transfer = max(self.most_in_transfer, self.in_transfer)
        self.blocks.append(_LazyBlock(self, (p1 - p0, d_size)))
        return self.blocks[-1]

    def _step_fn(self, _shape):
        def step(block, key, round_key, pid0, dblk0, acc_shares, acc_mask):
            handle = _Lazy(self, self.made - 1)
            return handle, handle, handle
        return step

    def _final_fn(self, d_size):
        return lambda acc_shares, acc_mask: np.zeros(d_size, np.int64)


def test_the_tile_loop_waits_so_that_two_blocks_are_in_flight():
    assert streaming.BLOCKS_IN_FLIGHT == 2
    owner = _LazyOwner()
    out = streaming._drive_stream(
        owner, 30, 12, jax.random.PRNGKey(0), make_block=owner.make_block,
        make_accs=lambda d_size: (_Lazy(owner, -1), None), fetch=np.asarray)
    assert out.shape == (12,) and owner.made == 10
    assert owner.finished == 10                 # the last wait is for all
    assert owner.most_live == streaming.BLOCKS_IN_FLIGHT
    assert owner.most_in_transfer == 1          # a block lands before the next is made


def test_the_bound_holds_on_every_dim_tile(monkeypatch):
    owner = _LazyOwner()
    streaming._drive_stream(
        owner, 30, 36, jax.random.PRNGKey(0), make_block=owner.make_block,
        make_accs=lambda d_size: (_Lazy(owner, -1), None), fetch=np.asarray)
    assert owner.made == 30 and owner.most_live == 2
    assert owner.most_in_transfer == 1
    # a loop that never waits holds every block of a dim tile
    monkeypatch.setattr(streaming, "BLOCKS_IN_FLIGHT", 10**9)
    unbounded = _LazyOwner()
    streaming._drive_stream(
        unbounded, 30, 36, jax.random.PRNGKey(0),
        make_block=unbounded.make_block,
        make_accs=lambda d_size: (_Lazy(unbounded, -1), None), fetch=np.asarray)
    assert unbounded.most_live == 10


@pytest.mark.parametrize("step", STEPS)
def test_a_provider_sees_at_most_two_live_blocks_over_ten_chunks(step):
    """The real aggregator on device blocks: the loop holds no block
    beyond the one it has just dispatched, so with the block being made
    at most two are referenced at any ``get_block`` call, and the step
    two back has finished by then."""
    agg = _aggregator(step, chunk=3)
    x = _inputs(30)
    live, seen = [], []

    def get_block(p0, p1, d0, d1):
        gc.collect()
        live[:] = [ref for ref in live if ref() is not None]
        seen.append(len(live))
        block = jnp.asarray(x[p0:p1, d0:d1])
        live.append(weakref.ref(block))
        return block

    out = agg.aggregate_blocks(get_block, 30, DIM, jax.random.PRNGKey(6))
    np.testing.assert_array_equal(out, _plain_sum(x))
    assert len(seen) == 10 and max(seen) <= 1      # + the one being made
    gc.collect()
    assert all(ref() is None for ref in live)      # none outlives the round
