"""The configuration ``pod-packed8-chacha`` at toy size on the CPU: packed
Shamir sharing under ChaCha seed masks on the fused kernel (interpreted,
fed external bits), held to the chip benchmark's plain reference
(``benchmarks/chip/references/packed_chacha.py``: its own ChaCha20 and
its own Lagrange matrices, nothing of ``sda_tpu``) bit for bit; the masks'
sum the kernel path returns, window by window; and the pass that expands
the masks a block of rows at a time against the whole-block pass it
replaced."""

import importlib.util
import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec

from sda_tpu.fields import numtheory
from sda_tpu.fields.ops import FieldOps
from sda_tpu.mesh import StreamingAggregator, simpod
from sda_tpu.mesh.simpod import SimulatedPod, make_mesh
from sda_tpu.protocol import ChaChaMasking, NoMasking, PackedShamirSharing
from sda_tpu.utils import metrics

from util import chacha_mask_rows, external_bits

CHIP = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
CONFIG = json.loads((CHIP / "configs" / "pod-packed8-chacha.json").read_text())
MODULUS = CONFIG["scheme"]["prime_modulus"]
SEED_BITS = CONFIG["masking"]["seed_bitsize"]
DIM = 96
#: rows a device holds: one block of 8, a ragged second block, two blocks
ROWS = (8, 13, 16)
INTERPRETED = dict(pallas_interpret=True, pallas_external_bits_fn=external_bits)


def _reference():
    spec = importlib.util.spec_from_file_location(
        "packed_chacha_reference", CHIP / "references" / "packed_chacha.py")
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


def _pod(mesh_shape, dim: int = DIM) -> SimulatedPod:
    """The configuration's constructor call, on a mesh of virtual devices."""
    pod = SimulatedPod(_scheme(), ChaChaMasking(MODULUS, dim, SEED_BITS),
                       mesh=make_mesh(*mesh_shape), use_pallas=True, **INTERPRETED)
    assert pod.pallas_active and pod._sp is not None
    return pod


def _inputs(participants: int, dim: int = DIM) -> np.ndarray:
    rng = np.random.default_rng(participants * 10_007 + dim)
    return rng.integers(0, 1 << 20, size=(participants, dim), dtype=np.int64)


def _seeds(key, first_id: int, count: int) -> np.ndarray:
    """The round's seed words of participants ``first_id .. + count``."""
    words = simpod._chacha_seed_words(key, first_id + jnp.arange(count), SEED_BITS)
    return np.asarray(words)[:, :SEED_BITS // 32]


def _mask_total(key, first_id: int, count: int, first_draw: int, draws: int):
    """The reference's masks of those participants, summed: ``[draws]``."""
    total = np.zeros(draws, np.int64)
    for seed in _seeds(key, first_id, count):
        total = (total + REFERENCE.mask_stream(seed, first_draw, draws, MODULUS)) % MODULUS
    return total


# -- (a) the round ----------------------------------------------------------------

@pytest.mark.parametrize("rows", ROWS)
@pytest.mark.parametrize("mesh_shape", [(1, 1), (4, 2)], ids=["1x1", "4x2"])
def test_the_round_equals_the_reference_round_and_the_plain_sum(mesh_shape, rows):
    """``rows`` per 'p' shard; on the 4 x 2 mesh every device expands its
    own window of every local row's stream (``d_block0`` per 'd' shard)."""
    if len(jax.devices()) < mesh_shape[0] * mesh_shape[1]:
        pytest.skip("needs 8 virtual devices")
    participants = rows * mesh_shape[0]
    pod = _pod(mesh_shape)
    inputs = _inputs(participants)
    key = jax.random.PRNGKey(participants + 35)
    plain = REFERENCE.plain_round(inputs, _seeds(key, 0, participants), CONFIG["scheme"],
                                  MODULUS, np.random.default_rng(1))
    want = REFERENCE.on_host(inputs, MODULUS)
    np.testing.assert_array_equal(plain["aggregate"], want)
    np.testing.assert_array_equal(np.asarray(pod.aggregate(inputs, key)), want)


def test_the_round_through_aggregate_fn_at_a_width_the_grain_pads():
    """What the chip benchmark's driver calls: the raw program on resident
    uint32 residues at the padded shape (95 -> 96, grain lcm(3, 8))."""
    pod = _pod((1, 1), dim=95)
    inputs = _inputs(13, 95)
    padded = pod.padded_shape(13, 95)
    assert padded == (13, 96)
    resident = np.zeros(padded, np.uint32)
    resident[:13, :95] = inputs
    out = pod.aggregate_fn(*padded)(jnp.asarray(resident), jax.random.PRNGKey(4))
    np.testing.assert_array_equal(np.asarray(out)[:95], REFERENCE.on_host(inputs, MODULUS))


# -- (b) the masks' sum, window by window -------------------------------------------

def _stage_on_two_windows(masking, x, dev_key, round_key, first_id):
    """``_pallas_stage`` under ``shard_map`` over two 'd' shards, as
    ``SimulatedPod._local_round`` calls it: -> (shares, masks' sum)."""
    scheme, field = _scheme(), FieldOps.create(MODULUS)
    matrices = simpod._build_matrices(scheme)
    d_loc = x.shape[1] // 2

    def local(x):
        return simpod._pallas_stage(
            scheme, field, matrices[0], masking, x, dev_key, round_key=round_key,
            pid_base=first_id, d_block0=jax.lax.axis_index("d") * (d_loc // 8),
            interpret=True, external_bits_fn=external_bits)

    sharded = simpod._shard_map(
        local, mesh=make_mesh(1, 2), in_specs=PartitionSpec(None, "d"),
        out_specs=(PartitionSpec(None, "d"), PartitionSpec("d")))
    return jax.jit(sharded)(x)


@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs 2 virtual devices")
@pytest.mark.parametrize("rows", (5,) + ROWS)
def test_the_mask_sum_is_the_sum_of_the_reference_streams_window_by_window(rows):
    """Rows that do not fill the last block are expanded too (13 -> 16): the
    sum holds the streams of the ids after the last row, which cancel."""
    first_id, round_key = 7, jax.random.PRNGKey(11)
    expanded = simpod._scan_rows(rows, simpod._SCAN_CHUNK)[1]
    assert expanded == {5: 5, 8: 8, 13: 16, 16: 16}[rows]
    x = jnp.zeros((rows, DIM), jnp.uint32)
    _, mask_sum = _stage_on_two_windows(
        ChaChaMasking(MODULUS, DIM, SEED_BITS), x, jax.random.PRNGKey(2), round_key, first_id)
    mask_sum = np.asarray(mask_sum).astype(np.int64)
    for first_draw in (0, DIM // 2):
        np.testing.assert_array_equal(
            mask_sum[first_draw:first_draw + DIM // 2],
            _mask_total(round_key, first_id, expanded, first_draw, DIM // 2))


# -- (c) the blocked pass against the whole-block pass it replaced -------------------

def _whole_block_pass(x, dev_key, round_key, first_id, d_block0):
    """The kernel path's ChaCha branch until PR 35: the whole ``[S, d]``
    block masked row by row (the per-row masks are made here, since the
    program's mask stage folds before it orders: PR 40), then the kernel
    mask-free on the masked rows."""
    scheme, field = _scheme(), FieldOps.create(MODULUS)
    masks = chacha_mask_rows(field, round_key, first_id, x.shape[0], DIM,
                             d_block0, SEED_BITS)
    masked, mask_sum = field.add(x, masks), field.sum(masks, axis=0)
    shares, none = simpod._pallas_stage(
        scheme, field, simpod._build_matrices(scheme)[0], NoMasking(), masked, dev_key,
        interpret=True, external_bits_fn=external_bits)
    assert none is None
    return shares, mask_sum


@pytest.mark.parametrize("d_block0", [0, 5])
@pytest.mark.parametrize("rows", [3, 8, 16, 24])
def test_the_blocked_pass_equals_the_whole_block_pass_bit_for_bit(rows, d_block0):
    scheme, field = _scheme(), FieldOps.create(MODULUS)
    masking = ChaChaMasking(MODULUS, DIM, SEED_BITS)
    x = jnp.asarray(_inputs(rows).astype(np.uint32))
    dev_key, round_key, first_id = jax.random.PRNGKey(3), jax.random.PRNGKey(21), 40
    shares, mask_sum = simpod._pallas_stage(
        scheme, field, simpod._build_matrices(scheme)[0], masking, x, dev_key,
        round_key=round_key, pid_base=first_id, d_block0=d_block0,
        interpret=True, external_bits_fn=external_bits)
    want_shares, want_sum = _whole_block_pass(x, dev_key, round_key, first_id, d_block0)
    np.testing.assert_array_equal(np.asarray(mask_sum), np.asarray(want_sum))
    np.testing.assert_array_equal(np.asarray(shares), np.asarray(want_shares))


def test_a_ragged_last_block_expands_its_zero_rows_and_they_cancel():
    """13 rows: the blocked pass sums the masks of 16 ids, the whole-block
    pass over the same 16 (three zero rows appended) gives that sum, and
    what enters the kernel differs from the 13-row pass by exactly the
    three masks that the reveal subtracts again."""
    field = FieldOps.create(MODULUS)
    masking = ChaChaMasking(MODULUS, DIM, SEED_BITS)
    round_key, first_id = jax.random.PRNGKey(21), 40
    blocked = simpod._chacha_mask_sum(masking, field, round_key, first_id, 13, DIM, 0)
    whole16, whole13 = (field.sum(chacha_mask_rows(field, round_key, first_id, rows,
                                                   DIM, 0, SEED_BITS), axis=0)
                        for rows in (16, 13))
    np.testing.assert_array_equal(np.asarray(blocked), np.asarray(whole16))
    extra = (np.asarray(blocked).astype(np.int64) - np.asarray(whole13)) % MODULUS
    np.testing.assert_array_equal(extra, _mask_total(round_key, first_id + 13, 3, 0, DIM))


def test_the_compiled_pass_holds_one_block_of_draws_whatever_the_rows():
    """The point of the blocked pass, as far as the CPU can show it: no
    array in the lowered round has the rows' extent but the input itself
    and the bits this test feeds the interpreted kernel."""
    pod = _pod((1, 1))
    text = pod.aggregate_fn(64, DIM).lower(
        jax.ShapeDtypeStruct((64, DIM), jnp.uint32),
        jax.ShapeDtypeStruct((2,), jnp.uint32)).as_text()
    per_row = {dims for dims in re.findall(r"tensor<([\dx]+)x(?:ui|i)\d+>", text)
               if dims.split("x")[0] == "64" and len(dims.split("x")) > 1}
    fed_bits = f"64x{2 * CONFIG['scheme']['privacy_threshold']}x128"
    assert per_row <= {f"64x{DIM}", fed_bits}, per_row


# -- (d) the counters ------------------------------------------------------------------

def test_the_kernel_path_counts_whole_blocks_of_rows():
    pod = _pod((1, 1), dim=95)
    report = metrics.counter_report("mesh.mask.")
    before = (report.get("mesh.mask.chacha_calls", 0), report.get("mesh.mask.chacha_blocks", 0))
    inputs = _inputs(13, 95)
    out = pod.aggregate(inputs, jax.random.PRNGKey(1))
    np.testing.assert_array_equal(np.asarray(out), REFERENCE.on_host(inputs, MODULUS))
    report = metrics.counter_report("mesh.mask.")
    assert report["mesh.mask.chacha_calls"] - before[0] == 1
    assert report["mesh.mask.chacha_blocks"] - before[1] == 16 * 96 // 8
    assert simpod._chacha_blocks(pod.masking, 1200, 1_000_008, 1) == 1200 * 125_001
    assert simpod._chacha_blocks(pod.masking, 5, 96, 2) == 2 * 5 * 12


# -- (e) the streamed driver -----------------------------------------------------------

@pytest.mark.parametrize("chunk, rows", [(8, 16), (12, 20)])
def test_the_streamed_kernel_step_under_chacha_equals_the_reference(chunk, rows):
    """Two participant blocks through ``StreamingAggregator``'s kernel step:
    a traced ``pid_base`` a block, a ragged second block."""
    agg = StreamingAggregator(
        _scheme(), ChaChaMasking(MODULUS, DIM, SEED_BITS), participants_chunk=chunk,
        use_pallas=True, **INTERPRETED)
    assert agg.pallas_active
    inputs = _inputs(rows)
    key = jax.random.PRNGKey(rows)
    out = agg.aggregate(inputs, key)
    plain = REFERENCE.plain_round(inputs, _seeds(key, 0, rows), CONFIG["scheme"], MODULUS,
                                  np.random.default_rng(2))
    np.testing.assert_array_equal(np.asarray(out), plain["aggregate"])
    np.testing.assert_array_equal(np.asarray(out), REFERENCE.on_host(inputs, MODULUS))
