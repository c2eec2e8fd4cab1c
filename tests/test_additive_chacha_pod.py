"""Additive n-of-n sharing under ChaCha seed masks through ``SimulatedPod``'s
XLA step (the deployment ``pod-additive3-chacha`` of the chip benchmark),
held to the benchmark's plain reference -- its own ChaCha20, nothing of
``sda_tpu`` -- and not to ``fields/chacha.py``."""

import importlib.util
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sda_tpu.fields import numtheory
from sda_tpu.fields.ops import FieldOps
from sda_tpu.mesh import simpod
from sda_tpu.mesh.simpod import SimulatedPod, default_mesh_shape, make_mesh
from sda_tpu.protocol import (AdditiveSharing, ChaChaMasking, FullMasking,
                              PackedShamirSharing)
from sda_tpu.utils import metrics

from util import chacha_mask_rows, lowered_ops

MODULUS = 536870233  # 2^29 - 679: the uint32 fast path
SHARES = 3
SEED_BITS = 128


def _load_reference():
    path = (Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
            / "references" / "additive_chacha.py")
    spec = importlib.util.spec_from_file_location("additive_chacha_reference", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = _load_reference()


def _pod(dim: int, masking=None) -> SimulatedPod:
    mesh = make_mesh(*default_mesh_shape(1, SHARES))
    masking = masking or ChaChaMasking(MODULUS, dim, SEED_BITS)
    return SimulatedPod(AdditiveSharing(SHARES, MODULUS), masking, mesh=mesh,
                        use_pallas=False)


def _inputs(participants: int, dim: int) -> np.ndarray:
    rng = np.random.default_rng(participants * 10_007 + dim)
    return rng.integers(0, 1 << 20, size=(participants, dim), dtype=np.int64)


def _through(pod: SimulatedPod, entry: str, inputs: np.ndarray, key):
    """One round through ``aggregate()``, or through the raw callable of
    ``aggregate_fn()`` on the zero-padded uint32 residues."""
    if entry == "aggregate":
        return np.asarray(pod.aggregate(inputs, key))
    rows, dim = inputs.shape
    padded = pod.padded_shape(rows, dim)
    resident = np.zeros(padded, np.uint32)
    resident[:rows, :dim] = inputs
    return np.asarray(pod.aggregate_fn(*padded)(jnp.asarray(resident), key))[:dim]


# -- (a) the round ----------------------------------------------------------------

@pytest.mark.parametrize("dim", [96, 95, 1000])
@pytest.mark.parametrize("participants", [8, 13, 24])
@pytest.mark.parametrize("entry", ["aggregate", "aggregate_fn"])
def test_pod_round_equals_the_reference_round_and_the_plain_sum(entry, participants, dim):
    pod = _pod(dim)
    assert pod.pallas_active is False and pod._sp is not None
    inputs = _inputs(participants, dim)
    key = jax.random.PRNGKey(participants + dim)
    seeds = np.asarray(simpod._chacha_seed_words(
        key, jnp.arange(participants), SEED_BITS))[:, :SEED_BITS // 32]
    plain = reference.plain_round(inputs, seeds, SHARES, MODULUS,
                                  np.random.default_rng(1))
    want = reference.on_host(inputs, MODULUS)
    assert np.array_equal(plain["aggregate"], want)
    assert np.array_equal(_through(pod, entry, inputs, key), want)


# -- (b) the mask stage against the plain ChaCha20 -----------------------------------

@pytest.mark.parametrize("d_block0", [0, 5])
def test_mask_stage_is_the_reference_stream_of_the_rounds_seeds(d_block0):
    rows, dim, first_id = 5, 64, 7
    field = FieldOps.create(MODULUS)
    round_key = jax.random.PRNGKey(11)
    mask_sum = simpod._chacha_mask_sum(
        ChaChaMasking(MODULUS, dim, SEED_BITS), field, round_key, first_id, rows,
        dim, d_block0, cipher="xla")
    seeds = np.asarray(simpod._chacha_seed_words(
        round_key, first_id + jnp.arange(rows), SEED_BITS))
    streams = np.stack([
        reference.mask_stream(seed[:SEED_BITS // 32], 8 * d_block0, dim, MODULUS)
        for seed in seeds])
    # row for row: the stage's three steps on every row, ordered a row
    masks = chacha_mask_rows(field, round_key, first_id, rows, dim, d_block0, SEED_BITS)
    assert np.array_equal(np.asarray(masks), streams)
    # the stage: the rows' masks folded, then ordered
    assert np.array_equal(np.asarray(mask_sum), streams.sum(axis=0) % MODULUS)


@pytest.mark.parametrize("participants", [3, 8, 13])
def test_a_pod_rounds_masks_are_the_reference_stream_mod_p(participants):
    """The masks of each block of rows, block by block as the XLA block
    function expands them, what the round shares (the fold of the rows,
    each with its mask) and the mask total it subtracts, against the
    reference's stream reduced mod p on the host: the aggregate alone
    cannot tell (masks cancel whatever they are)."""
    dim = 96
    pod = _pod(dim)
    field, chunk = pod._field, pod.scan_chunk
    round_key = jax.random.PRNGKey(participants)
    x = jnp.asarray(_inputs(participants, dim) % MODULUS, field.dtype)
    chunk, scanned_rows = simpod._scan_rows(participants, chunk)
    seeds = np.asarray(simpod._chacha_seed_words(
        round_key, jnp.arange(scanned_rows), SEED_BITS))
    want = np.stack([reference.mask_stream(seed[:SEED_BITS // 32], 0, dim, MODULUS)
                     for seed in seeds])
    for first in range(0, participants, chunk):
        count = min(chunk, participants - first)
        mask_sum = simpod._chacha_mask_sum(
            pod.masking, field, round_key, first, count, dim, 0, cipher="xla")
        per_row = chacha_mask_rows(field, round_key, first, count, dim, 0, SEED_BITS)
        assert np.array_equal(np.asarray(per_row), want[first:first + count])
        assert np.array_equal(np.asarray(mask_sum),
                              want[first:first + count].sum(axis=0) % MODULUS)
    shares, mask_total = simpod._scan_combine(
        pod.scheme, field, None, pod.masking, x, jax.random.PRNGKey(0),
        round_key=round_key, pid_base=0, d_block0=0)
    # the scan expands whole blocks: padded rows carry masks too
    assert np.array_equal(np.asarray(mask_total), want.sum(axis=0) % MODULUS)
    # what enters sharing: the fold of the rows, each with its mask (the
    # additive share rows sum to it)
    masked = np.asarray(x).astype(np.int64).sum(axis=0) + want.sum(axis=0)
    assert np.array_equal(np.asarray(shares).astype(np.int64).sum(axis=0) % MODULUS,
                          masked % MODULUS)


def test_a_128_bit_seed_fills_four_key_words_and_leaves_four_zero():
    seeds = np.asarray(simpod._chacha_seed_words(
        jax.random.PRNGKey(5), jnp.arange(6), SEED_BITS))
    assert seeds.shape == (6, 8) and seeds.dtype == np.uint32
    assert not seeds[:, 4:].any() and seeds[:, :4].all(axis=1).any()
    # the key the cipher sees is the seed zero-padded: same stream either way
    short = reference.mask_stream(seeds[0][:4], 3, 40, MODULUS)
    assert np.array_equal(short, reference.mask_stream(seeds[0], 3, 40, MODULUS))


def test_the_references_block_function_is_chacha20():
    # the zero key's keystream, counters 0 and 1 (draft-agl-tls-chacha20poly1305)
    words = reference.chacha20_block([0] * 8, [0, 1])
    assert words[0].astype("<u4").tobytes().hex().startswith("76b8e0ada0f13d90405d6ae55386bd28")
    assert words[1].astype("<u4").tobytes().hex().startswith("9f07e7be5551387a98ba977c732d080d")


# -- (c) the share stage, additive -----------------------------------------------------

def test_additive_share_rows_sum_to_the_masked_sum_and_are_redrawn_per_key():
    rows, dim = 6, 48
    field = FieldOps.create(MODULUS)
    scheme = AdditiveSharing(SHARES, MODULUS)
    masked = jnp.asarray(_inputs(rows, dim) % MODULUS, field.dtype)
    out = [np.asarray(simpod._share_combine(
        scheme, field, None, field.sum(masked, axis=0),
        simpod._share_draws(scheme, field, rows, dim, jax.random.PRNGKey(k))
    )).astype(np.int64) for k in (0, 1)]
    want = np.asarray(masked).astype(np.int64).sum(axis=0) % MODULUS
    for shares in out:
        assert shares.shape == (SHARES, dim)
        assert shares.min() >= 0 and shares.max() < MODULUS
        assert np.array_equal(shares.sum(axis=0) % MODULUS, want)
    assert not np.array_equal(out[0][:SHARES - 1], out[1][:SHARES - 1])


@pytest.mark.parametrize("shares", [2, 3, 8])
@pytest.mark.parametrize("modulus", [MODULUS, 433])
def test_additive_share_stage_is_the_fold_of_each_participants_shares(modulus, shares):
    # the stage folds the free rows where they are drawn and subtracts the
    # folded rows from the secrets' sum (shares = 2: one subtraction, no
    # other); the federated participant draws the same rows from the same
    # key through the generic uniform_mod, on either field path
    from sda_tpu.fields import sharing

    rows, dim = 5, 40
    field = FieldOps.create(modulus)
    assert (field.sp is not None) == (modulus == MODULUS)
    key = jax.random.PRNGKey(shares)
    masked = field.to_residues(_inputs(rows, dim) % modulus)
    scheme = AdditiveSharing(shares, modulus)
    stage = np.asarray(simpod._share_combine(
        scheme, field, None, field.sum(masked, axis=0),
        simpod._share_draws(scheme, field, rows, dim, key))).astype(np.int64)
    per = sharing.additive_share(key, jnp.asarray(masked, jnp.int64),
                                 share_count=shares, modulus=modulus)
    assert per.shape == (rows, shares, dim)
    assert np.array_equal(stage, np.asarray(per).sum(axis=0) % modulus)


@pytest.mark.parametrize("shares", [2, 8])
@pytest.mark.parametrize("entry", ["aggregate", "aggregate_fn"])
def test_the_xla_steps_aggregate_is_the_plain_sum_for_two_and_eight_clerks(entry, shares):
    participants, dim = 11, 96
    mesh = make_mesh(*default_mesh_shape(1, shares))
    pod = SimulatedPod(AdditiveSharing(shares, MODULUS),
                       ChaChaMasking(MODULUS, dim, SEED_BITS), mesh=mesh,
                       use_pallas=False)
    inputs = _inputs(participants, dim)
    got = _through(pod, entry, inputs, jax.random.PRNGKey(shares))
    assert np.array_equal(got, inputs.sum(axis=0) % MODULUS)


# -- (d) the scopes in the lowered round ---------------------------------------------

@pytest.mark.parametrize("masking", ["chacha", "full"])
def test_the_xla_step_names_the_cipher_and_the_reduction_only_under_chacha(masking):
    dim = 96
    pod = _pod(dim, FullMasking(MODULUS) if masking == "full" else None)
    lowered = pod.aggregate_fn(8, dim).lower(
        jnp.zeros((8, dim), jnp.uint32), jax.random.PRNGKey(0))
    text = lowered.as_text(debug_info=True)
    assert "sda.mask" in text and "sda.share" in text
    assert ("sda.mask.chacha" in text) == (masking == "chacha")
    assert ("sda.mask.reduce" in text) == (masking == "chacha")
    assert ("sda.mask.relayout" in text) == (masking == "chacha")
    # nested, as the compiler composes the op names a trace shows (the
    # masks' sum runs its own scan): a trace's sda.mask total still holds
    # all three
    if masking == "chacha":
        paths = [path for _, path in lowered_ops(lowered)]
        for part in ("chacha", "reduce", "relayout"):
            under = [path for path in paths if f"sda.mask.{part}" in path]
            assert under and all(
                "sda.mask" in path[:path.index(f"sda.mask.{part}")]
                for path in under), part


def _remainders_on_64_bits(text: str) -> list:
    return [line for line in text.splitlines()
            if "stablehlo.remainder" in line and re.search(r"[<x]u?i64>", line)]


@pytest.mark.parametrize("round_, modulus, on_64_bits", [
    ("additive-chacha", MODULUS, False),
    ("packed-int64-fed", MODULUS, False),
    ("additive-chacha", 433, True),   # off the fast path: the generic modulo
    ("packed-int64-fed", 433, True),
])
def test_a_round_over_a_solinas_modulus_lowers_no_64_bit_remainder(
        round_, modulus, on_64_bits):
    """The chip has no 64-bit integers: a ``remainder`` on one is a
    multi-word division emulated in 32-bit lanes (55 % of the additive
    round and 33 % of the host-fed one until PR 32). Over a Solinas modulus
    the mask draws (``FieldOps.from_u64``) and int64 inputs
    (``fastfield.to_residues32``) are reduced from their uint32 halves."""
    dim, mesh = 96, make_mesh(*default_mesh_shape(1, SHARES))
    if round_ == "additive-chacha":
        pod = SimulatedPod(AdditiveSharing(SHARES, modulus),
                           ChaChaMasking(modulus, dim, SEED_BITS), mesh=mesh)
        inputs = jnp.zeros((8, dim), jnp.uint32)
    else:
        t, prime, w2, w3 = ((4, 433, 354, 150) if modulus == 433 else
                            numtheory.generate_packed_params(3, 8, 28))
        pod = SimulatedPod(PackedShamirSharing(3, 8, t, prime, w2, w3),
                           FullMasking(prime), mesh=mesh)
        inputs = jnp.zeros((8, dim), jnp.int64)  # as aggregate() feeds a host matrix
    assert (pod._sp is None) == on_64_bits
    text = pod.aggregate_fn(8, dim).lower(inputs, jax.random.PRNGKey(0)).as_text()
    assert bool(_remainders_on_64_bits(text)) == on_64_bits


# -- (e) the counters -------------------------------------------------------------------

def _mask_counters() -> tuple:
    report = metrics.counter_report("mesh.mask.")
    return (report.get("mesh.mask.chacha_calls", 0),
            report.get("mesh.mask.chacha_blocks", 0))


@pytest.mark.parametrize("entry", ["aggregate", "aggregate_fn"])
@pytest.mark.parametrize("masking", ["chacha", "full"])
def test_a_dispatch_counts_its_chacha_blocks_and_only_under_chacha(masking, entry):
    participants, dim = 13, 95  # pads to 13 x 96; the scan expands 16 rows
    pod = _pod(dim, FullMasking(MODULUS) if masking == "full" else None)
    inputs = _inputs(participants, dim)
    before = _mask_counters()
    for round_index in range(2):
        out = _through(pod, entry, inputs, jax.random.PRNGKey(round_index))
        assert np.array_equal(out, reference.on_host(inputs, MODULUS))
    calls, blocks = (after - b for after, b in zip(_mask_counters(), before))
    if masking == "full":
        assert (calls, blocks) == (0, 0)
    else:
        scanned_rows = -(-participants // pod.scan_chunk) * pod.scan_chunk
        d_pad = pod.padded_shape(participants, dim)[1]
        assert (calls, blocks) == (2, 2 * scanned_rows * d_pad // 8)
