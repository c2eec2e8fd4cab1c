"""The on-core cipher (``fields/chacha_kernel.py``) against the XLA block
function it replaces on a TPU, bit for bit, in interpret mode on the CPU:
the kernel's fold of the rows' reduced draws, the host stream it is a
window of, the draws whose halves need a reduction, and the pods whose
mask stages take it where the platform they are built for says so."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from sda_tpu.fields import chacha, chacha_jax, chacha_kernel, numtheory
from sda_tpu.fields.ops import FieldOps
from sda_tpu.mesh import simpod
from sda_tpu.mesh.simpod import SimulatedPod, make_mesh
from sda_tpu.protocol import AdditiveSharing, ChaChaMasking, PackedShamirSharing
from sda_tpu.utils import metrics

from util import external_bits

MODULUS = 536870233  # 2^29 - 679: a uint32 Solinas field
FIELD = FieldOps.create(MODULUS)


def _folds(rows, nblocks, seed_bits, pid_base, d_block0):
    """(kernel's, XLA block function's) fold of ``rows`` participants'
    masks, both under ``jit`` with ``pid_base`` and ``d_block0`` traced."""
    masking = ChaChaMasking(MODULUS, 8 * nblocks, seed_bits)
    round_key = jax.random.PRNGKey(rows * 1000 + nblocks)

    def both(pid_base, d_block0):
        args = (masking, FIELD, round_key, pid_base, rows, 8 * nblocks, d_block0)
        return (simpod._chacha_kernel_fold(*args, interpret=True),
                simpod._chacha_block_fold(*args))

    return [np.asarray(a) for a in jax.jit(both)(jnp.int32(pid_base),
                                                  jnp.int32(d_block0))]


@pytest.mark.parametrize("seed_bits", [128, 256])
@pytest.mark.parametrize("nblocks", [256, 1001])
@pytest.mark.parametrize("rows", [1, 7, 8, 9, 24])
def test_the_kernels_fold_is_the_xla_fold_bit_for_bit(rows, nblocks, seed_bits):
    """``f.sum(f.from_u64(stream_u64_words_at(...)), axis=0)``: rows under,
    at and over a scan block, block counts of whole and ragged lane tiles,
    a traced block counter and first participant, 128- and 256-bit seeds."""
    got, want = _folds(rows, nblocks, seed_bits, pid_base=rows + 3, d_block0=37)
    assert got.shape == (8, nblocks) and got.dtype == np.uint32
    assert got.max() < MODULUS
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("nblocks", [3 * 3072, 125_000, 125_001],
                         ids=["whole-vectors", "additive-cell", "packed-cell"])
def test_the_kernels_fold_is_the_xla_fold_at_the_cells_block_counts(nblocks):
    """Block counts of whole vectors, and the cells' own: 125,000 and
    125,001 blocks run 41 grid steps of 3072, the pad of 952 and 951
    blocks sliced off."""
    got, want = _folds(2, nblocks, 128, pid_base=5, d_block0=3 * nblocks)
    np.testing.assert_array_equal(got, want)


def _host_stream(seed, block0: int, nblocks: int) -> np.ndarray:
    """``fields/chacha.py``'s draws of blocks [block0, block0 + nblocks)."""
    words = chacha.chacha_block_words(seed, block0, nblocks).reshape(-1)
    words = words.astype(np.uint64)
    return (words[1::2] << np.uint64(32)) | words[0::2]


@pytest.mark.parametrize("seed", [[0x9E3779B9, 7, 0, 1], [0xFFFFFFFF] * 8,
                                  list(range(11, 19))])
def test_one_rows_fold_in_element_order_is_the_host_stream_mod_p(seed):
    nblocks, block0 = 1001, 12_345
    words = np.zeros((1, 8), np.uint32)
    words[0, :len(seed)] = seed
    fold = jax.jit(lambda s, c: chacha_kernel.mask_fold(
        s, c, nblocks=nblocks, sp=FIELD.sp, interpret=True))(
            jnp.asarray(words), jnp.int32(block0))
    got = np.asarray(chacha_jax.element_order(fold)).astype(np.uint64)
    np.testing.assert_array_equal(
        got, _host_stream(seed, block0, nblocks) % np.uint64(MODULUS))


@pytest.mark.parametrize("half,block,pair", [("high", 169_461, 7), ("low", 17_886, 0)])
def test_a_draw_with_a_half_above_the_last_multiple_of_p_reduces_exactly(half, block, pair):
    """2^32 holds 8 whole multiples of p, and a half in the 5,432 values
    above 8p is where a reduction short by one subtraction of p would show.
    Seed [1, 2, 3, 4] draws one at ``block``, ``pair`` (found on the host:
    about one draw in 790,000)."""
    seed, block0, nblocks = [1, 2, 3, 4], block - 3, 8
    draws = _host_stream(seed, block0, nblocks)
    at = 8 * 3 + pair
    halves = {"high": draws >> np.uint64(32), "low": draws & np.uint64(0xFFFFFFFF)}
    assert halves[half][at] >= np.uint64((1 << 32) // MODULUS * MODULUS)
    words = np.zeros((1, 8), np.uint32)
    words[0, :4] = seed
    fold = chacha_kernel.mask_fold(jnp.asarray(words), block0, nblocks=nblocks,
                                   sp=FIELD.sp, interpret=True)
    got = np.asarray(chacha_jax.element_order(fold))
    assert int(got[at]) == int(draws[at]) % MODULUS
    np.testing.assert_array_equal(
        got.astype(np.uint64), draws % np.uint64(MODULUS))


def test_the_kernel_body_is_a_loop_not_an_unrolled_cipher():
    """The body traces to a few hundred equations however many rows and
    blocks: the twenty rounds are a ten-step loop (an unrolled body for 8
    rows is some 7,700, each traced and lowered on every warm start)."""
    from jax._src import core

    def equations(jaxpr):
        return sum(1 + sum(equations(sub) for sub in core.jaxprs_in_params(eqn.params))
                   for eqn in jaxpr.eqns)

    counts = [equations(jax.make_jaxpr(lambda s, c: chacha_kernel.mask_fold(
        s, c, nblocks=nblocks, sp=FIELD.sp))(
            jnp.zeros((rows, 8), jnp.uint32), jnp.int32(0)).jaxpr)
        for rows, nblocks in ((8, 125_000), (1200, 125_001))]
    assert counts[0] == counts[1] < 1000, counts


# -- the pods, with the lowering on the CPU taking the kernel as a TPU's does ---------

@pytest.fixture
def on_core_on_the_cpu(monkeypatch):
    """Steps built for the CPU take the on-core cipher, interpreted."""
    monkeypatch.setitem(simpod._ON_CORE_CIPHER, "cpu", "interpret")


def _packed_scheme():
    t, p, w2, w3 = numtheory.generate_packed_params(3, 8, 28)
    assert p == MODULUS
    return PackedShamirSharing(3, 8, t, p, w2, w3)


def _pod(kind: str, dim: int) -> SimulatedPod:
    masking = ChaChaMasking(MODULUS, dim, 128)
    if kind == "additive":
        return SimulatedPod(AdditiveSharing(3, MODULUS), masking, mesh=make_mesh(1, 1))
    return SimulatedPod(_packed_scheme(), masking, mesh=make_mesh(1, 1), use_pallas=True,
                        pallas_interpret=True, pallas_external_bits_fn=external_bits)


def _mask_sums(dim: int, cipher: str):
    """Two mask sums of 13 rows from id 40 at block 5 with ``cipher``: the
    fold of the 13 rows as they are, ordered once (``_chacha_mask_fold``,
    one call a block of rows), and ``_chacha_mask_sum`` (what both steps
    take, one call for all rows)."""
    masking = ChaChaMasking(MODULUS, dim, 128)
    round_key = jax.random.PRNGKey(21)

    def both(pid_base, d_block0):
        stage = simpod._element_order(simpod._chacha_mask_fold(
            masking, FIELD, round_key, pid_base, 13, dim, d_block0, cipher))
        return stage, simpod._chacha_mask_sum(masking, FIELD, round_key, pid_base,
                                              13, dim, d_block0, cipher)

    return [np.asarray(a) for a in jax.jit(both)(jnp.int32(40), jnp.int32(5))]


def test_the_mask_stages_with_the_kernel_give_the_xla_ciphers_sums():
    dim = 96
    stage_xla, sum_xla = _mask_sums(dim, "xla")
    stage_kernel, sum_kernel = _mask_sums(dim, "interpret")
    np.testing.assert_array_equal(stage_kernel, stage_xla)
    # the XLA cipher expands the kernel path's 13 rows as two blocks of 8:
    # the three rows past the last cancel in the round, and the kernel
    # expands the rows as they are
    three = np.asarray(FIELD.sum(chacha_jax.element_order(simpod._chacha_masks(
        ChaChaMasking(MODULUS, dim, 128), FIELD, jax.random.PRNGKey(21), 53, 3,
        dim, 5)), axis=0))
    np.testing.assert_array_equal(sum_kernel, stage_xla)
    np.testing.assert_array_equal(FIELD.add(jnp.asarray(sum_kernel), three), sum_xla)


def _kernel_blocks() -> int:
    return metrics.counter_report("mesh.mask.").get("mesh.mask.chacha_kernel_blocks", 0)


@pytest.mark.parametrize("on_core", [False, True], ids=["xla-cipher", "kernel"])
@pytest.mark.parametrize("kind", ["additive", "packed"])
def test_a_pod_aggregates_the_plain_sum_with_either_cipher_and_counts_the_kernels_blocks(
        request, kind, on_core):
    participants, dim = 16, 96
    if on_core:
        request.getfixturevalue("on_core_on_the_cpu")
    pod = _pod(kind, dim)
    rng = np.random.default_rng(participants + dim)
    inputs = rng.integers(0, 1 << 20, size=(participants, dim), dtype=np.int64)
    before = _kernel_blocks()
    out = np.asarray(pod.aggregate(inputs, jax.random.PRNGKey(3)))
    np.testing.assert_array_equal(out, inputs.sum(axis=0) % MODULUS)
    assert _kernel_blocks() - before == (participants * dim // 8 if on_core else 0)


def test_the_lowered_round_holds_the_kernel_only_where_the_platform_takes_it(request):
    """A pod built for the CPU lowers the XLA cipher and no kernel; where
    steps built for the CPU take the kernel, one ``sda_chacha_mask_fold`` a
    round under ``sda.mask.chacha`` and no cipher of the XLA block
    function."""
    def lowered_text():
        return _pod("additive", 96).aggregate_fn(16, 96).lower(
            jax.ShapeDtypeStruct((16, 96), jnp.uint32),
            jax.ShapeDtypeStruct((2,), jnp.uint32)).as_text(debug_info=True)

    xla = lowered_text()
    request.getfixturevalue("on_core_on_the_cpu")
    kernel = lowered_text()
    assert "sda_chacha_mask_fold" not in xla and "sda.mask.reduce" in xla
    assert "sda_chacha_mask_fold" in kernel and "sda.mask.reduce" not in kernel
    assert "sda.mask/sda.mask.chacha/" in kernel


@pytest.mark.parametrize("devices,modulus,cipher", [
    ("tpu", MODULUS, "kernel"),
    ("cpu", MODULUS, "xla"),
    ("tpu", 433, "xla"),                     # an int64 field: no kernel
    ("tpu+cpu", MODULUS, "xla"),
])
def test_the_cipher_is_the_platforms_and_the_fields(devices, modulus, cipher):
    class Device:
        def __init__(self, platform):
            self.platform = platform

    built_for = np.array([Device(name) for name in devices.split("+")])
    assert simpod._chacha_cipher(FieldOps.create(modulus), built_for) == cipher
