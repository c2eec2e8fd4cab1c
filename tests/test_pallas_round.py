"""Fused Pallas round kernel — interpret-mode exactness on CPU.

`external` randomness mode feeds deterministic bits so the kernel's uint32
Solinas arithmetic is checkable without TPU hardware. Round-level cases run
the one stage around the kernel (``SimulatedPod`` on a 1x1 mesh): the round
must equal the plain participant sum (masks and share randomness cancel).
Cases about the kernel's own parameters (``p_block``, ``p_tile``, ``tile``,
``tree_fold``) call it directly and hold its combined shares and mask
totals to the XLA fast-path shares computed from the same bits: the round
is exact for any randomness and so cannot see a wrong draw.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sda_tpu.fields import fastfield, numtheory
from sda_tpu.fields.pallas_round import (
    _participant_block,
    _participant_tile,
    _uniform_from_bits,
    column_tile,
    fused_mask_share_combine,
)
from sda_tpu.fields.sharing import batch_columns
from sda_tpu.mesh import SimulatedPod, make_mesh
from sda_tpu.protocol import FullMasking, NoMasking, PackedShamirSharing

from util import external_bits, one_chip_pallas_pod


def fast_scheme():
    t, p, w2, w3 = numtheory.generate_packed_params(3, 8, 28)
    return PackedShamirSharing(3, 8, t, p, w2, w3)


class SameBits:
    """One set of residues and pre-drawn bits, the kernel's arguments for
    them, and what the per-participant XLA share path makes of them."""

    def __init__(self, P, seed, masked, d=384):
        s = fast_scheme()
        self.sp = fastfield.SolinasPrime.try_from(s.prime_modulus)
        self.k, self.t = k, t = s.secret_count, s.privacy_threshold
        self.m_host = numtheory.share_matrix_for(s)
        self.P, self.masked = P, masked
        rng = np.random.default_rng(seed)
        self.x = jnp.asarray(
            rng.integers(0, s.prime_modulus, size=(P, d)).astype(np.uint32))
        self.bits = external_bits(
            jax.random.PRNGKey(seed), P, (k + t) if masked else t, d // k)

    def kernel(self, **params):
        """(combined shares, mask totals) of the fused kernel."""
        x_sum = batch_columns(fastfield.modsum32(self.x, self.sp, axis=0), self.k)
        return fused_mask_share_combine(
            x_sum, self.P, 0, self.sp, self.m_host, self.t, self.masked,
            tile=128, external_bits=self.bits, interpret=True, **params)

    def xla(self):
        """The same draws through the fastfield helpers, shared participant
        by participant and summed."""
        k, t, sp, bits = self.k, self.t, self.sp, self.bits
        cols = batch_columns(self.x, k)                         # [P, k, B]
        mask_tot = jnp.zeros(cols.shape[1:], jnp.uint32)
        if self.masked:
            mask = _uniform_from_bits(bits[:, 0:k, :], bits[:, k:2 * k, :], sp)
            cols = fastfield.modadd32(cols, mask, sp)
            mask_tot = fastfield.modsum32(mask, sp, axis=0)
            bits = bits[:, 2 * k:, :]
        rand = _uniform_from_bits(bits[:, 0:t, :], bits[:, t:2 * t, :], sp)
        zeros = jnp.zeros((self.P, 1, cols.shape[-1]), jnp.uint32)
        per_part = fastfield.modmatmul32(
            self.m_host, jnp.concatenate([zeros, cols, rand], axis=1), sp)
        return fastfield.modsum32(per_part, sp, axis=0), mask_tot

    def assert_kernel_matches_xla(self, **params):
        got, want = self.kernel(**params), self.xla()
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
        np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
        return got


@pytest.mark.parametrize("masking", ["none", "full"])
def test_pallas_round_equals_plain_sum(masking):
    s = fast_scheme()
    mask = FullMasking(s.prime_modulus) if masking == "full" else NoMasking()
    pod = one_chip_pallas_pod(s, mask)
    rng = np.random.default_rng(21)
    inputs = rng.integers(0, 1 << 20, size=(5, 500))  # B=167 -> padded to 256
    out = np.asarray(pod.aggregate(inputs, jax.random.PRNGKey(8)))
    np.testing.assert_array_equal(out, inputs.sum(axis=0) % s.prime_modulus)


def test_pallas_kernel_matches_xla_shares_same_bits():
    """Kernel combined-shares == XLA packed_share32 fed identical residues."""
    SameBits(P=4, seed=22, masked=True).assert_kernel_matches_xla()


@pytest.mark.parametrize("masked", [True, False], ids=["masked", "mask-free"])
@pytest.mark.parametrize("P,p_tile", [
    (96, 32),    # three visits of two whole blocks of 16
    (100, 50),   # two visits, each three blocks and a tail of 2
    (100, 20),   # five visits, each one block and a tail of 4
])
def test_pallas_round_streams_participant_tiles(P, p_tile, masked):
    """P larger than one participant tile: with external bits the kernel's
    second grid axis must start the output block on the first visit and
    accumulate across revisits of it (the lenet-60k VMEM-OOM regression:
    all P in one block). An explicit ``p_tile`` forces the revisits — the
    auto tile would fit all of P in one block at these shapes — and one
    that is no multiple of 16 makes every visit fold a tail too."""
    SameBits(P=P, seed=23, masked=masked).assert_kernel_matches_xla(p_tile=p_tile)


def test_pallas_round_refuses_a_p_tile_that_does_not_divide_p():
    with pytest.raises(ValueError, match="p_tile=32 must divide P=100"):
        SameBits(P=100, seed=23, masked=True).kernel(p_tile=32)


def test_pallas_combined_shares_equal_per_participant_sum():
    """Linearity fusion (Σp M@v_p == M@Σp v_p): kernel combined shares must
    equal folding per-participant packed_share32 rows from the same bits."""
    SameBits(P=6, seed=31, masked=False).assert_kernel_matches_xla(p_block=2)


def test_pallas_round_rejects_generic_prime():
    s = PackedShamirSharing(3, 8, 4, 433, 354, 150)
    with pytest.raises(ValueError, match="Solinas"):
        SimulatedPod(s, mesh=make_mesh(1, 1), use_pallas=True)


@pytest.mark.parametrize("masked", [True, False], ids=["masked", "mask-free"])
@pytest.mark.parametrize("p_block,P,block,tail", [
    (50, 100, 50, 0), (100, 100, 100, 0),  # divides P: no tail
    (16, 300, 16, 12),                # the benchmark cells' rows a chip
    (16, 24, 16, 8), (16, 18, 16, 2),  # never shrinks to a divisor
    (16, 7, 7, 0), (16, 1, 1, 0), (16, 15, 15, 0),  # clamps to P: one block
    (16, 16, 16, 0),                  # one block, no tail
    (16, 17, 16, 1), (16, 33, 16, 1),  # a tail of one
])
def test_pallas_round_divisor_p_blocks(p_block, P, block, tail, masked):
    """The participants fold in whole blocks of ``p_block`` clamped to P,
    whatever P's divisors, and ``P mod block`` of them in one tail; however
    it splits, the kernel draws for exactly P participants from the same
    bits as the XLA path."""
    assert _participant_block(p_block, P) == (block, tail)
    SameBits(P=P, seed=3, masked=masked).assert_kernel_matches_xla(p_block=p_block)


@pytest.mark.parametrize("P,rows,tile,p_tile", [
    (300, 14, 2048, 25),      # the cells' shape: 26 fit, 25 divides
    (60_000, 14, 512, 100),   # lenet-60k
    (100, 14, 128, 100), (7, 14, 2048, 7),  # all of P fits
    (1201, 14, 2048, 1),      # a prime over the budget
])
def test_external_bits_participant_tile_is_the_largest_divisor_that_fits(
        P, rows, tile, p_tile):
    assert _participant_tile(P, rows, tile) == p_tile
    assert P % p_tile == 0 and p_tile * rows * tile * 4 <= 3_000_000


@pytest.mark.parametrize("B0,tile,padded", [
    (8, 128, 128), (2047, 2048, 2048), (2048, 2048, 2048),
    (333_333, 2048, 333_824),         # the benchmark cells' columns a chip
])
def test_column_tile(B0, tile, padded):
    """The one rule for the kernel's lane-dim tile: whole vregs for a small
    column count, 2048 from there on; the stage pads to whole tiles."""
    assert column_tile(B0) == tile
    assert B0 + (-B0) % tile == padded


# -- tree fold: dense-sublane halving fold, bit-identical ------------------

@pytest.mark.parametrize("masking", ["none", "full"])
@pytest.mark.parametrize("p_block", [2, 4, 8])
def test_tree_fold_bit_identical_to_slice_fold(masking, p_block):
    """tree_fold=True must reproduce the slice fold bit-for-bit from the
    same external bits (mod-p sums are order-free; the canon cadence
    keeps raw partials inside uint32), and both the XLA shares."""
    same = SameBits(P=8, seed=14, masked=masking == "full")
    tree = same.assert_kernel_matches_xla(p_block=p_block, tree_fold=True)
    flat = same.kernel(p_block=p_block, tree_fold=False)
    np.testing.assert_array_equal(np.asarray(tree[0]), np.asarray(flat[0]))
    np.testing.assert_array_equal(np.asarray(tree[1]), np.asarray(flat[1]))


def test_tree_fold_shares_match_slice_shares_same_bits():
    """A block of 32: five halving levels, more than the raw adds one
    canon interval allows (2^29-sized residues: 8 terms), so the tree
    canonicalizes part-way."""
    SameBits(P=32, seed=34, masked=True).assert_kernel_matches_xla(
        p_block=32, tree_fold=True)


@pytest.mark.parametrize("P", [20, 22])
def test_tree_fold_folds_a_tail_by_its_own_rule(P):
    """A block of 16 through the tree, and a tail of 4 through it too or
    one of 6 through the slice fold: each by its own count."""
    SameBits(P=P, seed=36, masked=True).assert_kernel_matches_xla(tree_fold=True)


def test_tree_fold_non_pow2_p_block_falls_back():
    """A block (or tail) of a non-power-of-two count silently runs the
    slice fold (the parameter is a no-op there, never an error)."""
    SameBits(P=6, seed=35, masked=True).assert_kernel_matches_xla(
        p_block=3, tree_fold=True)
