"""Fused Pallas round kernel — interpret-mode exactness on CPU.

`external` randomness mode feeds deterministic bits so the kernel's uint32
Solinas arithmetic is checkable without TPU hardware: the full round must
equal the plain participant sum (masks and share randomness cancel), and
the kernel's combined shares must equal the XLA fast-path shares computed
from the same bits.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sda_tpu.fields import fastfield, numtheory
from sda_tpu.fields.pallas_round import (
    _uniform_from_bits,
    fused_mask_share_combine,
    single_chip_round_pallas,
)
from sda_tpu.fields.sharing import batch_columns
from sda_tpu.protocol import FullMasking, NoMasking, PackedShamirSharing


def fast_scheme():
    t, p, w2, w3 = numtheory.generate_packed_params(3, 8, 28)
    return PackedShamirSharing(3, 8, t, p, w2, w3)


from util import external_bits


@pytest.mark.parametrize("masking", ["none", "full"])
def test_pallas_round_equals_plain_sum(masking):
    s = fast_scheme()
    mask = FullMasking(s.prime_modulus) if masking == "full" else NoMasking()
    fn = single_chip_round_pallas(
        s, mask, tile=128, interpret=True, external_bits_fn=external_bits
    )
    rng = np.random.default_rng(21)
    inputs = rng.integers(0, 1 << 20, size=(5, 500))  # B=167 -> padded to 256
    out = np.asarray(fn(jnp.asarray(inputs), jax.random.PRNGKey(8)))
    np.testing.assert_array_equal(out, inputs.sum(axis=0) % s.prime_modulus)


def test_pallas_kernel_matches_xla_shares_same_bits():
    """Kernel combined-shares == XLA packed_share32 fed identical residues."""
    s = fast_scheme()
    sp = fastfield.SolinasPrime.try_from(s.prime_modulus)
    k, t, n = s.secret_count, s.privacy_threshold, s.share_count
    m_host = numtheory.packed_share_matrix(
        k, n, t, s.prime_modulus, s.omega_secrets, s.omega_shares
    )
    P, d, tile = 4, 384, 128
    B = d // k
    rng = np.random.default_rng(22)
    x = jnp.asarray(rng.integers(0, s.prime_modulus, size=(P, d)).astype(np.uint32))
    x_cols = batch_columns(x, k)
    bits = external_bits(jax.random.PRNGKey(30), P, k + t, B)

    shares, mask_tot = fused_mask_share_combine(
        fastfield.modsum32(x_cols, sp, axis=0), P, 0, sp, m_host, t, True,
        tile=tile, external_bits=bits, interpret=True,
    )

    # reference: same draws through the fastfield helpers
    mask = _uniform_from_bits(bits[:, 0:k, :], bits[:, k:2 * k, :], sp)
    rand = _uniform_from_bits(bits[:, 2 * k:2 * k + t, :],
                              bits[:, 2 * k + t:2 * (k + t), :], sp)
    masked_cols = fastfield.modadd32(x_cols, mask, sp)
    zeros = jnp.zeros((P, 1, B), jnp.uint32)
    values = jnp.concatenate([zeros, masked_cols, rand], axis=1)
    per_part = fastfield.modmatmul32(m_host, values, sp)        # [P, n, B]
    expected_shares = fastfield.modsum32(per_part, sp, axis=0)
    expected_mask_tot = fastfield.modsum32(mask, sp, axis=0)

    np.testing.assert_array_equal(np.asarray(shares), np.asarray(expected_shares))
    np.testing.assert_array_equal(np.asarray(mask_tot), np.asarray(expected_mask_tot))


def test_pallas_round_streams_participant_tiles():
    """P larger than one VMEM participant tile: the kernel's second grid
    axis must zero-init on the first visit and accumulate across revisits
    of the same output block (the lenet-60k VMEM-OOM regression: all P in
    one block). p_tile=32 with P=70 forces ceil(80/32)=3 grid-axis-1
    steps — the auto tile would fit all of P in one block at these
    shapes and never exercise the revisit path."""
    s = fast_scheme()
    fn = single_chip_round_pallas(
        s, FullMasking(s.prime_modulus),
        tile=128, interpret=True, external_bits_fn=external_bits,
        p_tile=32,
    )
    rng = np.random.default_rng(23)
    inputs = rng.integers(0, 1 << 20, size=(70, 500))
    out = np.asarray(fn(jnp.asarray(inputs), jax.random.PRNGKey(9)))
    np.testing.assert_array_equal(out, inputs.sum(axis=0) % s.prime_modulus)


def test_pallas_combined_shares_equal_per_participant_sum():
    """Linearity fusion (Σp M@v_p == M@Σp v_p): kernel combined shares must
    equal folding per-participant packed_share32 rows from the same bits."""
    s = fast_scheme()
    sp = fastfield.SolinasPrime.try_from(s.prime_modulus)
    k, t = s.secret_count, s.privacy_threshold
    m_host = numtheory.packed_share_matrix(
        k, s.share_count, t, s.prime_modulus, s.omega_secrets, s.omega_shares
    )
    P, d = 6, 384
    B = d // k
    rng = np.random.default_rng(31)
    x = jnp.asarray(rng.integers(0, s.prime_modulus, size=(P, d)).astype(np.uint32))
    bits = external_bits(jax.random.PRNGKey(44), P, t, B)  # unmasked: t rows

    shares, _ = fused_mask_share_combine(
        batch_columns(fastfield.modsum32(x, sp, axis=0), k), P, 0, sp,
        m_host, t, False,
        tile=128, external_bits=bits, interpret=True, p_block=2,
    )
    # per-participant path from the identical bits
    rand = _uniform_from_bits(bits[:, 0:t, :], bits[:, t:2 * t, :], sp)
    per_part = fastfield.modmatmul32(
        m_host,
        jnp.concatenate(
            [jnp.zeros((P, 1, B), jnp.uint32), batch_columns(x, k), rand],
            axis=1,
        ),
        sp,
    )
    np.testing.assert_array_equal(
        np.asarray(shares), np.asarray(fastfield.modsum32(per_part, sp, axis=0))
    )


def test_pallas_round_rejects_generic_prime():
    s = PackedShamirSharing(3, 8, 4, 433, 354, 150)
    with pytest.raises(ValueError, match="Solinas"):
        single_chip_round_pallas(s)


@pytest.mark.parametrize("p_block", [50, 100])
def test_pallas_round_divisor_p_blocks(p_block):
    """p_block values dividing P exactly (the sweep's zero-padding points:
    at P=100, p_block 16/32/64 pad the participant axis to 112/128 rows
    while 50/100 pad none) stay exact."""
    s = fast_scheme()
    fn = single_chip_round_pallas(
        s, FullMasking(s.prime_modulus), p_block=p_block, tile=128,
        interpret=True, external_bits_fn=external_bits,
    )
    rng = np.random.default_rng(3)
    inputs = rng.integers(0, 1 << 20, size=(100, 3 * 128))
    out = np.asarray(fn(jnp.asarray(inputs), jax.random.PRNGKey(5)))
    np.testing.assert_array_equal(out, inputs.sum(axis=0) % s.prime_modulus)


# -- tree fold: dense-sublane halving fold, bit-identical ------------------

@pytest.mark.parametrize("masking", ["none", "full"])
@pytest.mark.parametrize("p_block", [2, 4, 8])
def test_tree_fold_bit_identical_to_slice_fold(masking, p_block):
    """tree_fold=True must reproduce the slice fold bit-for-bit from the
    same external bits (mod-p sums are order-free; the canon cadence
    keeps raw partials inside uint32)."""
    s = fast_scheme()
    mask = FullMasking(s.prime_modulus) if masking == "full" else NoMasking()
    rng = np.random.default_rng(31)
    inputs = jnp.asarray(rng.integers(0, 1 << 20, size=(8, 504)))
    key = jax.random.PRNGKey(14)
    outs = {}
    for tree in (False, True):
        fn = single_chip_round_pallas(
            s, mask, tile=128, interpret=True,
            external_bits_fn=external_bits, p_block=p_block,
            tree_fold=tree,
        )
        outs[tree] = np.asarray(fn(inputs, key))
    np.testing.assert_array_equal(outs[True], outs[False])
    np.testing.assert_array_equal(
        outs[True], np.asarray(inputs).sum(axis=0) % s.prime_modulus)


def test_tree_fold_shares_match_slice_shares_same_bits():
    """At the kernel seam: combined shares and mask totals identical."""
    s = fast_scheme()
    sp = fastfield.SolinasPrime.try_from(s.prime_modulus)
    k, t = s.secret_count, s.privacy_threshold
    m_host = numtheory.packed_share_matrix(
        k, s.share_count, t, s.prime_modulus, s.omega_secrets,
        s.omega_shares)
    P, d, tile = 8, 384, 128
    B = d // k
    rng = np.random.default_rng(33)
    x = jnp.asarray(
        rng.integers(0, s.prime_modulus, size=(P, d)).astype(np.uint32))
    x_sum = batch_columns(fastfield.modsum32(x, sp, axis=0), k)
    bits = external_bits(jax.random.PRNGKey(34), P, k + t, B)
    got = {}
    for tree in (False, True):
        got[tree] = fused_mask_share_combine(
            x_sum, P, 0, sp, m_host, t, True, tile=tile, external_bits=bits,
            interpret=True, p_block=4, tree_fold=tree)
    np.testing.assert_array_equal(
        np.asarray(got[True][0]), np.asarray(got[False][0]))
    np.testing.assert_array_equal(
        np.asarray(got[True][1]), np.asarray(got[False][1]))


def test_tree_fold_non_pow2_p_block_falls_back():
    """A non-power-of-two effective p_block silently runs the slice fold
    (the knob is a no-op, never an error)."""
    s = fast_scheme()
    rng = np.random.default_rng(35)
    inputs = jnp.asarray(rng.integers(0, 1 << 20, size=(6, 336)))
    fn = single_chip_round_pallas(
        s, FullMasking(s.prime_modulus), tile=112, interpret=True,
        external_bits_fn=external_bits, p_block=3, tree_fold=True)
    out = np.asarray(fn(inputs, jax.random.PRNGKey(15)))
    np.testing.assert_array_equal(
        out, np.asarray(inputs).sum(axis=0) % s.prime_modulus)
