"""BasicShamir — the reference's declared-but-disabled classic Shamir
scheme (protocol/src/crypto.rs:89-95), implemented end-to-end.

Rides the packed machinery as its k=1 degenerate (same [0; secrets;
randomness] column convention, scheme-dispatched Vandermonde/Lagrange
matrices from fields/numtheory.py), so every execution mode is covered:
federated full loop, pod mesh, streamed, Pallas local step, dropout
quorums, and the CLI.
"""

import itertools

import jax
import numpy as np
import pytest

from sda_tpu.crypto.sharing import new_share_generator, new_secret_reconstructor
from sda_tpu.fields import numtheory
from sda_tpu.mesh import SimulatedPod, StreamedPod, StreamingAggregator, make_mesh
from sda_tpu.protocol import BasicShamirSharing, ChaChaMasking, FullMasking

from util import external_bits, one_chip_pallas_pod


def test_scheme_properties_match_reference_declaration():
    """Derived properties per the commented match arms of crypto.rs:117-155:
    input_size 1, output_size n, privacy_threshold t,
    reconstruction_threshold t+1."""
    s = BasicShamirSharing(share_count=5, privacy_threshold=2,
                           prime_modulus=433)
    assert s.input_size == 1 and s.secret_count == 1
    assert s.output_size == 5
    assert s.privacy_threshold == 2
    assert s.reconstruction_threshold == 3
    with pytest.raises(ValueError):
        BasicShamirSharing(5, 0, 433)      # t must be >= 1
    with pytest.raises(ValueError):
        BasicShamirSharing(5, 5, 433)      # t must be < n
    with pytest.raises(ValueError):
        BasicShamirSharing(433, 3, 433)    # points 1..n need p > n


def test_serde_roundtrip():
    s = BasicShamirSharing(8, 3, 433)
    from sda_tpu.protocol import LinearSecretSharingScheme

    assert LinearSecretSharingScheme.from_obj(s.to_obj()) == s


def test_every_minimal_quorum_reconstructs():
    """Any t+1 of n shares reveal; matrix path == reference Shamir math."""
    s = BasicShamirSharing(share_count=5, privacy_threshold=2,
                           prime_modulus=433)
    gen = new_share_generator(s)
    secrets = np.array([7, 100, 432, 0, 1, 211], dtype=np.int64)
    shares = gen.generate(secrets)
    assert len(shares) == 5
    rec = new_secret_reconstructor(s, secrets.size)
    for subset in itertools.combinations(range(5), 3):
        got = rec.reconstruct([(i, shares[i]) for i in subset])
        np.testing.assert_array_equal(got, secrets % 433)
    with pytest.raises(ValueError):
        rec.reconstruct([(0, shares[0]), (1, shares[1])])  # below quorum


def test_shares_hide_the_secret_at_threshold():
    """t shares are an affine function of t uniform coefficients with a
    full-rank (Vandermonde) coefficient matrix, so they are uniform and
    independent of the secret — verified by rank over Z_p."""
    n, t, p = 5, 2, 433
    M = numtheory.basic_share_matrix(n, t, p)
    # columns 2..2+t multiply the randomness; any t rows of that block
    # must be invertible mod p for perfect privacy
    import itertools as it

    def det2(m):
        return (m[0][0] * m[1][1] - m[0][1] * m[1][0]) % p

    R = [[int(M[i][2 + j]) for j in range(t)] for i in range(n)]
    for rows in it.combinations(range(n), t):
        assert det2([R[rows[0]], R[rows[1]]]) != 0


def needs_devices(k):
    return pytest.mark.skipif(
        len(jax.devices()) < k, reason=f"needs {k} virtual devices"
    )


def fast_basic():
    _, p, _, _ = numtheory.generate_packed_params(3, 8, 28)  # Solinas prime
    return BasicShamirSharing(share_count=8, privacy_threshold=3,
                              prime_modulus=p)


@needs_devices(8)
def test_pod_round_with_dropout():
    s = fast_basic()
    pod = SimulatedPod(
        s, masking_scheme=FullMasking(s.prime_modulus), mesh=make_mesh(4, 2),
        surviving_clerks=(0, 2, 4, 7),  # r = t+1 = 4
    )
    rng = np.random.default_rng(11)
    inputs = rng.integers(0, 1 << 20, size=(8, 48))
    out = np.asarray(pod.aggregate(inputs))
    np.testing.assert_array_equal(out, inputs.sum(axis=0) % s.prime_modulus)


@needs_devices(8)
def test_streamed_pod_chacha():
    s = fast_basic()
    spod = StreamedPod(
        s, ChaChaMasking(s.prime_modulus, 48, 128), mesh=make_mesh(4, 2),
        participants_chunk=8, dim_chunk=24,
    )
    rng = np.random.default_rng(12)
    inputs = rng.integers(0, 1 << 20, size=(11, 48))
    out = np.asarray(spod.aggregate(inputs, jax.random.PRNGKey(2)))
    np.testing.assert_array_equal(out, inputs.sum(axis=0) % s.prime_modulus)


def test_streaming_pallas_kernel():
    """The fused Pallas kernel serves BasicShamir unchanged (k=1 columns)."""
    s = fast_basic()
    agg = StreamingAggregator(
        s, FullMasking(s.prime_modulus), participants_chunk=4, dim_chunk=24,
        use_pallas=True, pallas_interpret=True,
        pallas_external_bits_fn=external_bits,
    )
    assert agg.pallas_active
    rng = np.random.default_rng(13)
    inputs = rng.integers(0, 1 << 20, size=(9, 30))
    out = np.asarray(agg.aggregate(inputs, jax.random.PRNGKey(3)))
    np.testing.assert_array_equal(out, inputs.sum(axis=0) % s.prime_modulus)


def test_single_chip_pallas_round():
    """The Pallas stage on one chip (a 1x1 pod) serves BasicShamir via
    the dispatched matrices (interpret mode, external bits)."""
    s = fast_basic()
    pod = one_chip_pallas_pod(s, FullMasking(s.prime_modulus))
    rng = np.random.default_rng(15)
    inputs = rng.integers(0, 1 << 20, size=(5, 500))
    out = np.asarray(pod.aggregate(inputs, jax.random.PRNGKey(8)))
    np.testing.assert_array_equal(out, inputs.sum(axis=0) % s.prime_modulus)


def test_oracle_matches_device_given_same_randomness():
    s = fast_basic()
    from sda_tpu.fields import oracle
    import sda_tpu.fields as fields
    import jax.numpy as jnp

    secrets = np.arange(24, dtype=np.int64)
    rng = np.random.default_rng(14)
    randomness = rng.integers(0, s.prime_modulus,
                              size=(s.privacy_threshold, 24), dtype=np.int64)
    host = oracle.packed_share_from_randomness(secrets, randomness, s)
    M = jnp.asarray(numtheory.share_matrix_for(s))
    dev = np.asarray(fields.packed_share_from_randomness(
        jnp.asarray(secrets), jnp.asarray(randomness), M,
        prime=s.prime_modulus, secret_count=1,
    ))
    np.testing.assert_array_equal(host, dev)
