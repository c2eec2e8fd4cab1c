"""Fast-path rounds (uint32 Solinas) vs generic rounds and the protocol sum.

single_chip_round and SimulatedPod auto-select the fastfield kernels when
the scheme prime qualifies; these tests pin that selection AND that results
stay bit-exact against plain integer aggregation.
"""

import jax
import numpy as np
import pytest

from sda_tpu.fields import fastfield, numtheory
from sda_tpu.mesh import SimulatedPod, make_mesh, single_chip_round
from sda_tpu.protocol import FullMasking, NoMasking, PackedShamirSharing


def fast_scheme():
    t, p, w2, w3 = numtheory.generate_packed_params(3, 8, 28)
    assert fastfield.supported(p)
    return PackedShamirSharing(3, 8, t, p, w2, w3)


@pytest.mark.parametrize("masking", ["none", "full"])
def test_single_chip_fast_round_exact(masking):
    s = fast_scheme()
    mask = FullMasking(s.prime_modulus) if masking == "full" else NoMasking()
    fn = jax.jit(single_chip_round(s, mask))
    rng = np.random.default_rng(5)
    inputs = rng.integers(0, 1 << 20, size=(7, 123))
    out = np.asarray(fn(jax.numpy.asarray(inputs), jax.random.PRNGKey(3)))
    np.testing.assert_array_equal(out, inputs.sum(axis=0) % s.prime_modulus)


def test_single_chip_fast_round_accepts_uint32_inputs():
    s = fast_scheme()
    fn = jax.jit(single_chip_round(s, FullMasking(s.prime_modulus)))
    rng = np.random.default_rng(6)
    inputs = rng.integers(0, 1 << 20, size=(5, 60)).astype(np.uint32)
    out = np.asarray(fn(jax.numpy.asarray(inputs), jax.random.PRNGKey(4)))
    np.testing.assert_array_equal(out, inputs.astype(np.int64).sum(0) % s.prime_modulus)


def test_single_chip_fast_round_canonicalizes_int32_negatives():
    s = fast_scheme()
    p = s.prime_modulus
    fn = jax.jit(single_chip_round(s, NoMasking()))
    inputs = np.array([[-1, -7, 5, 0], [3, 7, -5, 1]], dtype=np.int32)
    out = np.asarray(fn(jax.numpy.asarray(inputs), jax.random.PRNGKey(0)))
    np.testing.assert_array_equal(out, inputs.astype(np.int64).sum(0) % p)


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 virtual devices")
@pytest.mark.parametrize("mesh_shape", [(8, 1), (4, 2), (1, 8)])
def test_pod_fast_round_exact(mesh_shape):
    s = fast_scheme()
    pod = SimulatedPod(s, FullMasking(s.prime_modulus), mesh=make_mesh(*mesh_shape))
    assert pod._sp is not None, "pod should select the uint32 fast path"
    rng = np.random.default_rng(7)
    inputs = rng.integers(0, 1 << 20, size=(16, 48))
    out = np.asarray(pod.aggregate(inputs, key=jax.random.PRNGKey(9)))
    np.testing.assert_array_equal(out, inputs.sum(axis=0) % s.prime_modulus)


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 virtual devices")
def test_pod_golden_prime_uses_generic_path():
    """p=433 (reference conformance vector) must not enter the fast path and
    must still be exact."""
    s = PackedShamirSharing(3, 8, 4, 433, 354, 150)
    pod = SimulatedPod(s, mesh=make_mesh(8, 1))
    assert pod._sp is None
    rng = np.random.default_rng(8)
    inputs = rng.integers(0, 50, size=(16, 12))
    out = np.asarray(pod.aggregate(inputs, key=jax.random.PRNGKey(1)))
    np.testing.assert_array_equal(out, inputs.sum(axis=0) % 433)


def test_large_committee_scheme_round():
    """n=26 committee (m3=27, m2=16): generator finds a Solinas prime with
    432 | p-1 and the fast round stays exact at radix-3 scale."""
    t, p, w2, w3 = numtheory.generate_packed_params(11, 26, 26)
    s = PackedShamirSharing(11, 26, t, p, w2, w3)
    assert s.reconstruction_threshold == t + 11 <= 26
    fn = jax.jit(single_chip_round(s, FullMasking(p) if fastfield.supported(p)
                                   else NoMasking()))
    rng = np.random.default_rng(31)
    inputs = rng.integers(0, 1 << 16, size=(4, 11 * 7))
    out = np.asarray(fn(jax.numpy.asarray(inputs), jax.random.PRNGKey(6)))
    np.testing.assert_array_equal(out, inputs.sum(axis=0) % p)


@pytest.mark.parametrize("masking", ["none", "full", "chacha"])
@pytest.mark.parametrize("dim", [96, 123, 240, 241])  # 96 = exactly 1 tile
def test_single_chip_round_dim_tiled_exact(masking, dim):
    """The dim-tiled schedule (lax.scan over fixed-width tiles) must be
    bit-exact vs plain aggregation for every masking scheme, including a
    ragged last tile and dims off the tile grain. ChaCha pins that each
    tile reads ITS window of the global mask stream (d_block0)."""
    from sda_tpu.protocol import ChaChaMasking

    s = fast_scheme()
    p = s.prime_modulus
    d_cha = -(-dim // 8) * 8  # chacha requires whole 8-dim blocks
    d = d_cha if masking == "chacha" else dim
    mask = {"none": NoMasking(), "full": FullMasking(p),
            "chacha": ChaChaMasking(p, d, 128)}[masking]
    fn = jax.jit(single_chip_round(s, mask, dim_tile=96))
    rng = np.random.default_rng(11)
    inputs = rng.integers(0, 1 << 20, size=(9, d))
    out = np.asarray(fn(jax.numpy.asarray(inputs), jax.random.PRNGKey(7)))
    np.testing.assert_array_equal(out, inputs.sum(axis=0) % p)


def test_single_chip_round_dim_tile_wider_than_dim_is_untiled():
    s = fast_scheme()
    fn = jax.jit(single_chip_round(s, FullMasking(s.prime_modulus),
                                   dim_tile=4096))
    rng = np.random.default_rng(12)
    inputs = rng.integers(0, 1 << 20, size=(5, 60))
    out = np.asarray(fn(jax.numpy.asarray(inputs), jax.random.PRNGKey(8)))
    np.testing.assert_array_equal(out, inputs.sum(axis=0) % s.prime_modulus)


@pytest.mark.parametrize("P", [1, 2])
def test_single_participant_edge(P):
    """P=1/P=2 rounds: the smallest participant counts exercise pb-clamp
    and single-term folds in every single-chip path."""
    import jax.numpy as jnp

    from sda_tpu.mesh import StreamingAggregator

    s = fast_scheme()
    p = s.prime_modulus
    rng = np.random.default_rng(2)
    x = rng.integers(0, 1 << 20, size=(P, 384)).astype(np.uint32)
    exp = x.astype(np.int64).sum(axis=0) % p
    from util import one_chip_pallas_pod

    key = jax.random.PRNGKey(1)

    out_xla = jax.jit(single_chip_round(s, FullMasking(p)))(jnp.asarray(x), key)
    out_pl = one_chip_pallas_pod(s, FullMasking(p)).aggregate(x, key)
    out_st = StreamingAggregator(
        s, FullMasking(p), participants_chunk=1, dim_chunk=96
    ).aggregate(x, key=key)
    for name, out in [("xla", out_xla), ("pallas", out_pl), ("streaming", out_st)]:
        np.testing.assert_array_equal(np.asarray(out), exp, err_msg=name)
