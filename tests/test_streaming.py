"""Streamed (chunked) rounds: exactness across tilings, paths, maskings.

The streaming driver must produce the exact participant-sum regardless of
how the [P, d] matrix is tiled — including remainder chunks on both axes —
on both the uint32 Solinas fast path and the generic s64 path.
"""

import jax
import numpy as np
import pytest

from sda_tpu.fields import fastfield, numtheory
from sda_tpu.mesh import (
    StreamingAggregator,
    array_block_provider,
    synthetic_block_provider,
)
from sda_tpu.protocol import FullMasking, NoMasking, PackedShamirSharing

GOLDEN = PackedShamirSharing(3, 8, 4, 433, 354, 150)  # generic path


def fast_scheme():
    t, p, w2, w3 = numtheory.generate_packed_params(3, 8, 28)
    assert fastfield.supported(p)
    return PackedShamirSharing(3, 8, t, p, w2, w3)


@pytest.mark.parametrize("scheme_kind", ["fast", "generic"])
@pytest.mark.parametrize("masking", ["none", "full"])
@pytest.mark.parametrize("P,d,pc,dc", [
    (10, 60, 4, 30),    # remainder on the participant axis
    (8, 50, 8, 21),     # remainder on the dim axis (21 % 3 == 0)
    (7, 33, 3, 12),     # remainders on both
    (5, 12, 64, 3 << 20),  # single block
])
def test_streaming_exact(scheme_kind, masking, P, d, pc, dc):
    scheme = fast_scheme() if scheme_kind == "fast" else GOLDEN
    p = scheme.prime_modulus
    mask = FullMasking(p) if masking == "full" else NoMasking()
    agg = StreamingAggregator(scheme, mask, participants_chunk=pc, dim_chunk=dc)
    assert (agg._sp is not None) == (scheme_kind == "fast")
    rng = np.random.default_rng(11)
    inputs = rng.integers(0, min(p, 1 << 20), size=(P, d))
    out = agg.aggregate(inputs, key=jax.random.PRNGKey(2))
    np.testing.assert_array_equal(out, inputs.sum(axis=0) % p)


def test_streaming_matches_block_provider_forms():
    scheme = fast_scheme()
    agg = StreamingAggregator(scheme, FullMasking(scheme.prime_modulus),
                              participants_chunk=3, dim_chunk=9)
    rng = np.random.default_rng(13)
    inputs = rng.integers(0, 1 << 16, size=(7, 21))
    direct = agg.aggregate(inputs, key=jax.random.PRNGKey(5))
    via_provider = StreamingAggregator(
        scheme, FullMasking(scheme.prime_modulus),
        participants_chunk=3, dim_chunk=9,
    ).aggregate_blocks(array_block_provider(inputs), 7, 21, jax.random.PRNGKey(5))
    np.testing.assert_array_equal(direct, via_provider)


def test_synthetic_provider_consistent_across_tilings():
    """The virtual matrix must not depend on the tiling used to read it."""
    prov = synthetic_block_provider(modulus=433, seed=9)
    whole = prov(0, 6, 0, 12)
    by_rows = np.concatenate([prov(0, 3, 0, 12), prov(3, 6, 0, 12)], axis=0)
    by_cols = np.concatenate([prov(0, 6, 0, 5), prov(0, 6, 5, 12)], axis=1)
    np.testing.assert_array_equal(whole, by_rows)
    np.testing.assert_array_equal(whole, by_cols)
    assert whole.min() >= 0 and whole.max() < 433
    # and streamed aggregation over it is exact
    scheme = GOLDEN
    agg = StreamingAggregator(scheme, participants_chunk=4, dim_chunk=6)
    out = agg.aggregate_blocks(prov, 6, 12, jax.random.PRNGKey(0))
    np.testing.assert_array_equal(out, prov(0, 6, 0, 12).sum(axis=0) % 433)


def test_dim_chunk_rounds_up_to_scheme_grain():
    # misaligned tile sizes round up to the packing (and, with ChaCha,
    # the 8-word block) grain instead of erroring
    assert StreamingAggregator(GOLDEN, dim_chunk=10).dim_chunk == 12
    from sda_tpu.protocol import ChaChaMasking

    agg = StreamingAggregator(
        GOLDEN, ChaChaMasking(433, 100, 128), dim_chunk=10
    )
    assert agg.dim_chunk == 24  # lcm(secret_count=3, chacha block 8)


# ---------------------------------------------------------------------------
# StreamedPod: streamed x multi-chip composition

needs8 = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 virtual devices"
)

from util import scheme_lattice_config as _streamed_config


@needs8
@pytest.mark.parametrize("mesh_shape", [(8, 1), (4, 2)])
@pytest.mark.parametrize("config", ["shamir-full", "add-chacha", "basic-chacha"])
def test_streamed_pod_exact(mesh_shape, config):
    """Tiled multi-device rounds (collective-free steps, one transpose per
    dim tile) aggregate exactly, including ragged edge tiles."""
    from sda_tpu.mesh import StreamedPod
    from sda_tpu.mesh.simpod import make_mesh

    dim, participants = 50, 10
    sharing, masking = _streamed_config(config, dim)
    pod = StreamedPod(
        sharing, masking, mesh=make_mesh(*mesh_shape),
        participants_chunk=4, dim_chunk=24,
    )
    rng = np.random.default_rng(21)
    inputs = rng.integers(0, 433, size=(participants, dim))
    out = pod.aggregate(inputs, key=jax.random.PRNGKey(9))
    np.testing.assert_array_equal(out, inputs.sum(axis=0) % 433)


@needs8
def test_streamed_pod_matches_simulated_pod():
    """One-tile StreamedPod and SimulatedPod agree with the plain sum on
    the same mesh (independent randomness, same aggregate)."""
    from sda_tpu.mesh import SimulatedPod, StreamedPod
    from sda_tpu.mesh.simpod import make_mesh

    mesh = make_mesh(4, 2)
    rng = np.random.default_rng(22)
    inputs = rng.integers(0, 433, size=(8, 48))
    expected = inputs.sum(axis=0) % 433
    streamed = StreamedPod(GOLDEN, FullMasking(433), mesh=mesh,
                           participants_chunk=8, dim_chunk=48)
    pod = SimulatedPod(GOLDEN, FullMasking(433), mesh=mesh)
    np.testing.assert_array_equal(
        streamed.aggregate(inputs, key=jax.random.PRNGKey(1)), expected)
    np.testing.assert_array_equal(
        np.asarray(pod.aggregate(inputs, key=jax.random.PRNGKey(1))), expected)


@needs8
def test_streamed_pod_large_committee_smoke():
    """80-clerk committee streamed over the mesh (reference scale story)."""
    from sda_tpu.mesh import StreamedPod
    from sda_tpu.mesh.simpod import make_mesh
    from sda_tpu.protocol import AdditiveSharing

    pod = StreamedPod(
        AdditiveSharing(share_count=80, modulus=433),
        mesh=make_mesh(8, 1), participants_chunk=8, dim_chunk=12,
    )
    rng = np.random.default_rng(23)
    inputs = rng.integers(0, 433, size=(12, 20))
    out = pod.aggregate(inputs, key=jax.random.PRNGKey(2))
    np.testing.assert_array_equal(out, inputs.sum(axis=0) % 433)


def test_streaming_aggregator_chacha_exact_across_tilings():
    """ChaCha seed masks in the single-chip streamed mode: exact aggregate
    for several tilings, including edge tiles not aligned to the 8-word
    ChaCha block grain (the dim tile pads to the grain internally)."""
    import jax

    from sda_tpu.mesh import StreamingAggregator
    from sda_tpu.protocol import ChaChaMasking, PackedShamirSharing

    s = PackedShamirSharing(3, 8, 4, 433, 354, 150)
    rng = np.random.default_rng(41)
    P, d = 13, 100  # d % 24 != 0: every tiling has a ragged edge tile
    x = rng.integers(0, 433, size=(P, d))
    expected = x.sum(axis=0) % 433
    for pc, dc in [(4, 24), (5, 48), (13, 120), (2, 25)]:
        agg = StreamingAggregator(
            s, ChaChaMasking(433, d, 128),
            participants_chunk=pc, dim_chunk=dc,
        )
        out = agg.aggregate(x, key=jax.random.PRNGKey(12))
        np.testing.assert_array_equal(out, expected, err_msg=f"tiling {pc}x{dc}")


def test_streaming_aggregator_additive_schemes():
    """Additive sharing in the streamed single-chip mode (scheme-lattice
    parity with the pod modes), across maskings and ragged tilings."""
    import jax

    from sda_tpu.mesh import StreamingAggregator
    from sda_tpu.protocol import (
        AdditiveSharing,
        ChaChaMasking,
        FullMasking,
        NoMasking,
    )

    rng = np.random.default_rng(53)
    P, d = 11, 70
    x = rng.integers(0, 433, size=(P, d))
    expected = x.sum(axis=0) % 433
    s = AdditiveSharing(share_count=8, modulus=433)
    for masking in (NoMasking(), FullMasking(433), ChaChaMasking(433, d, 128)):
        agg = StreamingAggregator(
            s, masking, participants_chunk=4, dim_chunk=30
        )
        out = agg.aggregate(x, key=jax.random.PRNGKey(21))
        np.testing.assert_array_equal(
            out, expected, err_msg=type(masking).__name__
        )


def test_streaming_checkpoint_resume_bit_identical(tmp_path):
    """A crash mid-round resumes from the snapshot and produces the exact
    bytes of an uninterrupted run (tile keys are pure functions of the
    round key and tile indices), skipping already-folded chunks."""
    import os

    from sda_tpu.mesh import synthetic_block_provider32

    t, p, w2, w3 = numtheory.generate_packed_params(3, 8, 28)
    s = PackedShamirSharing(3, 8, t, p, w2, w3)
    key = jax.random.PRNGKey(7)
    prov = synthetic_block_provider32(p, seed=4, max_value=1 << 20)
    ck = str(tmp_path / "round.ckpt.npz")

    def agg():
        return StreamingAggregator(
            s, FullMasking(p), participants_chunk=4, dim_chunk=24
        )

    ref = agg().aggregate_blocks(prov, 23, 100, key)

    calls = {"n": 0}

    def flaky(p0, p1, d0, d1):
        calls["n"] += 1
        if calls["n"] == 13:
            raise RuntimeError("simulated crash")
        return prov(p0, p1, d0, d1)

    with pytest.raises(RuntimeError):
        agg().aggregate_blocks(flaky, 23, 100, key, checkpoint_path=ck,
                               checkpoint_every_chunks=2)
    assert os.path.exists(ck)

    resumed_calls = {"n": 0}

    def counting(p0, p1, d0, d1):
        resumed_calls["n"] += 1
        return prov(p0, p1, d0, d1)

    out = agg().aggregate_blocks(counting, 23, 100, key, checkpoint_path=ck,
                                 checkpoint_every_chunks=2)
    np.testing.assert_array_equal(out, ref)
    assert not os.path.exists(ck)  # removed on completion
    total_chunks = (-(-23 // 4)) * (-(-100 // 24))
    assert resumed_calls["n"] < total_chunks  # resume skipped folded chunks


def test_streaming_checkpoint_rejects_foreign_snapshot(tmp_path):
    """A snapshot from a different round (different key) is ignored: the
    fingerprint mismatch forces a clean fresh run, never a silent mix."""
    from sda_tpu.mesh import synthetic_block_provider32

    t, p, w2, w3 = numtheory.generate_packed_params(3, 8, 28)
    s = PackedShamirSharing(3, 8, t, p, w2, w3)
    prov = synthetic_block_provider32(p, seed=4, max_value=1 << 20)
    ck = str(tmp_path / "round.ckpt.npz")

    def agg():
        return StreamingAggregator(
            s, FullMasking(p), participants_chunk=4, dim_chunk=24
        )

    import os

    calls = {"n": 0}

    def boom(p0, p1, d0, d1):
        calls["n"] += 1
        if calls["n"] == 3:
            raise RuntimeError("crash")
        return prov(p0, p1, d0, d1)

    with pytest.raises(RuntimeError):
        agg().aggregate_blocks(boom, 23, 100, jax.random.PRNGKey(7),
                               checkpoint_path=ck, checkpoint_every_chunks=1)
    assert os.path.exists(ck)  # a key-7 snapshot exists
    # different key: snapshot must not be trusted
    out = agg().aggregate_blocks(prov, 23, 100, jax.random.PRNGKey(8),
                                 checkpoint_path=ck)
    exp = agg().aggregate_blocks(prov, 23, 100, jax.random.PRNGKey(8))
    np.testing.assert_array_equal(out, exp)


@needs8
def test_streamed_pod_checkpoint_resume_bit_identical(tmp_path):
    """StreamedPod (multi-chip) rounds resume from snapshots too: the
    fingerprint additionally pins the mesh shape, and loaded accumulators
    are re-placed with the pod's ('p', 'd') sharding."""
    import os

    from sda_tpu.mesh import StreamedPod, synthetic_block_provider32
    from sda_tpu.mesh.simpod import make_mesh

    t, p, w2, w3 = numtheory.generate_packed_params(3, 8, 28)
    s = PackedShamirSharing(3, 8, t, p, w2, w3)
    key = jax.random.PRNGKey(9)
    prov = synthetic_block_provider32(p, seed=6, max_value=1 << 20)
    ck = str(tmp_path / "pod.ckpt.npz")

    def pod():
        return StreamedPod(s, FullMasking(p), mesh=make_mesh(4, 2),
                           participants_chunk=8, dim_chunk=24)

    ref = pod().aggregate_blocks(prov, 21, 96, key)

    calls = {"n": 0}

    def flaky(p0, p1, d0, d1):
        calls["n"] += 1
        if calls["n"] == 8:
            raise RuntimeError("crash")
        return prov(p0, p1, d0, d1)

    with pytest.raises(RuntimeError):
        pod().aggregate_blocks(flaky, 21, 96, key, checkpoint_path=ck,
                               checkpoint_every_chunks=2)
    assert os.path.exists(ck)

    counting = {"n": 0}

    def cprov(p0, p1, d0, d1):
        counting["n"] += 1
        return prov(p0, p1, d0, d1)

    resumed = pod()
    out = resumed.aggregate_blocks(cprov, 21, 96, key, checkpoint_path=ck,
                                   checkpoint_every_chunks=2)
    assert resumed.last_resumed
    np.testing.assert_array_equal(out, ref)
    assert not os.path.exists(ck)
    assert counting["n"] < 12  # resume skipped folded chunks


def test_checkpoint_boundary_only_cadence_resumes_exact(tmp_path):
    """checkpoint_every_chunks=0 snapshots at dim-tile boundaries only
    (the flagship e2e cadence — intra-tile snapshots would copy the
    accumulators to the host every few hundred ms of compute): a
    crash mid-tile resumes from the last completed tile and the round
    stays bit-exact."""
    import os

    from sda_tpu.mesh import StreamingAggregator, synthetic_block_provider32

    s = fast_scheme()
    p = s.prime_modulus
    key = jax.random.PRNGKey(19)
    prov = synthetic_block_provider32(p, seed=21, max_value=1 << 20)
    ck = str(tmp_path / "boundary.ckpt.npz")

    def agg():
        return StreamingAggregator(
            s, FullMasking(p), participants_chunk=4, dim_chunk=24
        )

    ref = agg().aggregate_blocks(prov, 23, 100, key)

    calls = {"n": 0}

    def flaky(p0, p1, d0, d1):
        calls["n"] += 1
        # 6 participant chunks per dim tile: call 15 is the third chunk
        # of dim tile 2 — two chunks are already folded into tile 2's
        # accumulator when the crash lands, but with cadence 0 no
        # intra-tile snapshot exists, so resume must DISCARD that partial
        # fold and rebuild tile 2 from its boundary snapshot
        if calls["n"] == 15:
            raise RuntimeError("simulated crash")
        return prov(p0, p1, d0, d1)

    with pytest.raises(RuntimeError):
        agg().aggregate_blocks(flaky, 23, 100, key, checkpoint_path=ck,
                               checkpoint_every_chunks=0)
    assert os.path.exists(ck)  # the completed-tile boundary snapshot

    resumed = agg()
    resumed_calls = {"n": 0}

    def counting(p0, p1, d0, d1):
        resumed_calls["n"] += 1
        return prov(p0, p1, d0, d1)

    out = resumed.aggregate_blocks(counting, 23, 100, key,
                                   checkpoint_path=ck,
                                   checkpoint_every_chunks=0)
    assert resumed.last_resumed
    np.testing.assert_array_equal(out, ref)
    assert not os.path.exists(ck)
    # dim tiles 0 and 1 (12 chunks) restored from the boundary snapshot;
    # tiles 2..4 re-fed in full — exactly 18 of the 30 chunks
    assert resumed_calls["n"] == 18


# -- uniform_tail: one compiled step/finale shape per round ----------------
# Opt-in tail padding (the model-scale driver uses it to compile ONE
# step/finale shape per streamed config instead of paying the ragged-tail
# shapes' extra compiles).

def test_uniform_tail_exact_and_single_step_shape():
    scheme = fast_scheme()
    p = scheme.prime_modulus
    rng = np.random.default_rng(71)
    P, d, pc, dc = 9, 100, 4, 36  # tail tile 100-72=28 -> padded to 36
    x = rng.integers(0, 1 << 16, size=(P, d))
    expected = x.sum(axis=0) % p
    for masking in (NoMasking(), FullMasking(p)):
        agg = StreamingAggregator(
            scheme, masking, participants_chunk=pc, dim_chunk=dc,
            uniform_tail=True)
        out = agg.aggregate(x, key=jax.random.PRNGKey(3))
        np.testing.assert_array_equal(out, expected,
                                      err_msg=type(masking).__name__)
        # THE point of the flag: a ragged round compiles one step shape
        # and one finale shape
        assert len(agg._steps) == 1, list(agg._steps)
        assert len(agg._finals) == 1, list(agg._finals)
        baseline = StreamingAggregator(
            scheme, masking, participants_chunk=pc, dim_chunk=dc)
        base_out = baseline.aggregate(x, key=jax.random.PRNGKey(3))
        np.testing.assert_array_equal(out, base_out)
        # the ragged tails it exists to avoid: full/tail shapes on both
        # axes -> 4 separately compiled steps
        assert len(baseline._steps) == 4, list(baseline._steps)


def test_uniform_tail_chacha_and_additive_exact():
    from sda_tpu.protocol import AdditiveSharing, ChaChaMasking

    rng = np.random.default_rng(73)
    P, d = 11, 100
    x = rng.integers(0, 433, size=(P, d))
    expected = x.sum(axis=0) % 433
    for scheme, masking in [
        (GOLDEN, ChaChaMasking(433, d, 128)),
        (AdditiveSharing(share_count=8, modulus=433), ChaChaMasking(433, d, 128)),
        (AdditiveSharing(share_count=8, modulus=433), FullMasking(433)),
    ]:
        agg = StreamingAggregator(
            scheme, masking, participants_chunk=4, dim_chunk=48,
            uniform_tail=True)
        out = agg.aggregate(x, key=jax.random.PRNGKey(9))
        np.testing.assert_array_equal(
            out, expected,
            err_msg=f"{type(scheme).__name__}/{type(masking).__name__}")


def test_uniform_tail_single_tile_unchanged():
    scheme = fast_scheme()
    rng = np.random.default_rng(77)
    x = rng.integers(0, 1 << 16, size=(5, 30))
    a = StreamingAggregator(scheme, FullMasking(scheme.prime_modulus),
                            participants_chunk=8, dim_chunk=3 << 20,
                            uniform_tail=True)
    out = a.aggregate(x, key=jax.random.PRNGKey(1))
    np.testing.assert_array_equal(out, x.sum(axis=0) % scheme.prime_modulus)
    # dim < dim_chunk: the single tile keeps its grain-rounded size, not
    # the full chunk width
    (shape,) = a._steps
    assert shape[1] < a.dim_chunk


def test_uniform_tail_checkpoint_resume_and_fingerprint(tmp_path):
    import os

    from sda_tpu.mesh import synthetic_block_provider32

    scheme = fast_scheme()
    p = scheme.prime_modulus
    prov = synthetic_block_provider32(p, seed=5, max_value=1 << 16)
    key = jax.random.PRNGKey(8)
    P, d = 10, 100

    def agg(**kw):
        return StreamingAggregator(scheme, FullMasking(p),
                                   participants_chunk=4, dim_chunk=36, **kw)

    ref = agg(uniform_tail=True).aggregate_blocks(prov, P, d, key)
    exp = prov(0, P, 0, d).astype(np.int64).sum(axis=0) % p
    np.testing.assert_array_equal(ref, exp)

    # crash mid-round, resume bit-identically under uniform_tail
    ck = str(tmp_path / "ut.ckpt.npz")
    calls = {"n": 0}

    def flaky(p0, p1, d0, d1):
        calls["n"] += 1
        if calls["n"] == 5:
            raise RuntimeError("boom")
        return prov(p0, p1, d0, d1)

    with pytest.raises(RuntimeError):
        agg(uniform_tail=True).aggregate_blocks(
            flaky, P, d, key, checkpoint_path=ck, checkpoint_every_chunks=1)
    assert os.path.exists(ck)
    resumed = agg(uniform_tail=True)
    out = resumed.aggregate_blocks(prov, P, d, key, checkpoint_path=ck,
                                   checkpoint_every_chunks=1)
    assert resumed.last_resumed
    np.testing.assert_array_equal(out, ref)

    # a snapshot written WITHOUT uniform_tail must not be resumed WITH it
    # (accumulator shapes differ mid-round): fingerprints diverge
    calls["n"] = 0
    with pytest.raises(RuntimeError):
        agg().aggregate_blocks(
            flaky, P, d, key, checkpoint_path=ck, checkpoint_every_chunks=1)
    fresh = agg(uniform_tail=True)
    out2 = fresh.aggregate_blocks(prov, P, d, key, checkpoint_path=ck,
                                  checkpoint_every_chunks=1)
    assert not fresh.last_resumed  # foreign snapshot rejected, clean round
    np.testing.assert_array_equal(out2, ref)


def test_uniform_tail_pallas_streamed_exact():
    """uniform_tail + the PALLAS streamed stage — the exact combination
    the TPU suite runs (interpret-mode kernel, external bits): ragged
    tails on both axes pad to the chunk and the aggregate stays exact,
    with one compiled step shape."""
    from util import external_bits

    scheme = fast_scheme()
    p = scheme.prime_modulus
    rng = np.random.default_rng(91)
    P, d, pc, dc = 10, 100, 4, 36  # ragged on both axes
    x = rng.integers(0, 1 << 16, size=(P, d))
    agg = StreamingAggregator(
        scheme, FullMasking(p), participants_chunk=pc, dim_chunk=dc,
        use_pallas=True, pallas_interpret=True,
        pallas_external_bits_fn=external_bits, uniform_tail=True)
    assert agg.pallas_active
    out = agg.aggregate(x, key=jax.random.PRNGKey(7))
    np.testing.assert_array_equal(out, x.sum(axis=0) % p)
    assert len(agg._steps) == 1, list(agg._steps)
