"""Model layer: fixed-point codec, families, secure FedAvg (both surfaces).

The exactness contract under test: the secure modular sum of encoded
deltas decodes to the *exact* sum of the quantized deltas — FedAvg through
the protocol equals FedAvg on plaintext quantized values bit-for-bit.
"""

import numpy as np
import pytest

from sda_tpu.models import (
    FederatedSession,
    FixedPointCodec,
    LeNet,
    LoRAMLP,
    LocalTrainer,
    MobileLite,
    lora_adapter_params,
    merge_lora_params,
    param_count,
    pod_fedavg_round,
    ravel_pytree,
)

M31 = (1 << 31) - 1  # Mersenne prime, the widest additive modulus allowed


# ---------------------------------------------------------------------------
# codec

def test_codec_sum_exactness():
    rng = np.random.default_rng(7)
    codec = FixedPointCodec(M31, fractional_bits=16, max_summands=10, clip=8.0)
    xs = rng.normal(0, 2, size=(10, 64))
    encoded = np.stack([codec.encode(x) for x in xs])
    secure_sum = np.mod(encoded.sum(axis=0), M31)
    expected = np.stack([codec.quantize(x) for x in xs]).sum(axis=0) / codec.scale
    np.testing.assert_array_equal(codec.decode_sum(secure_sum, 10), expected)


def test_codec_negative_and_clip():
    codec = FixedPointCodec(M31, fractional_bits=8, max_summands=1, clip=2.0)
    enc = codec.encode(np.array([-1.5, 2.0, -2.0, 5.0, -5.0]))
    assert (enc >= 0).all() and (enc < M31).all()
    dec = codec.decode_sum(enc, 1)
    np.testing.assert_array_equal(dec, [-1.5, 2.0, -2.0, 2.0, -2.0])


def test_codec_capacity_guards():
    with pytest.raises(ValueError, match="headroom"):
        FixedPointCodec(433, fractional_bits=8, max_summands=1000)
    with pytest.raises(ValueError, match="capacity"):
        FixedPointCodec(M31, fractional_bits=16, max_summands=100, clip=1e6)
    codec = FixedPointCodec(M31, fractional_bits=16, max_summands=2, clip=1.0)
    with pytest.raises(ValueError, match="summands"):
        codec.decode_sum(np.zeros(4, np.int64), 3)


def test_codec_device_matches_host():
    rng = np.random.default_rng(11)
    codec = FixedPointCodec(M31, fractional_bits=12, max_summands=4, clip=4.0)
    x = rng.normal(0, 1.5, size=(3, 32))
    host = np.stack([codec.encode(row) for row in x])
    dev = np.asarray(codec.encode_device(x))
    np.testing.assert_array_equal(host, dev)


@pytest.mark.parametrize("modulus,fractional_bits,max_summands,clip", [
    (M31, 12, 4, 4.0),            # wide modulus, generous headroom
    (M31, 16, 100, 1.0),          # fine grid, many summands
    ((1 << 20), 8, 3, None),      # small power-of-two modulus, derived clip
    ((1 << 24) - 3, 4, 50, None),  # coarse grid at the capacity-derived cap
])
def test_codec_host_device_bit_exact_property_matrix(
        modulus, fractional_bits, max_summands, clip):
    """The host/device codec claim, at the edges: ``encode`` ==
    ``encode_device`` element-wise over clip boundaries, negative halves,
    the q_max boundary, half-to-even rounding ties, and a random cloud —
    the exactness argument of docs/models.md leans on this equality."""
    codec = FixedPointCodec(modulus, fractional_bits=fractional_bits,
                            max_summands=max_summands, clip=clip)
    step = 1.0 / codec.scale
    eps = step / 8.0
    ties = (np.arange(-9, 9, dtype=np.float64) + 0.5) * step  # .5 grid ties
    probes = np.concatenate([
        np.array([0.0, -0.0, codec.clip, -codec.clip,          # clip edges
                  codec.clip - eps, -codec.clip + eps,
                  codec.clip + 1.0, -codec.clip - 1.0,         # beyond clip
                  codec.clip * 3, -codec.clip * 3]),
        ties, -ties[::-1],                                     # half-to-even
        np.array([step, -step, step / 2, -step / 2,            # neg halves
                  1.5 * step, -1.5 * step, 2.5 * step, -2.5 * step]),
        np.random.default_rng(17).normal(0, codec.clip, size=64),
    ])
    host = codec.encode(probes)
    dev = np.asarray(codec.encode_device(probes), dtype=np.int64)
    np.testing.assert_array_equal(host, dev)
    # both paths clamp the quantized value to the q_max boundary exactly
    q_max = int(round(codec.clip * codec.scale))
    centered = host - np.where(host > modulus // 2, modulus, 0)
    assert centered.max() == q_max and centered.min() == -q_max
    # and the ties actually rounded half to EVEN on both paths
    tie_q = codec.quantize(ties)
    assert (tie_q % 2 == 0).all(), tie_q


@pytest.mark.parametrize("modulus,fractional_bits,max_summands,clip", [
    (M31, 12, 4, 4.0),
    ((1 << 20), 8, 3, None),
])
def test_codec_adversarial_floats_clamp_deterministically(
        modulus, fractional_bits, max_summands, clip):
    """NaN/±Inf from a hostile (or merely diverged) client must clamp
    deterministically on BOTH lanes — NaN -> 0, ±Inf -> ±clip — never an
    undefined float->int64 cast. ``np.clip`` passes NaN through, so this
    pins the explicit scrub; and host/device bit-identity must survive
    the adversarial corners too."""
    codec = FixedPointCodec(modulus, fractional_bits=fractional_bits,
                            max_summands=max_summands, clip=clip)
    probes = np.array([np.nan, -np.nan, np.inf, -np.inf,
                       np.float64(1e300), -np.float64(1e300),  # f32 overflow
                       0.5, -codec.clip / 2], dtype=np.float64)
    q = codec.quantize(probes)
    q_max = codec.q_max
    expected = np.array([0, 0, q_max, -q_max, q_max, -q_max,
                         int(round(0.5 * codec.scale)),
                         -int(round(codec.clip / 2 * codec.scale))],
                        dtype=np.int64)
    np.testing.assert_array_equal(q, expected)
    host = codec.encode(probes)
    assert (host >= 0).all() and (host < modulus).all()
    dev = np.asarray(codec.encode_device(probes), dtype=np.int64)
    np.testing.assert_array_equal(host, dev)
    # a NaN-poisoned vector still decodes: the aggregate of one scrubbed
    # encoding is the scrubbed quantized value, exactly
    np.testing.assert_array_equal(
        codec.decode_sum(host, 1), q.astype(np.float64) / codec.scale)


def test_codec_norm_clip_projects_by_construction():
    """The L2 defense: vectors inside the ball pass through untouched
    (bit-identical to a norm_clip-free codec); vectors outside are
    projected onto the ball — the quantized norm lands at norm_clip
    regardless of how hard the attacker boosted."""
    base = FixedPointCodec(M31, fractional_bits=16, max_summands=4,
                           clip=1.0)
    clipped = FixedPointCodec(M31, fractional_bits=16, max_summands=4,
                              clip=1.0, norm_clip=0.5)
    rng = np.random.default_rng(23)
    inside = rng.normal(0, 1, size=64)
    inside *= 0.4 / np.linalg.norm(inside)
    np.testing.assert_array_equal(clipped.encode(inside),
                                  base.encode(inside))
    boosted = inside * -80.0  # boost:-80 attacker
    q = clipped.quantize(boosted)
    norm = np.linalg.norm(q.astype(np.float64) / clipped.scale)
    assert abs(norm - 0.5) < 1e-3, norm
    # NaN scrub happens before the norm: a single NaN cannot zero the
    # whole projection or poison the reduction
    poisoned = inside.copy()
    poisoned[0] = np.nan
    assert np.isfinite(
        clipped.quantize(poisoned).astype(np.float64)).all()


def test_codec_norm_clip_is_host_lane_only():
    """The L2 reduction is not bit-reproducible between numpy and XLA, so
    a norm-clipped codec must refuse the device encode path with a typed
    error instead of silently forking host/device encodings."""
    with pytest.raises(ValueError, match="norm_clip must be positive"):
        FixedPointCodec(M31, fractional_bits=8, max_summands=2,
                        norm_clip=0.0)
    codec = FixedPointCodec(M31, fractional_bits=8, max_summands=2,
                            norm_clip=1.0)
    with pytest.raises(ValueError, match="host-lane"):
        codec.encode_device(np.zeros(4, np.float32))
    assert "norm_clip" in repr(codec)


def test_codec_decode_rejects_empty_summand_set():
    """decode_sum/decode_mean with summands < 1 is always a caller bug
    (empty frozen set): typed error, not ZeroDivisionError or a silent
    'sum of nothing'."""
    codec = FixedPointCodec(M31, fractional_bits=8, max_summands=4)
    with pytest.raises(ValueError, match="at least one summand"):
        codec.decode_mean(np.zeros(4, np.int64), 0)
    with pytest.raises(ValueError, match="at least one summand"):
        codec.decode_sum(np.zeros(4, np.int64), -2)


P29 = (1 << 29) - 679  # the pod cells' Solinas prime


@pytest.mark.parametrize("count", ["constant", "traced"])
@pytest.mark.parametrize("summands", [1, 7, 1200])
@pytest.mark.parametrize("modulus", [P29, M31, (1 << 20)])
def test_codec_device_decode_is_the_hosts_rounded_to_float32(modulus, summands,
                                                             count):
    """``decode_mean_device`` against ``decode_mean``, the count folded
    into the program or an argument it reads: over the lift's
    boundaries, sums on both sides of float32's exact integers (2^24) and
    random residues, the device's float32 mean is the host's float64 one
    rounded to float32 to within 2^-23 |mean| -- the lift's conversion and
    the division each round once -- and exactly it below 2^24 at one
    summand; ``decode_sum_device`` likewise against ``decode_sum``."""
    import jax
    import jax.numpy as jnp

    codec = FixedPointCodec(modulus, fractional_bits=4, max_summands=1200,
                            clip=16.0)
    half = modulus // 2
    edges = [0, 1, half - 1, half, half + 1, half + 2, modulus - 1]
    wide = [v for v in ((1 << 24) - 1, (1 << 24), (1 << 24) + 1,
                        (1 << 24) + 3, modulus - (1 << 24) - 1,
                        modulus - (1 << 24) - 3) if 0 <= v < modulus]
    rng = np.random.default_rng(42)
    values = np.concatenate(
        [edges, wide, rng.integers(0, modulus, size=4096)]).astype(np.int64)
    for dtype in (jnp.int64, jnp.uint32):
        on_device = jnp.asarray(values, dtype)
        if count == "constant":
            mean = jax.jit(
                lambda v: codec.decode_mean_device(v, summands))(on_device)
        else:
            mean = jax.jit(lambda v, c: codec.decode_mean_device(
                v, c, capacity=1200))(on_device, jnp.int32(summands))
        total = codec.decode_sum_device(on_device, summands)
        assert mean.dtype == total.dtype == jnp.float32
        for got, host in ((mean, codec.decode_mean(values, summands)),
                          (total, codec.decode_sum(values, summands))):
            got = np.asarray(got).astype(np.float64)
            rounded = host.astype(np.float32).astype(np.float64)
            assert (np.abs(got - rounded) <= 2.0 ** -23 * np.abs(host)).all()
            assert np.array_equal(np.sign(got), np.sign(host))
        small = np.abs(codec.decode_sum(values, 1)) * codec.scale < (1 << 24)
        np.testing.assert_array_equal(
            np.asarray(total)[small],
            codec.decode_sum(values, summands)[small].astype(np.float32))
    # the lift's boundary itself: m // 2 stays positive, m // 2 + 1 wraps
    lifted = np.asarray(codec.decode_sum_device(
        jnp.asarray([half, half + 1], jnp.int64), 1)) * codec.scale
    np.testing.assert_array_equal(
        lifted, np.array([half, half + 1 - modulus], np.float32))


def test_codec_device_decode_raises_the_hosts_typed_errors():
    import jax.numpy as jnp

    codec = FixedPointCodec(P29, fractional_bits=8, max_summands=4)
    zeros = jnp.zeros(4, jnp.int64)
    for decode in (codec.decode_mean_device, codec.decode_sum_device):
        with pytest.raises(ValueError, match="at least one summand"):
            decode(zeros, 0)
        with pytest.raises(ValueError, match="exceeds configured capacity"):
            decode(zeros, 5)
    for decode in (codec.decode_mean, codec.decode_sum):  # as they were
        with pytest.raises(ValueError, match="exceeds configured capacity"):
            decode(np.zeros(4, np.int64), 5)


def test_modulus_mismatch_is_rejected():
    """A codec/aggregation modulus mismatch must fail loudly, not decode
    garbage (both FedAvg surfaces validate it)."""
    from sda_tpu.mesh import SimulatedPod, make_mesh
    from sda_tpu.protocol import AdditiveSharing

    pod = SimulatedPod(AdditiveSharing(share_count=8, modulus=M31),
                       mesh=make_mesh(4, 2))
    codec = FixedPointCodec((1 << 29) - 3, fractional_bits=8,
                            max_summands=2, clip=1.0)
    with pytest.raises(ValueError, match="modulus"):
        pod_fedavg_round(pod, codec, np.zeros(8), np.zeros((2, 8)))


def test_ravel_pytree_roundtrip():
    import jax.numpy as jnp

    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"c": jnp.ones((4,), jnp.float32), "d": jnp.zeros(())}}
    vec, unravel = ravel_pytree(tree)
    assert vec.shape == (11,)
    back = unravel(vec + 1.0)
    np.testing.assert_array_equal(np.asarray(back["a"]),
                                  np.arange(6).reshape(2, 3) + 1)
    assert np.asarray(back["b"]["d"]).shape == ()


# ---------------------------------------------------------------------------
# families

def test_lenet_is_the_60k_family():
    import jax

    model = LeNet()
    params = model.init(jax.random.PRNGKey(0), np.zeros((1, 28, 28, 1), np.float32))
    n = param_count(params)
    assert 50_000 < n < 80_000, n
    out = model.apply(params, np.zeros((2, 28, 28, 1), np.float32))
    assert out.shape == (2, 10)


def test_mobilelite_and_lora_forward():
    import jax

    tiny = MobileLite(width=8, block_channels=(16, 24))
    params = tiny.init(jax.random.PRNGKey(0), np.zeros((1, 32, 32, 3), np.float32))
    assert tiny.apply(params, np.zeros((2, 32, 32, 3), np.float32)).shape == (2, 10)

    lora = LoRAMLP(features=64, layers=2, rank=4)
    lp = lora.init(jax.random.PRNGKey(1), np.zeros((1, 16), np.float32))
    assert lora.apply(lp, np.zeros((3, 16), np.float32)).shape == (3, 10)
    adapters = lora_adapter_params(lp)
    assert set(adapters) == {"lora_a_0", "lora_b_0", "lora_a_1", "lora_b_1"}
    merged = merge_lora_params(lp, adapters)
    assert param_count(merged) == param_count(lp)


def test_family_flagship_sizes():
    """The default widths land on the benchmark workload sizes."""
    import jax

    mob = MobileLite()
    mp = jax.eval_shape(
        lambda k: mob.init(k, np.zeros((1, 32, 32, 3), np.float32)),
        jax.random.PRNGKey(0))
    n_mob = param_count(mp)
    assert 2_500_000 < n_mob < 5_000_000, n_mob

    lora = LoRAMLP()
    lp = jax.eval_shape(
        lambda k: lora.init(k, np.zeros((1, 4096), np.float32)),
        jax.random.PRNGKey(0))
    n_ad = param_count(lora_adapter_params(lp))
    assert 9_000_000 < n_ad < 18_000_000, n_ad


# ---------------------------------------------------------------------------
# secure FedAvg — protocol surface

def _new_client(service):
    from sda_tpu.client import SdaClient
    from sda_tpu.crypto import MemoryKeystore

    ks = MemoryKeystore()
    return SdaClient(SdaClient.new_agent(ks), ks, service)


def test_federated_session_exact_round():
    from sda_tpu.crypto import sodium

    if not sodium.available():
        pytest.skip("libsodium not present")
    from sda_tpu.protocol import (
        AdditiveSharing,
        Aggregation,
        AggregationId,
        NoMasking,
        SodiumEncryption,
    )
    from sda_tpu.server import new_memory_server

    dim, n_part = 24, 3
    service = new_memory_server()
    recipient = _new_client(service)
    rkey = recipient.new_encryption_key()
    recipient.upload_agent()
    recipient.upload_encryption_key(rkey)
    clerks = [_new_client(service) for _ in range(3)]
    for c in clerks:
        ck = c.new_encryption_key()
        c.upload_agent()
        c.upload_encryption_key(ck)
    participants = [_new_client(service) for _ in range(n_part)]
    for p in participants:
        p.upload_agent()

    template = Aggregation(
        id=AggregationId.random(), title="fedavg", vector_dimension=dim,
        modulus=M31, recipient=recipient.agent.id, recipient_key=rkey,
        masking_scheme=NoMasking(),
        committee_sharing_scheme=AdditiveSharing(share_count=3, modulus=M31),
        recipient_encryption_scheme=SodiumEncryption(),
        committee_encryption_scheme=SodiumEncryption(),
    )
    codec = FixedPointCodec(M31, fractional_bits=16, max_summands=n_part, clip=4.0)
    session = FederatedSession(template, codec, recipient, clerks, participants)

    rng = np.random.default_rng(3)
    deltas = rng.normal(0, 1, size=(n_part, dim))
    mean = session.round(list(deltas))
    expected = np.stack([codec.quantize(d) for d in deltas]).sum(0) \
        / codec.scale / n_part
    np.testing.assert_array_equal(mean, expected)

    # a second round creates a fresh aggregation and still reveals exactly
    mean2 = session.round(list(-deltas))
    np.testing.assert_array_equal(mean2, -expected)


def test_federated_session_packed_shamir_semantics():
    """FedAvg over Packed-Shamir: values live in Z_m but are SHARED in
    Z_p (p > m). Negative encodings sit near m, so exactness needs
    n_participants * m < p — the codec's modulus is m and the final
    positive() lift mod m recovers the centered sum. Pins that the wrap
    algebra composes (reference: crypto.rs derived properties +
    receive.rs:14-21 lift)."""
    from sda_tpu.crypto import sodium

    if not sodium.available():
        pytest.skip("libsodium not present")
    from sda_tpu.fields import numtheory
    from sda_tpu.protocol import (
        Aggregation,
        AggregationId,
        FullMasking,
        PackedShamirSharing,
        SodiumEncryption,
    )
    from sda_tpu.server import new_memory_server

    t, p, w2, w3 = numtheory.generate_packed_params(3, 8, 28)
    m = 1 << 20  # n * m = 3 * 2^20 << p = 5.4e8: no Z_p wrap
    dim, n_part = 12, 3
    service = new_memory_server()
    recipient = _new_client(service)
    rkey = recipient.new_encryption_key()
    recipient.upload_agent()
    recipient.upload_encryption_key(rkey)
    clerks = [_new_client(service) for _ in range(8)]
    for c in clerks:
        ck = c.new_encryption_key()
        c.upload_agent()
        c.upload_encryption_key(ck)
    participants = [_new_client(service) for _ in range(n_part)]
    for part in participants:
        part.upload_agent()

    template = Aggregation(
        id=AggregationId.random(), title="fedavg-shamir",
        vector_dimension=dim, modulus=m,
        recipient=recipient.agent.id, recipient_key=rkey,
        masking_scheme=FullMasking(m),
        committee_sharing_scheme=PackedShamirSharing(3, 8, t, p, w2, w3),
        recipient_encryption_scheme=SodiumEncryption(),
        committee_encryption_scheme=SodiumEncryption(),
    )
    codec = FixedPointCodec(m, fractional_bits=8, max_summands=n_part)
    session = FederatedSession(template, codec, recipient, clerks,
                               participants)
    rng = np.random.default_rng(9)
    deltas = rng.normal(0, 100, size=(n_part, dim))  # mixed signs, clipped
    mean = session.round(list(deltas))
    expected = np.stack([codec.quantize(d) for d in deltas]).sum(0) \
        / codec.scale / n_part
    np.testing.assert_array_equal(mean, expected)

    # fault tolerance composes with the model layer: one clerk never runs
    # chores, the reconstruction threshold (t + k = 4+3 = 7 of 8) is still
    # met, and the round reveals the exact mean (crypto.rs:146-153)
    session_drop = FederatedSession(
        template, codec, recipient,
        [c for c in clerks if c is not clerks[5]], participants)
    mean2 = session_drop.round(list(-deltas))
    np.testing.assert_array_equal(mean2, -expected)


def test_federated_session_surfaces_typed_round_verdict():
    """A round that cannot complete (additive sharing, one clerk never
    clerks) must surface a typed lifecycle verdict from ``await_result``
    within the deadline — not hang, not silently decode a partial
    committee sum, not a bare NotFound."""
    from sda_tpu.crypto import sodium

    if not sodium.available():
        pytest.skip("libsodium not present")
    from sda_tpu.protocol import (
        AdditiveSharing,
        Aggregation,
        AggregationId,
        NoMasking,
        RoundFailed,
        SodiumEncryption,
    )
    from sda_tpu.server import new_memory_server

    dim, n_part = 8, 2
    service = new_memory_server()
    recipient = _new_client(service)
    rkey = recipient.new_encryption_key()
    recipient.upload_agent()
    recipient.upload_encryption_key(rkey)
    clerks = [_new_client(service) for _ in range(3)]
    for c in clerks:
        c.upload_agent()
        c.upload_encryption_key(c.new_encryption_key())
    participants = [_new_client(service) for _ in range(n_part)]
    for p in participants:
        p.upload_agent()
    template = Aggregation(
        id=AggregationId.random(), title="fedavg-dead", vector_dimension=dim,
        modulus=M31, recipient=recipient.agent.id, recipient_key=rkey,
        masking_scheme=NoMasking(),
        # a 4-of-4 committee over exactly 4 key-holders (recipient + 3
        # clerks): election MUST include clerk 2, whose chores the
        # session below never runs — deterministic regardless of the
        # uuid-sorted suggestion order (the recipient's own chores ARE
        # run by FederatedSession.round)
        committee_sharing_scheme=AdditiveSharing(share_count=4, modulus=M31),
        recipient_encryption_scheme=SodiumEncryption(),
        committee_encryption_scheme=SodiumEncryption(),
    )
    codec = FixedPointCodec(M31, fractional_bits=8, max_summands=n_part,
                            clip=1.0)
    # clerk 2 never runs chores: additive n-of-n can never reconstruct
    session = FederatedSession(template, codec, recipient, clerks[:2],
                               participants)
    deltas = np.random.default_rng(1).normal(0, 0.5, size=(n_part, dim))
    with pytest.raises(RoundFailed):  # RoundExpired subclasses RoundFailed
        session.round(list(deltas), deadline=1.0)


def test_participation_input_ndarray_fast_path():
    """The encoded int64 ndarray goes through ``participate`` without a
    per-element Python conversion; raw float arrays are rejected (a
    silent float->int64 truncation would bypass the codec contract)."""
    from sda_tpu.crypto import sodium

    if not sodium.available():
        pytest.skip("libsodium not present")
    from sda_tpu.protocol import (
        AdditiveSharing,
        Aggregation,
        AggregationId,
        NoMasking,
        SodiumEncryption,
    )
    from sda_tpu.server import new_memory_server

    service = new_memory_server()
    recipient = _new_client(service)
    rkey = recipient.new_encryption_key()
    recipient.upload_agent()
    recipient.upload_encryption_key(rkey)
    clerks = [_new_client(service) for _ in range(3)]
    for c in clerks:
        c.upload_agent()
        c.upload_encryption_key(c.new_encryption_key())
    participant = _new_client(service)
    participant.upload_agent()
    aggregation = Aggregation(
        id=AggregationId.random(), title="nd", vector_dimension=16,
        modulus=M31, recipient=recipient.agent.id, recipient_key=rkey,
        masking_scheme=NoMasking(),
        committee_sharing_scheme=AdditiveSharing(share_count=3, modulus=M31),
        recipient_encryption_scheme=SodiumEncryption(),
        committee_encryption_scheme=SodiumEncryption(),
    )
    recipient.upload_aggregation(aggregation)
    recipient.begin_aggregation(aggregation.id)
    codec = FixedPointCodec(M31, fractional_bits=8, max_summands=2, clip=1.0)
    encoded = codec.encode(np.random.default_rng(2).normal(0, 0.4, size=16))
    assert encoded.dtype == np.int64
    participant.participate(encoded, aggregation.id)  # ndarray, no list()
    status = service.get_aggregation_status(recipient.agent, aggregation.id)
    assert status.number_of_participations == 1
    with pytest.raises(ValueError, match="FixedPointCodec"):
        participant.new_participation(
            np.zeros(16, dtype=np.float64), aggregation.id)


# ---------------------------------------------------------------------------
# secure FedAvg — mesh surface + real training

def test_pod_fedavg_training_improves():
    """Two secure FedAvg rounds on the 8-device pod mesh train a real model.

    Linear-regression MLP on synthetic data; every client update is encoded,
    shared, and aggregated through SimulatedPod. Loss must drop and the
    aggregate must match the plaintext quantized mean exactly.
    """
    import jax
    import jax.numpy as jnp
    import optax

    from sda_tpu.mesh import SimulatedPod, make_mesh
    from sda_tpu.protocol import AdditiveSharing

    rng = np.random.default_rng(0)
    w_true = rng.normal(size=(8,))
    xs = rng.normal(size=(4, 16, 8)).astype(np.float32)  # 4 clients
    ys = (xs @ w_true).astype(np.float32)

    def loss_fn(params, batch):
        x, y = batch
        pred = x @ params["w"] + params["b"]
        return jnp.mean((pred - y) ** 2)

    trainer = LocalTrainer(loss_fn, optax.sgd(0.05))
    global_params = {"w": jnp.zeros((8,), jnp.float32),
                     "b": jnp.zeros((), jnp.float32)}
    global_vec, unravel = ravel_pytree(global_params)

    pod = SimulatedPod(AdditiveSharing(share_count=8, modulus=M31),
                       mesh=make_mesh(4, 2))
    codec = FixedPointCodec(M31, fractional_bits=16, max_summands=4, clip=4.0)

    def global_loss(params):
        return float(np.mean([loss_fn(params, (xs[i], ys[i])) for i in range(4)]))

    losses = [global_loss(global_params)]
    for _ in range(2):
        client_vecs = []
        for i in range(4):
            p = unravel(global_vec)
            st = trainer.init_state(p)
            batches = (jnp.tile(xs[i][None], (3, 1, 1)),
                       jnp.tile(ys[i][None], (3, 1)))
            p, st, _ = trainer.fit(p, st, batches)
            vec, _ = ravel_pytree(p)
            client_vecs.append(vec)

        # plaintext oracle for the same quantized round
        deltas = np.stack(client_vecs) - global_vec[None, :]
        expected_mean = np.stack(
            [codec.quantize(d) for d in deltas]).sum(0) / codec.scale / 4

        key = jax.random.PRNGKey(len(losses))
        new_vec = pod_fedavg_round(pod, codec, global_vec, client_vecs, key)
        np.testing.assert_allclose(new_vec - global_vec, expected_mean,
                                   rtol=0, atol=0)
        global_vec = new_vec
        global_params = unravel(global_vec)
        losses.append(global_loss(global_params))

    assert losses[-1] < losses[0] * 0.7, losses


def test_streamed_fedavg_lora_adapters():
    """pod_fedavg_round is polymorphic over the aggregation surfaces: the
    same call drives StreamedPod (the HBM-exceeding large-model path, i.e.
    the lora-13m setting) with LoRA adapter vectors, exactly."""
    import jax

    from sda_tpu.mesh import StreamedPod, make_mesh
    from sda_tpu.protocol import AdditiveSharing

    lora = LoRAMLP(features=32, layers=2, rank=4)
    lp = lora.init(jax.random.PRNGKey(0), np.zeros((1, 16), np.float32))
    adapters = lora_adapter_params(lp)
    gvec, unravel = ravel_pytree(adapters)

    pod = StreamedPod(AdditiveSharing(share_count=8, modulus=M31),
                      mesh=make_mesh(4, 2), dim_chunk=256)
    codec = FixedPointCodec(M31, fractional_bits=16, max_summands=3, clip=2.0)

    rng = np.random.default_rng(5)
    client_vecs = gvec[None, :] + rng.normal(0, 0.1, size=(3, gvec.size))
    deltas = client_vecs - gvec[None, :]
    expected = np.stack([codec.quantize(d) for d in deltas]).sum(0) \
        / codec.scale / 3

    new_vec = pod_fedavg_round(pod, codec, gvec, client_vecs,
                               jax.random.PRNGKey(9))
    # compare the updated vector itself: (g + m) - g re-rounds in float64
    np.testing.assert_array_equal(new_vec, gvec + expected)
    merged = merge_lora_params(lp, unravel(new_vec))
    assert lora.apply(merged, np.zeros((2, 16), np.float32)).shape == (2, 10)
