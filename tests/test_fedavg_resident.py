"""``pod_fedavg_round`` on arrays the devices hold (models/federated.py):
delta, fixed-point encode, the pod's round, decode and the new global
vector as one device program, against the benchmark's plain reference
(``benchmarks/chip/references/fedavg.py``, loaded by path: it imports
nothing of the program). Toy sizes on the CPU; the kernel is interpreted
and fed external bits where the step is the kernel."""

import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec

from sda_tpu.fields import numtheory
from sda_tpu.mesh import SimulatedPod, StreamingAggregator, make_mesh
from sda_tpu.models import FixedPointCodec, federated, pod_fedavg_round
from sda_tpu.protocol import (AdditiveSharing, ChaChaMasking, FullMasking,
                              PackedShamirSharing)
from sda_tpu.utils import metrics

from util import external_bits

MODULUS = 536870233  # 2^29 - 679: the uint32 fast path
ROWS, DIM = 13, 50   # off every grain: rows and columns are padded
CLIP, FRACTIONAL_BITS = 2.0, 16

_spec = importlib.util.spec_from_file_location(
    "fedavg_reference", Path(__file__).resolve().parents[1]
    / "benchmarks" / "chip" / "references" / "fedavg.py")
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)

#: scheme x masking x step: the kernel serves the packed scheme alone
CASES = [(scheme, masking, step)
         for scheme, steps in (("packed", ("xla", "kernel")),
                               ("additive", ("xla",)))
         for masking in ("none", "full", "chacha") for step in steps]


def _pod(scheme: str, masking: str, step: str, mesh=None) -> SimulatedPod:
    if scheme == "packed":
        t, p, w2, w3 = numtheory.generate_packed_params(3, 8, 28)
        assert p == MODULUS
        sharing = PackedShamirSharing(3, 8, t, p, w2, w3)
    else:
        sharing = AdditiveSharing(3, MODULUS)
    mask = {"none": None, "full": FullMasking(MODULUS),
            "chacha": ChaChaMasking(MODULUS, DIM, 128)}[masking]
    kernel = dict(use_pallas=True, pallas_interpret=True,
                  pallas_external_bits_fn=external_bits) \
        if step == "kernel" else {}
    pod = SimulatedPod(sharing, mask, mesh=mesh or make_mesh(1, 1), **kernel)
    assert pod.pallas_active is (step == "kernel")
    return pod


def _codec() -> FixedPointCodec:
    return FixedPointCodec(MODULUS, FRACTIONAL_BITS, max_summands=ROWS,
                           clip=CLIP)


def _weights(seed: int = 42):
    """A global vector in (-1, 1) and clients a standard normal away from
    it, with what a diverged or hostile client sends among them: NaN,
    +-Inf, float32's largest, values beyond and on the clip, a tie."""
    rng = np.random.default_rng(seed)
    global_vec = rng.uniform(-1, 1, size=DIM).astype(np.float32)
    clients = (global_vec[None, :]
               + rng.normal(size=(ROWS, DIM))).astype(np.float32)
    clients[0, :8] = [np.nan, np.inf, -np.inf, 3.4e38, -3.4e38, 7.5, -7.5, 0]
    clients[1, :3] = global_vec[:3] + np.float32([2.0, -2.0, 2.0 ** -17])
    return global_vec, clients


def _expected(global_vec, clients):
    """(the integer sum, the new global vector rounded to float32, the
    tolerance) of the reference."""
    total = reference.integer_sum(global_vec, clients, MODULUS, CLIP,
                                  FRACTIONAL_BITS, rows=4)
    exact, mean = reference.new_global(global_vec, total, len(clients),
                                       MODULUS, FRACTIONAL_BITS)
    return total, exact.astype(np.float32), reference.tolerance(global_vec, mean)


def _on(mesh, *arrays):
    """Committed to the mesh's devices, whole on each."""
    everywhere = NamedSharding(mesh, PartitionSpec())
    return [jax.device_put(jnp.asarray(a), everywhere) for a in arrays]


def _assert_within(result, want, limit):
    outside, _, share = reference.outside(np.asarray(result), want, limit)
    assert outside == 0, share


@pytest.mark.parametrize("scheme,masking,step", CASES,
                         ids=["-".join(case) for case in CASES])
def test_resident_round_is_the_references_on_every_scheme_masking_and_step(
        scheme, masking, step):
    pod, codec = _pod(scheme, masking, step), _codec()
    global_vec, clients = _weights()
    total, want, limit = _expected(global_vec, clients)
    on_device = _on(pod.mesh, global_vec, clients, jax.random.PRNGKey(1))
    result = pod_fedavg_round(pod, codec, *on_device)
    assert isinstance(result, jax.Array) and result.dtype == jnp.float32
    assert result.shape == (DIM,)
    _assert_within(result, want, limit)
    # the integer stage, read off the same program: exactly the reference's
    both = federated._resident_program(pod, codec, ROWS, DIM,
                                        with_aggregate=True)
    again, aggregate = both(*on_device)
    assert aggregate.dtype == jnp.int64
    np.testing.assert_array_equal(np.asarray(aggregate), total)
    np.testing.assert_array_equal(np.asarray(again), np.asarray(result))
    # the probes took the path the guarantees state: NaN -> 0, the rest clip
    lifted = total - np.where(total > MODULUS // 2, MODULUS, 0)
    others = reference.quantize(clients[1:, :8] - global_vec[None, :8],
                                CLIP, FRACTIONAL_BITS).sum(axis=0)
    q_max = int(CLIP * 2 ** FRACTIONAL_BITS)
    np.testing.assert_array_equal(
        lifted[:7] - others[:7],
        [0, q_max, -q_max, q_max, -q_max, q_max, -q_max])


@pytest.mark.parametrize("reporters", ["all-rows", "nine-of-13"])
@pytest.mark.parametrize("shape", [(1, 2), (2, 1)], ids=["1x2", "2x1"])
@pytest.mark.parametrize("step", ["xla", "kernel"])
def test_resident_round_on_a_mesh(shape, step, reporters):
    """With ``reported`` the round is the reference's over the rows that
    reported (the NaN of row 0 among those that did not), its count summed
    over the mesh's ``p`` axis."""
    pod = _pod("packed", "full", step, mesh=make_mesh(*shape))
    global_vec, clients = _weights(7)
    reported = None
    if reporters == "nine-of-13":
        reported = np.arange(ROWS) % 3 != 0
        _, want, limit = _expected(global_vec, clients[reported])
    else:
        _, want, limit = _expected(global_vec, clients)
    result = pod_fedavg_round(
        pod, _codec(), *_on(pod.mesh, global_vec, clients,
                            jax.random.PRNGKey(2)), reported=reported)
    assert isinstance(result, jax.Array) and result.dtype == jnp.float32
    _assert_within(result, want, limit)


@pytest.mark.parametrize("step", ["xla", "kernel"])
def test_nothing_of_a_resident_round_crosses_to_the_host(step):
    pod, codec = _pod("packed", "full", step), _codec()
    global_vec, clients = _weights()
    _, want, limit = _expected(global_vec, clients)
    on_device = _on(pod.mesh, global_vec, clients, jax.random.PRNGKey(3))
    pod_fedavg_round(pod, codec, *on_device)   # compiles
    metrics.reset_counters()
    with jax.transfer_guard("disallow"):
        result = pod_fedavg_round(pod, codec, *on_device)
        result.block_until_ready()
    assert metrics.counter_report("models.fedavg.") == {
        "models.fedavg.rounds": 1, "models.fedavg.host_bytes": 0}
    assert metrics.counter_report("mesh.feed.") == {}   # nothing was fed
    _assert_within(result, want, limit)
    # one program a (pod, codec, shape), built once
    (program,) = pod._programs.values()
    assert program._cache_size() == 1


def test_a_global_vector_from_the_host_is_put_on_the_devices_and_counted():
    pod, codec = _pod("packed", "full", "xla"), _codec()
    global_vec, clients = _weights()
    _, want, limit = _expected(global_vec, clients)
    metrics.reset_counters()
    result = pod_fedavg_round(pod, codec, global_vec, jnp.asarray(clients),
                              jax.random.PRNGKey(4))
    assert isinstance(result, jax.Array)
    assert metrics.counter_report("models.fedavg.")[
        "models.fedavg.host_bytes"] == global_vec.nbytes
    _assert_within(result, want, limit)


@pytest.mark.parametrize("cohort", ["numpy", "list-of-vectors"])
def test_host_inputs_return_what_they_returned(cohort, monkeypatch):
    """The host contract: float64 subtraction on the host, a NumPy float64
    result, ``global + mean`` of the quantized float64 deltas exactly --
    and the encoded matrix now goes to ``aggregate`` as the device array
    it is, and is not fetched on the way."""
    pod, codec = _pod("packed", "full", "xla"), _codec()
    global_vec, clients = _weights()
    clients = np.nan_to_num(clients.astype(np.float64), posinf=9.0, neginf=-9.0)
    global_vec = global_vec.astype(np.float64)
    mean = np.stack([codec.quantize(row - global_vec) for row in clients]
                    ).sum(axis=0) / codec.scale / ROWS
    handed, aggregate = [], pod.aggregate
    monkeypatch.setattr(pod, "aggregate", lambda inputs, key=None: (
        handed.append(inputs), aggregate(inputs, key))[1])
    metrics.reset_counters()
    result = pod_fedavg_round(
        pod, codec, global_vec,
        clients if cohort == "numpy" else list(clients), jax.random.PRNGKey(5))
    assert isinstance(result, np.ndarray) and result.dtype == np.float64
    np.testing.assert_array_equal(result, global_vec + mean)
    (encoded,) = handed
    assert isinstance(encoded, jax.Array) and encoded.dtype == jnp.int32
    assert metrics.counter_report("models.fedavg.") == {
        "models.fedavg.rounds": 1,
        "models.fedavg.host_bytes": ROWS * DIM * 4 + DIM * 8}


def test_aggregate_keeps_a_device_array_on_the_devices():
    """``SimulatedPod.aggregate`` on a ``jax.Array``: no fetch, the pad
    made where the array lives, the sum as from the host."""
    pod = _pod("packed", "full", "xla")
    inputs = np.random.default_rng(3).integers(
        0, 1 << 20, size=(ROWS, DIM), dtype=np.int64)
    want = inputs.sum(axis=0) % MODULUS
    on_device, key = _on(pod.mesh, inputs.astype(np.int32),
                         jax.random.PRNGKey(6))
    with jax.transfer_guard_device_to_host("disallow"):
        out = pod.aggregate(on_device, key)
    np.testing.assert_array_equal(np.asarray(out), want)
    np.testing.assert_array_equal(np.asarray(pod.aggregate(inputs, key)), want)


def test_a_surface_without_a_traceable_round_keeps_the_host_path():
    t, p, w2, w3 = numtheory.generate_packed_params(3, 8, 28)
    agg = StreamingAggregator(PackedShamirSharing(3, 8, t, p, w2, w3),
                              FullMasking(p), participants_chunk=8)
    global_vec, clients = _weights()
    clients = np.nan_to_num(clients, posinf=9.0, neginf=-9.0)
    codec = _codec()
    mean = np.stack([codec.quantize(row.astype(np.float64) - global_vec)
                     for row in clients]).sum(axis=0) / codec.scale / ROWS
    result = pod_fedavg_round(agg, codec, jnp.asarray(global_vec),
                              jnp.asarray(clients), jax.random.PRNGKey(8))
    assert isinstance(result, np.ndarray) and result.dtype == np.float64
    np.testing.assert_array_equal(result, global_vec.astype(np.float64) + mean)


def test_resident_round_checks_its_arguments():
    pod, codec = _pod("packed", "full", "xla"), _codec()
    global_vec, clients = _weights()
    with pytest.raises(ValueError, match="incompatible"):
        pod_fedavg_round(pod, codec, jnp.asarray(global_vec[:-1]),
                         jnp.asarray(clients))
    with pytest.raises(ValueError, match="exceed codec capacity"):
        pod_fedavg_round(pod, codec, jnp.asarray(global_vec),
                         jnp.asarray(np.tile(clients, (2, 1))))
    other = FixedPointCodec((1 << 29) - 3, 8, max_summands=ROWS, clip=1.0)
    with pytest.raises(ValueError, match="modulus"):
        pod_fedavg_round(pod, other, jnp.asarray(global_vec),
                         jnp.asarray(clients))
