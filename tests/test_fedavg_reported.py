"""A round whose reporters differ every round: the ``reported`` operand of
``SimulatedPod``'s round and of ``pod_fedavg_round`` (mesh/simpod.py,
models/federated.py), and ``decode_mean_device`` by a count the program
reads (models/encoding.py). Against the round on the compacted rows, bit
for bit, and against the benchmark's plain reference
(``benchmarks/chip/references/fedavg_reported.py``, loaded by path: it
imports nothing of the program). Toy sizes on the CPU; the kernel is
interpreted and fed external bits where the step is the kernel."""

import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec

from sda_tpu.fields import numtheory
from sda_tpu.mesh import (SimulatedPod, StreamedPod, StreamingAggregator,
                          make_mesh, multihost)
from sda_tpu.models import FixedPointCodec, encoding, federated, pod_fedavg_round
from sda_tpu.protocol import (AdditiveSharing, ChaChaMasking, FullMasking,
                              PackedShamirSharing)
from sda_tpu.utils import metrics

from util import external_bits

MODULUS = 536870233  # 2^29 - 679: the uint32 fast path
ROWS, DIM = 13, 50   # off every grain: rows and columns are padded
CLIP, FRACTIONAL_BITS = 2.0, 16
COMPILED = "/jax/core/compile/backend_compile_duration"

_spec = importlib.util.spec_from_file_location(
    "fedavg_reported_reference", Path(__file__).resolve().parents[1]
    / "benchmarks" / "chip" / "references" / "fedavg_reported.py")
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)

MESHES = [(1, 1), (2, 1), (1, 2)]
MASKINGS = ["none", "full", "chacha"]
STEPS = ["xla", "kernel"]


def _packed() -> PackedShamirSharing:
    t, p, w2, w3 = numtheory.generate_packed_params(3, 8, 28)
    assert p == MODULUS
    return PackedShamirSharing(3, 8, t, p, w2, w3)


def _pod(masking: str = "full", step: str = "xla", mesh=(1, 1),
         scheme: str = "packed") -> SimulatedPod:
    sharing = _packed() if scheme == "packed" else AdditiveSharing(3, MODULUS)
    mask = {"none": None, "full": FullMasking(MODULUS),
            "chacha": ChaChaMasking(MODULUS, DIM, 128)}[masking]
    kernel = dict(use_pallas=True, pallas_interpret=True,
                  pallas_external_bits_fn=external_bits) \
        if step == "kernel" else {}
    pod = SimulatedPod(sharing, mask, mesh=make_mesh(*mesh), **kernel)
    assert pod.pallas_active is (step == "kernel")
    return pod


def _codec(rows: int = ROWS) -> FixedPointCodec:
    return FixedPointCodec(MODULUS, FRACTIONAL_BITS, max_summands=rows,
                           clip=CLIP)


def _weights(seed: int = 44):
    """A global vector in (-1, 1), clients a standard normal away from it
    (so some deltas pass the clip), and who reported: 8 of the 13 rows."""
    rng = np.random.default_rng(seed)
    global_vec = rng.uniform(-1, 1, size=DIM).astype(np.float32)
    clients = (global_vec[None, :]
               + rng.normal(size=(ROWS, DIM))).astype(np.float32)
    reported = np.zeros(ROWS, dtype=bool)
    reported[rng.choice(ROWS, size=8, replace=False)] = True
    return global_vec, clients, reported


def _expected(global_vec, clients, reported, dtype=None):
    """(the integer sum, the new global vector rounded to float32, the
    tolerance) of the reference."""
    xp = np if dtype is None else jnp
    total = np.asarray(reference.integer_sum(
        xp.asarray(global_vec), xp.asarray(clients), xp.asarray(reported),
        MODULUS, CLIP, FRACTIONAL_BITS, rows=4, xp=xp, dtype=dtype))
    exact, mean = reference.new_global(global_vec, total, int(reported.sum()),
                                       MODULUS, FRACTIONAL_BITS)
    return total, exact.astype(np.float32), reference.tolerance(global_vec, mean)


def _on(mesh, *arrays):
    """Committed to the mesh's devices, whole on each."""
    everywhere = NamedSharding(mesh, PartitionSpec())
    return [jax.device_put(jnp.asarray(a), everywhere) for a in arrays]


# -- (a) the round with ``reported`` is the round on the compacted rows ------

@pytest.mark.parametrize("mesh", MESHES, ids=["1x1", "2x1", "1x2"])
@pytest.mark.parametrize("masking", MASKINGS)
@pytest.mark.parametrize("step", STEPS)
def test_the_integer_aggregate_is_the_compacted_rounds_bit_for_bit(
        step, masking, mesh):
    pod = _pod(masking, step, mesh)
    rng = np.random.default_rng(5)
    inputs = rng.integers(0, 1 << 20, size=(ROWS, DIM), dtype=np.int64)
    reported = rng.random(ROWS) < 0.6
    key = jax.random.PRNGKey(9)
    got = np.asarray(pod.aggregate(inputs, key, reported=reported))
    assert got.dtype == np.int64
    np.testing.assert_array_equal(
        got, np.asarray(pod.aggregate(inputs[reported], key)))
    np.testing.assert_array_equal(got, inputs[reported].sum(axis=0) % MODULUS)
    # 0/1 integers and a device array say the same as booleans
    for form in (reported.astype(np.int64).tolist(), jnp.asarray(reported)):
        np.testing.assert_array_equal(
            np.asarray(pod.aggregate(inputs, key, reported=form)), got)


def test_the_round_returns_its_count_beside_the_aggregate():
    pod = _pod("full", "xla", (2, 1))
    rows, dim = pod.padded_shape(ROWS, DIM)
    inputs = np.zeros((rows, dim), np.int64)
    inputs[:ROWS] = 7
    reported = np.zeros(rows, dtype=bool)
    reported[[0, 3, 4, 11]] = True
    aggregate, count = pod.aggregate_fn(rows, dim, reported=True)(
        inputs, jax.random.PRNGKey(1), reported)
    assert count.dtype == jnp.int32 and count.shape == () and int(count) == 4
    np.testing.assert_array_equal(np.asarray(aggregate), np.full(dim, 28))


def test_a_reported_of_another_length_is_refused():
    pod = _pod()
    with pytest.raises(ValueError, match="the cohort has 13 rows"):
        pod.aggregate(np.zeros((ROWS, DIM), np.int64),
                      reported=np.ones(ROWS + 1, bool))
    global_vec, clients, reported = _weights()
    for cohort in (clients, jnp.asarray(clients)):
        with pytest.raises(ValueError, match="the cohort has 13 rows"):
            pod_fedavg_round(pod, _codec(), jnp.asarray(global_vec), cohort,
                             reported=reported[:-1])


# -- (b) what a row that did not report holds reaches nothing ----------------

GARBAGE = [np.nan, np.inf, -np.inf, 3e38, -3e38]


@pytest.mark.parametrize("contract", ["resident-xla", "resident-kernel", "host"])
def test_garbage_in_the_rows_that_did_not_report_changes_no_bit(contract):
    pod = _pod("full", "kernel" if contract.endswith("kernel") else "xla")
    global_vec, clients, reported = _weights()
    fouled = clients.copy()
    fouled[~reported] = np.resize(np.float32(GARBAGE), (5, DIM))
    if contract == "host":
        place = lambda *arrays: [np.asarray(a) for a in arrays]  # noqa: E731
    else:
        place = lambda *arrays: _on(pod.mesh, *arrays)            # noqa: E731
    key = jax.random.PRNGKey(3)
    clean = pod_fedavg_round(pod, _codec(), *place(global_vec, clients), key,
                             reported=reported)
    dirty = pod_fedavg_round(pod, _codec(), *place(global_vec, fouled), key,
                             reported=reported)
    assert np.isfinite(np.asarray(dirty)).all()
    assert np.asarray(clean).tobytes() == np.asarray(dirty).tobytes()
    # and the integer stage of the resident program, exactly the reference's
    if contract != "host":
        both = federated._resident_program(pod, _codec(), ROWS, DIM,
                                           with_aggregate=True, reported=True)
        _, aggregate = both(*_on(pod.mesh, global_vec, fouled, key, reported))
        total, _, _ = _expected(global_vec, clients, reported)
        np.testing.assert_array_equal(np.asarray(aggregate), total)


# -- (c) all rows, and no row -------------------------------------------------

@pytest.mark.parametrize("contract", ["resident-xla", "resident-kernel", "host"])
def test_all_rows_reported_is_the_round_without_the_operand_bit_for_bit(contract):
    pod = _pod("full", "kernel" if contract.endswith("kernel") else "xla")
    global_vec, clients, _ = _weights()
    if contract != "host":
        global_vec, clients = _on(pod.mesh, global_vec, clients)
    key = jax.random.PRNGKey(4)
    plain = pod_fedavg_round(pod, _codec(), global_vec, clients, key)
    everyone = pod_fedavg_round(pod, _codec(), global_vec, clients, key,
                                reported=np.ones(ROWS, bool))
    assert type(plain) is type(everyone) and plain.dtype == everyone.dtype
    assert np.asarray(plain).tobytes() == np.asarray(everyone).tobytes()


@pytest.mark.parametrize("contract", ["resident-xla", "resident-kernel", "host"])
def test_no_reporter_returns_the_global_vector(contract):
    pod = _pod("full", "kernel" if contract.endswith("kernel") else "xla")
    global_vec, clients, _ = _weights()
    clients[3] = np.nan
    held = global_vec
    if contract != "host":
        global_vec, clients = _on(pod.mesh, global_vec, clients)
    result = pod_fedavg_round(pod, _codec(), global_vec, clients,
                              jax.random.PRNGKey(5),
                              reported=np.zeros(ROWS, bool))
    np.testing.assert_array_equal(np.asarray(result), held)


# -- (d) against the plain reference ------------------------------------------

#: scheme x masking x step: the kernel serves the packed scheme alone
CASES = [(scheme, masking, step)
         for scheme, steps in (("packed", STEPS), ("additive", ("xla",)))
         for masking in MASKINGS for step in steps]


@pytest.mark.parametrize("scheme,masking,step", CASES,
                         ids=["-".join(case) for case in CASES])
def test_the_resident_round_is_the_references_over_the_rows_that_reported(
        scheme, masking, step):
    pod, codec = _pod(masking, step, scheme=scheme), _codec()
    global_vec, clients, reported = _weights()
    _, want, limit = _expected(global_vec, clients, reported)
    metrics.reset_counters()
    result = pod_fedavg_round(
        pod, codec, *_on(pod.mesh, global_vec, clients, jax.random.PRNGKey(1)),
        reported=reported)
    assert isinstance(result, jax.Array) and result.dtype == jnp.float32
    outside, _, share = reference.outside(np.asarray(result), want, limit)
    assert outside == 0, share
    # the coordinator's list went up, 13 bytes, and was counted
    assert metrics.counter_report("models.fedavg.") == {
        "models.fedavg.rounds": 1, "models.fedavg.host_bytes": ROWS,
        "models.fedavg.reported_rows": 8}
    # a program that sums every row, or divides by the buffer's rows, is
    # what the reference must tell from it: both are far outside
    everyone = np.asarray(pod_fedavg_round(
        pod, codec, jnp.asarray(global_vec), jnp.asarray(clients),
        jax.random.PRNGKey(1)))
    assert reference.outside(everyone, want, limit)[0] > DIM // 2
    by_rows = global_vec + (np.asarray(result) - global_vec) * np.float32(8 / ROWS)
    assert reference.outside(by_rows, want, limit)[0] > DIM // 2


def test_a_bfloat16_encode_fails_the_references_tolerance():
    """The comparison's other reading: the reference itself with steps 1-2
    in bfloat16, the precision below the configuration's, is outside."""
    global_vec, clients, reported = _weights()
    _, want, limit = _expected(global_vec, clients, reported)
    _, coarse, _ = _expected(global_vec, clients, reported, dtype=jnp.bfloat16)
    outside, _, share = reference.outside(coarse, want, limit)
    assert outside > DIM // 2 and share > 100


def test_the_host_contract_divides_by_the_hosts_count():
    pod, codec = _pod(), _codec()
    global_vec, clients, reported = _weights()
    global_vec, clients = global_vec.astype(np.float64), clients.astype(np.float64)
    mean = np.stack([codec.quantize(row - global_vec)
                     for row in clients[reported]]).sum(axis=0) / codec.scale / 8
    metrics.reset_counters()
    result = pod_fedavg_round(pod, codec, global_vec, clients,
                              jax.random.PRNGKey(2), reported=reported)
    assert isinstance(result, np.ndarray) and result.dtype == np.float64
    np.testing.assert_array_equal(result, global_vec + mean)
    assert metrics.counter_report("models.fedavg.") == {
        "models.fedavg.rounds": 1, "models.fedavg.reported_rows": 8,
        "models.fedavg.host_bytes": ROWS * DIM * 4 + DIM * 8 + ROWS}


def test_a_device_reported_is_used_where_it_lies():
    pod, codec = _pod(), _codec()
    global_vec, clients, reported = _weights()
    _, want, limit = _expected(global_vec, clients, reported)
    on_device = _on(pod.mesh, global_vec, clients, jax.random.PRNGKey(6),
                    reported)
    pod_fedavg_round(pod, codec, *on_device[:3], reported=on_device[3])
    metrics.reset_counters()
    with jax.transfer_guard("disallow"):
        result = pod_fedavg_round(pod, codec, *on_device[:3],
                                  reported=on_device[3])
        result.block_until_ready()
    # nothing crossed, and the host never saw who reported: no count of rows
    assert metrics.counter_report("models.fedavg.") == {
        "models.fedavg.rounds": 1, "models.fedavg.host_bytes": 0}
    assert reference.outside(np.asarray(result), want, limit)[0] == 0


# -- (e) one build, one compile, whoever reported ----------------------------

@pytest.fixture
def compiles():
    """Compile requests since the fixture was made, from ``jax.monitoring``."""
    from jax import monitoring
    from jax._src import monitoring as registry

    seen = []

    def listener(event, _seconds, **_kw):
        if event == COMPILED:
            seen.append(event)

    monitoring.register_event_duration_secs_listener(listener)
    yield seen
    registry.unregister_event_duration_listener(listener)


def _eight_sets():
    """Eight reporter sets of eight distinct counts, 5 .. 12 of 13 rows."""
    rng = np.random.default_rng(8)
    sets = np.zeros((8, ROWS), dtype=bool)
    for row, count in zip(sets, rng.permutation(np.arange(5, 13))):
        row[rng.choice(ROWS, size=count, replace=False)] = True
    assert len(set(sets.sum(axis=1))) == 8
    return sets


@pytest.mark.parametrize("step", STEPS)
def test_eight_sets_of_reporters_cost_one_build_and_one_compile(step, compiles):
    pod, codec = _pod("full", step), _codec()
    global_vec, clients, _ = _weights()
    on_device = _on(pod.mesh, global_vec, clients)
    keys = [jax.random.fold_in(jax.random.PRNGKey(7), i) for i in range(9)]
    sets = _eight_sets()
    metrics.reset_counters()
    pod_fedavg_round(pod, codec, *on_device, keys[8],
                     reported=sets[0]).block_until_ready()   # the warm-up
    assert metrics.counter_report("mesh.round.") == {"mesh.round.builds": 1}
    del compiles[:]
    for who, key in zip(sets, keys):
        result = pod_fedavg_round(pod, codec, *on_device, key, reported=who)
        _, want, limit = _expected(global_vec, clients, who)
        assert reference.outside(np.asarray(result), want, limit)[0] == 0
    assert compiles == []
    assert metrics.counter_report("mesh.round.") == {"mesh.round.builds": 1}
    (program,) = pod._programs.values()
    assert program._cache_size() == 1
    assert metrics.counter_report("models.fedavg.")[
        "models.fedavg.reported_rows"] == sets[0].sum() + sets.sum()


def test_aggregate_builds_once_for_eight_sets_of_reporters(compiles):
    pod = _pod("full", "xla", (2, 1))
    inputs = np.random.default_rng(2).integers(
        0, 1 << 20, size=(ROWS, DIM), dtype=np.int64)
    sets, key = _eight_sets(), jax.random.PRNGKey(11)
    metrics.reset_counters()
    pod.aggregate(inputs, key, reported=sets[0])
    del compiles[:]
    for who in sets:
        np.testing.assert_array_equal(
            np.asarray(pod.aggregate(inputs, key, reported=who)),
            inputs[who].sum(axis=0) % MODULUS)
    assert compiles == []
    assert metrics.counter_report("mesh.round.") == {"mesh.round.builds": 1}
    # the round without the operand is another program: one more build
    pod.aggregate(inputs, key)
    assert metrics.counter_report("mesh.round.") == {"mesh.round.builds": 2}


# -- (f) the decode by a count the program reads ------------------------------

CAPACITY = 1200
Q_MAX = 131072  # clip 2.0 at 16 fractional bits: 1200 of them never wrap


def _lifts(kind: str, count: int) -> np.ndarray:
    """Centered lifts a round of ``count`` reporters can reveal."""
    top = Q_MAX * count
    if kind == "extremes":     # the whole range's ends, and just inside them
        lifts = [top, top - 1, top - count + 1, -top, 1 - top, count - 1 - top]
    elif kind == "below-one":  # |mean| < 1: the reciprocal's rounding, bare
        lifts = [1, -1, count - 1, 1 - count, count // 2, -(count // 3)]
    else:                      # around float32's exact integers, and at large
        rng = np.random.default_rng(count)
        lifts = [*((1 << 24) + np.arange(-2, 3)) % (top + 1),
                 *rng.integers(-top, top + 1, size=24)]
    return np.asarray(lifts, dtype=np.int64)


@pytest.mark.parametrize("kind", ["extremes", "below-one", "spread"])
def test_decode_by_a_traced_count_holds_its_bound_for_every_count(kind):
    """``decode_mean_device`` with the count an argument of the program:
    for every count from 1 to the capacity the float32 mean is the host's
    float64 one rounded to float32 to within 2^-23 |mean|, one compile."""
    codec = FixedPointCodec(MODULUS, FRACTIONAL_BITS, max_summands=CAPACITY,
                            clip=CLIP)
    assert codec.q_max == Q_MAX
    decode = jax.jit(lambda values, count: codec.decode_mean_device(
        values, count, capacity=CAPACITY))
    for count in range(1, CAPACITY + 1):
        lifts = _lifts(kind, count)
        values = np.mod(lifts, MODULUS)
        got = np.asarray(decode(jnp.asarray(values, jnp.uint32),
                                jnp.int32(count))).astype(np.float64)
        host = codec.decode_mean(values, count)
        np.testing.assert_array_equal(host, lifts / codec.scale / count)
        rounded = host.astype(np.float32).astype(np.float64)
        assert (np.abs(got - rounded) <= 2.0 ** -23 * np.abs(host)).all(), count
        assert np.array_equal(np.sign(got), np.sign(host)), count
    assert decode._cache_size() == 1


@pytest.mark.parametrize("count", [1, 7, 923, 1137, 1200])
def test_decode_by_a_traced_count_is_the_constant_counts_bit_for_bit(count):
    codec = FixedPointCodec(MODULUS, FRACTIONAL_BITS, max_summands=CAPACITY,
                            clip=CLIP)
    values = jnp.asarray(np.random.default_rng(count).integers(
        0, MODULUS, size=4096), jnp.uint32)
    traced = jax.jit(lambda v, c: codec.decode_mean_device(
        v, c, capacity=CAPACITY))(values, jnp.int32(count))
    constant = jax.jit(lambda v: codec.decode_mean_device(v, count))(values)
    assert traced.dtype == constant.dtype == jnp.float32
    assert np.asarray(traced).tobytes() == np.asarray(constant).tobytes()


def test_decode_by_a_traced_count_of_zero_is_exactly_zero():
    codec = FixedPointCodec(MODULUS, FRACTIONAL_BITS, max_summands=CAPACITY,
                            clip=CLIP)
    mean = jax.jit(lambda v, c: codec.decode_mean_device(
        v, c, capacity=CAPACITY))(jnp.zeros(8, jnp.uint32), jnp.int32(0))
    assert np.asarray(mean).tobytes() == np.zeros(8, np.float32).tobytes()


def test_a_traced_count_needs_a_capacity_within_the_codecs():
    codec = FixedPointCodec(MODULUS, FRACTIONAL_BITS, max_summands=4)
    zeros = jnp.zeros(4, jnp.uint32)
    with pytest.raises(ValueError, match="static capacity"):
        jax.jit(codec.decode_mean_device)(zeros, jnp.int32(2))
    with pytest.raises(ValueError, match="exceeds configured capacity"):
        jax.jit(lambda v, c: codec.decode_mean_device(v, c, capacity=5))(
            zeros, jnp.int32(2))


def test_the_device_reciprocal_is_the_hosts_for_every_count_below_2_to_24():
    counts = np.arange(1, 1 << 24, dtype=np.int64)
    got = jax.jit(jax.vmap(encoding._reciprocal_device))(jnp.asarray(counts))
    assert got.dtype == jnp.float32
    assert np.asarray(got).tobytes() == (1.0 / counts).astype(np.float32).tobytes()


@pytest.mark.parametrize("off", [-127, -9, -1, 0, 1, 9, 127])
def test_a_quotient_proposed_within_127_settles_on_the_nearest(off):
    """A division only proposes: whatever it rounds to within 127 of
    2^47 / c, the integers settle on the nearest."""
    c = np.arange(1 << 23, 1 << 24, 499, dtype=np.uint32)
    nearest = np.asarray([((1 << 47) + int(x) // 2) // int(x) for x in c])
    settled = jax.jit(jax.vmap(encoding._settle_quotient))(
        jnp.asarray((nearest + off).astype(np.uint32)), jnp.asarray(c))
    np.testing.assert_array_equal(np.asarray(settled), nearest)


# -- (g) the drivers that stream take no ``reported`` -------------------------

@pytest.mark.parametrize("driver", ["StreamingAggregator", "StreamedPod",
                                    "multihost", "fedavg-over-a-stream"])
def test_the_streamed_drivers_refuse_the_operand(driver):
    inputs = np.zeros((8, 48), np.int64)
    reported = np.ones(8, bool)
    if driver == "StreamedPod":
        surface = StreamedPod(_packed(), FullMasking(MODULUS),
                              mesh=make_mesh(1, 1), participants_chunk=8)
    else:
        surface = StreamingAggregator(_packed(), FullMasking(MODULUS),
                                      participants_chunk=8)
    with pytest.raises(NotImplementedError, match="takes no `reported`"):
        if driver == "multihost":
            multihost.aggregate_process_local(_pod(), inputs, reported=reported)
        elif driver == "fedavg-over-a-stream":
            pod_fedavg_round(surface, _codec(8), np.zeros(48), inputs * 1.0,
                             reported=reported)
        else:
            surface.aggregate(inputs, reported=reported)
    if driver in ("StreamingAggregator", "StreamedPod"):   # as it was, without
        np.testing.assert_array_equal(surface.aggregate(inputs), 0)
