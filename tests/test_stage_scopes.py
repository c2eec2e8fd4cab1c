"""The stage scopes of a round (docs/observability.md, "Stage scopes"):
one closed list of ``jax.named_scope`` names, and every device op a round's
own statements make stands under exactly one of them. Seven rounds on the
CPU (the kernel interpreted and fed external bits where the step is the
kernel; the fifth is the resident FedAvg program, its codec's two scopes
around the round's; the last two take the ``reported`` operand, which adds
ops and no scope), each held to the plain sum bit for bit; and the
compile cache, which the program keys on those scopes wherever it leaves one in force."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sda_tpu.fields import numtheory
from sda_tpu.mesh import StreamingAggregator
from sda_tpu.mesh.simpod import SimulatedPod, make_mesh
from sda_tpu.models import FixedPointCodec, federated
from sda_tpu.protocol import (AdditiveSharing, ChaChaMasking, FullMasking,
                              PackedShamirSharing)
from sda_tpu.utils import backend

from util import external_bits, lowered_ops

MODULUS = 536870233  # 2^29 - 679: the uint32 fast path
ROWS, DIM = 13, 96   # a ragged second scan block; whole ChaCha blocks
INTERPRETED = dict(pallas_interpret=True, pallas_external_bits_fn=external_bits)

#: the list, as docs/observability.md holds it
STAGES = {"sda.residues", "sda.fold", "sda.blocks", "sda.mask", "sda.share",
          "sda.relayout", "sda.mask_share", "sda.clerk_combine",
          "sda.reconstruct", "sda.unmask", "sda.stream.acc",
          "sda.encode", "sda.decode"}
CHILDREN = {"sda.mask.chacha", "sda.mask.reduce", "sda.mask.relayout",
            "sda.mask.fold", "sda.reconstruct.lagrange",
            "sda.reconstruct.unbatch"}
CHACHA = {"sda.mask", "sda.mask.chacha", "sda.mask.reduce",
          "sda.mask.relayout", "sda.mask.fold"}
LAGRANGE = {"sda.reconstruct", "sda.reconstruct.lagrange",
            "sda.reconstruct.unbatch"}
KERNEL = {"sda.residues", "sda.fold", "sda.relayout", "sda.mask_share"}
#: what each round's lowered programs should name, and nothing else
EXPECTED = {
    "packed-full-kernel":
        KERNEL | LAGRANGE | {"sda.clerk_combine", "sda.unmask"},
    "packed-chacha-kernel":
        KERNEL | CHACHA | LAGRANGE | {"sda.clerk_combine", "sda.unmask"},
    # additive: the reconstruction is a plain sum of the rows, no product
    "additive-chacha-xla":
        {"sda.residues", "sda.fold", "sda.blocks", "sda.share",
         "sda.clerk_combine", "sda.reconstruct", "sda.unmask"} | CHACHA,
    "streamed-step-and-finale":
        KERNEL | LAGRANGE | {"sda.stream.acc", "sda.unmask"},
    # models.federated's resident program: the codec around the round
    "fedavg-packed-full-kernel":
        KERNEL | LAGRANGE | {"sda.clerk_combine", "sda.unmask",
                             "sda.encode", "sda.decode"},
}
# the rounds that are told who reported name what the others name: the
# select stands under sda.fold, the count under sda.unmask and sda.decode
EXPECTED.update({
    "fedavg-packed-full-kernel-reported": EXPECTED["fedavg-packed-full-kernel"],
    "additive-chacha-xla-reported": EXPECTED["additive-chacha-xla"]})
REPORTED = np.arange(13) % 4 != 1   # 9 of the 13 rows
#: what ``lax.scan`` lowers to around a body that stands under no stage (the
#: XLA step's): its counter, its test, the slice of a block: no statement's
SCAN_OWN = re.compile(
    r"^(jit\([^/]*\)/)*while/(cond/lt|body/(add|dynamic_slice|squeeze))$")


def _packed() -> PackedShamirSharing:
    t, p, w2, w3 = numtheory.generate_packed_params(3, 8, 28)
    assert p == MODULUS
    return PackedShamirSharing(3, 8, t, p, w2, w3)


def _inputs() -> np.ndarray:
    return np.random.default_rng(38).integers(
        0, 1 << 20, size=(ROWS, DIM), dtype=np.int64)


def _round(name: str):
    """-> (lowered programs, the round's aggregate of ``_inputs()``, of
    the rows ``REPORTED`` where the round is told who reported)."""
    inputs, key = _inputs(), jax.random.PRNGKey(38)
    who = (jnp.asarray(REPORTED),) if name.endswith("reported") else ()
    told = (jax.ShapeDtypeStruct((ROWS,), jnp.bool_),) if who else ()
    if name == "streamed-step-and-finale":
        agg = StreamingAggregator(_packed(), FullMasking(MODULUS),
                                  participants_chunk=8, use_pallas=True,
                                  **INTERPRETED)
        dtype, scalar = agg._field.dtype, jax.ShapeDtypeStruct((), jnp.int32)
        keys = jax.ShapeDtypeStruct((2,), jnp.uint32)
        accs = (jax.ShapeDtypeStruct((8, DIM // 3), dtype),
                jax.ShapeDtypeStruct((DIM,), dtype))
        lowered = [
            agg._step_fn((8, DIM)).lower(
                jax.ShapeDtypeStruct((8, DIM), jnp.int64), keys, keys,
                scalar, scalar, *accs),
            agg._final_fn(DIM).lower(*accs)]
        return lowered, agg.aggregate(inputs, key)
    scheme = (AdditiveSharing(3, MODULUS) if name.startswith("additive")
              else _packed())
    masking = (ChaChaMasking(MODULUS, DIM, 128) if "chacha" in name
               else FullMasking(MODULUS))
    kernel = "kernel" in name
    pod = SimulatedPod(scheme, masking, mesh=make_mesh(1, 1),
                       use_pallas=kernel, **(INTERPRETED if kernel else {}))
    assert pod.pallas_active is kernel
    rows, dim = pod.padded_shape(ROWS, DIM)
    assert (rows, dim) == (ROWS, DIM)
    if name.startswith("fedavg"):
        # weights whose deltas are the inputs as 20 fractional bits exactly:
        # the program's integer stage must reveal their plain sum
        codec = FixedPointCodec(MODULUS, 20, max_summands=ROWS, clip=1.0)
        program = federated._resident_program(
            pod, codec, rows, dim, with_aggregate=True, reported=bool(who))
        global_vec = jnp.full((dim,), 0.5, jnp.float32)
        clients = global_vec + jnp.asarray(inputs / 2.0 ** 20, jnp.float32)
        lowered = [program.lower(
            jax.ShapeDtypeStruct((dim,), jnp.float32),
            jax.ShapeDtypeStruct((rows, dim), jnp.float32),
            jax.ShapeDtypeStruct((2,), jnp.uint32), *told)]
        return lowered, np.asarray(program(global_vec, clients, key, *who)[1])
    step = pod.aggregate_fn(rows, dim, reported=bool(who))
    lowered = [step.lower(jax.ShapeDtypeStruct((rows, dim), jnp.uint32),
                          jax.ShapeDtypeStruct((2,), jnp.uint32), *told)]
    out = step(jnp.asarray(inputs, jnp.uint32), key, *who)
    return lowered, np.asarray(out[0] if who else out)


def _op_paths(lowered):
    """(op name, name-stack path) of every op of a lowered program
    (``util.lowered_ops``); a constant is no op's work."""
    return [(op.operation.name, path) for op, path in lowered_ops(lowered)
            if op.operation.name not in ("func.return", "stablehlo.return",
                                         "sdy.return", "stablehlo.constant")]


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_a_round_names_its_stages_and_reveals_the_plain_sum(name):
    lowered, aggregate = _round(name)
    text = "\n".join(low.as_text(debug_info=True) for low in lowered)
    named = set(re.findall(r'(?<=[/"])sda\.[\w.]+(?=[/"])', text))
    assert named == EXPECTED[name]
    assert named <= STAGES | CHILDREN
    # sda.blocks is the XLA step's scan; the two children of sda.reconstruct
    # wherever there is a Lagrange product
    assert ("sda.blocks" in named) == ("xla" in name)
    assert (LAGRANGE <= named) == (not name.startswith("additive"))
    rows = REPORTED if name.endswith("reported") else slice(None)
    want = _inputs()[rows].sum(axis=0) % MODULUS
    assert aggregate.dtype == np.int64 and np.array_equal(aggregate, want)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_every_op_of_a_round_stands_under_exactly_one_stage(name):
    lowered, _ = _round(name)
    ops = [op for low in lowered for op in _op_paths(low)]
    assert len(ops) > 100
    stray = [(kind, "/".join(path)) for kind, path in ops
             if len(STAGES.intersection(path)) != 1
             and not SCAN_OWN.match("/".join(path))]
    assert stray == []
    # a child scope stands under its parent, nowhere else
    for kind, path in ops:
        for child in CHILDREN.intersection(path):
            assert child.rsplit(".", 1)[0] in path[:path.index(child)]
    # only the XLA step's scan stands under no stage
    own = [path for _, path in ops if not STAGES.intersection(path)]
    assert bool(own) == ("xla" in name)


@pytest.fixture
def cache_config(monkeypatch):
    before = {name: getattr(jax.config, name) for name in (
        "jax_compilation_cache_dir",
        "jax_compilation_cache_include_metadata_in_key")}
    yield monkeypatch
    for name, value in before.items():
        jax.config.update(name, value)


@pytest.mark.parametrize("placed", ["by-the-environment", "by-the-program"])
def test_a_cache_left_in_force_is_keyed_on_the_scopes(cache_config, placed, tmp_path):
    jax.config.update("jax_compilation_cache_include_metadata_in_key", False)
    if placed == "by-the-environment":
        cache_config.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert backend.arm_compile_cache() == str(tmp_path)
    else:
        cache_config.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        cache_config.setattr(jax, "default_backend", lambda: "tpu")
        assert backend.arm_compile_cache().endswith(".jax_compile_cache")
    assert jax.config.jax_compilation_cache_include_metadata_in_key is True


def test_no_cache_in_force_leaves_the_key_alone(cache_config):
    jax.config.update("jax_compilation_cache_include_metadata_in_key", False)
    cache_config.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert backend.arm_compile_cache() is None   # the CPU, nobody placed one
    assert jax.config.jax_compilation_cache_include_metadata_in_key is False
