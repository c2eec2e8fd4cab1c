"""Shared test fixtures: fake-crypto agent factories and service contexts.

Modeled on the reference harness (integration-tests/src/lib.rs): CRUD/logic
tests use agents with all-zero keys and signatures (:51-71) since the server
never verifies signatures; full-loop tests use real crypto via SdaClient.
The fixture decides how distributed the system is — in-process memory,
durable JSON files, or HTTP (the same tests run against each seam).
"""

from __future__ import annotations

from sda_tpu.protocol import (
    Agent,
    AgentId,
    B32,
    B64,
    Binary,
    Encryption,
    EncryptionKey,
    EncryptionKeyId,
    Labelled,
    Signature,
    Signed,
    VerificationKey,
    VerificationKeyId,
)


def new_agent() -> Agent:
    return Agent(
        id=AgentId.random(),
        verification_key=Labelled(VerificationKeyId.random(), VerificationKey("Sodium", B32())),
    )


def new_key_for_agent(agent: Agent) -> Signed:
    return Signed(
        signature=Signature("Sodium", B64()),
        signer=agent.id,
        body=Labelled(EncryptionKeyId.random(), EncryptionKey("Sodium", B32())),
    )


def new_full_agent(service):
    agent = new_agent()
    service.create_agent(agent, agent)
    key = new_key_for_agent(agent)
    service.create_encryption_key(agent, key)
    return agent, key


def mock_encryption(data: bytes) -> Encryption:
    """Raw bytes posing as a ciphertext — server logic never opens them
    (reference mock pattern: integration-tests/tests/service.rs:29-47)."""
    return Encryption("Sodium", Binary(data))


# ---------------------------------------------------------------------------
# Real-MongoDB seam (reference: integration-tests/src/lib.rs:110-140 runs the
# same suites against a live mongod with a random per-test database, dropped
# after). Enabled by SDA_TEST_MONGO_URI; in-image runs use the fake instead.

def mongo_real_params():
    """Extra fixture params when a live mongod is configured."""
    import os

    return ["mongo-real"] if os.environ.get("SDA_TEST_MONGO_URI") else []


def new_mongo_real_service(request):
    """SdaServerService on a fresh random database of the configured
    mongod; registers a finalizer that drops the database."""
    import os
    import uuid

    import pytest

    from sda_tpu.server import mongo as mongo_mod
    from sda_tpu.server import new_mongo_server

    uri = os.environ.get("SDA_TEST_MONGO_URI")
    if not mongo_mod.available():
        pytest.skip("SDA_TEST_MONGO_URI set but pymongo is not installed")
    import pymongo

    client = pymongo.MongoClient(uri, serverSelectionTimeoutMS=5000)
    dbname = "sda_test_" + uuid.uuid4().hex[:12]

    def drop():
        client.drop_database(dbname)
        client.close()

    request.addfinalizer(drop)
    return new_mongo_server(client[dbname])


def scheme_lattice_config(name, dim, *, additive_share_count=8):
    """masking x sharing point of the golden scheme lattice (reference
    pluggability: masking/mod.rs:33-94 x sharing/mod.rs:35-96), mod 433."""
    from sda_tpu.protocol import (
        AdditiveSharing,
        BasicShamirSharing,
        ChaChaMasking,
        FullMasking,
        PackedShamirSharing,
    )

    if name.startswith("add"):
        sharing = AdditiveSharing(share_count=additive_share_count, modulus=433)
    elif name.startswith("basic"):
        sharing = BasicShamirSharing(share_count=8, privacy_threshold=4,
                                     prime_modulus=433)
    else:
        sharing = PackedShamirSharing(3, 8, 4, 433, 354, 150)
    masking = {
        "none": None,
        "full": FullMasking(433),
        "chacha": ChaChaMasking(433, dim, 128),
    }[name.split("-")[1]]
    return sharing, masking


def external_bits(key, P, draws, B):
    """[P, 2*draws, B] uint32 pre-drawn bits for the Pallas round's
    external-randomness mode (layout contract: pallas_round.py) — shared
    by the interpret-mode kernel tests."""
    import jax
    import jax.numpy as jnp

    return jax.random.bits(key, (P, 2 * draws, B), dtype=jnp.uint32)


def chacha_mask_rows(field, round_key, first_id, rows, dim, d_block0,
                     seed_bits):
    """[rows, dim] ChaCha masks a participant row, in element order: what
    the pod's mask stage added to its rows one by one until it folded the
    masks first (PR 40). The program's residues (``simpod._chacha_masks``,
    word-major) with every row put through the layout change, for the
    tests that hold a round's masks row for row."""
    from sda_tpu.fields import chacha_jax
    from sda_tpu.mesh import simpod
    from sda_tpu.protocol import ChaChaMasking

    return chacha_jax.element_order(simpod._chacha_masks(
        ChaChaMasking(field.m, dim, seed_bits), field, round_key, first_id,
        rows, dim, d_block0))


def lowered_ops(lowered):
    """(op, name-stack path) of every op of a lowered program, a callee's
    ops under the path of each of its call sites -- as the compiler's
    inliner composes ``op_name``, which the profiler shows (a scan's body
    stands under ``while/body``). Ops that hold regions (``while``,
    ``shard_map``) are entered, not yielded."""
    import re

    module = lowered.compiler_ir()
    functions = {str(op.attributes["sym_name"]).strip('"'): op
                 for op in module.body.operations
                 if op.operation.name == "func.func"}

    def path_of(op):
        match = re.search(r'loc\("([^"]*)"', str(op.location))
        return match.group(1).split("/") if match else []

    def visit(op, prefix):
        for region in op.regions:
            for block in region.blocks:
                for inner in block.operations:
                    path = prefix + path_of(inner)
                    if inner.operation.name == "func.call":
                        callee = str(inner.attributes["callee"]).lstrip("@")
                        yield from visit(functions[callee.strip('"')], path)
                    elif inner.regions:     # while, shard_map: their bodies
                        yield from visit(inner, prefix)
                    else:
                        yield inner, path

    return list(visit(functions["main"], []))


def one_chip_pallas_pod(scheme, mask=None):
    """The Pallas stage as a 1x1 pod sees it: every row on one device, the
    kernel interpreted and fed ``external_bits`` (no TPU PRNG on the CPU)."""
    from sda_tpu.mesh import SimulatedPod, make_mesh

    return SimulatedPod(
        scheme, mask, mesh=make_mesh(1, 1), use_pallas=True,
        pallas_interpret=True, pallas_external_bits_fn=external_bits)
