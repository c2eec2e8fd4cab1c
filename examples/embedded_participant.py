"""An embedded (C-core) participant joins a packed-Shamir round.

The reference README announces an `/embeddable-client` exposing the
client "in a C-friendly" API for mobile apps (never released). This demo
runs the TPU build's analog end-to-end in one process:

- participant #1's crypto is computed ENTIRELY by the native C core
  (`sda_embed_participate_shamir`): ChaCha-seed masking, packed-Shamir
  share evaluation, varint framing, libsodium sealed boxes;
- participant #2 is an ordinary Python `SdaClient`;
- the Python clerks and recipient decrypt, combine, and reveal — the
  exact sum proves byte-level wire compatibility.

    JAX_PLATFORMS=cpu python examples/embedded_participant.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from sda_tpu import native
from sda_tpu.client import SdaClient
from sda_tpu.client.embed import participate_embedded
from sda_tpu.crypto import MemoryKeystore, sodium
from sda_tpu.protocol import (
    Aggregation,
    AggregationId,
    ChaChaMasking,
    PackedShamirSharing,
    SodiumEncryption,
)
from sda_tpu.server import new_memory_server

DIM, MOD = 8, 433

if not (sodium.available() and native.available()):
    # loud on purpose: in CI this image HAS the toolchain, so an
    # unavailable native core is a build regression, not an environment
    print("error: libsodium or the native build is unavailable — the "
          "embedded demo cannot run", file=sys.stderr)
    raise SystemExit(1)

service = new_memory_server()


def new_client():
    ks = MemoryKeystore()
    c = SdaClient(SdaClient.new_agent(ks), ks, service)
    c.upload_agent()
    return c


recipient = new_client()
rkey = recipient.new_encryption_key()
recipient.upload_encryption_key(rkey)

agg = Aggregation(
    id=AggregationId.random(),
    title="embedded-demo",
    vector_dimension=DIM,
    modulus=MOD,
    recipient=recipient.agent.id,
    recipient_key=rkey,
    # the golden full_loop.rs packed-Shamir config: 8 clerks, threshold 4
    masking_scheme=ChaChaMasking(MOD, DIM, 128),
    committee_sharing_scheme=PackedShamirSharing(3, 8, 4, MOD, 354, 150),
    recipient_encryption_scheme=SodiumEncryption(),
    committee_encryption_scheme=SodiumEncryption(),
)
recipient.upload_aggregation(agg)

clerks = [new_client() for _ in range(8)]
for c in clerks:
    c.upload_encryption_key(c.new_encryption_key())
recipient.begin_aggregation(agg.id)

embedded_update = [3, 1, 4, 1, 5, 9, 2, 6]
python_update = [2, 7, 1, 8, 2, 8, 1, 8]

participate_embedded(new_client(), embedded_update, agg.id)  # C core
new_client().participate(python_update, agg.id)              # Python

recipient.end_aggregation(agg.id)
recipient.run_chores(-1)
for c in clerks:
    c.run_chores(-1)

out = recipient.reveal_aggregation(agg.id).positive().values
expected = (np.asarray(embedded_update) + np.asarray(python_update)) % MOD
assert np.array_equal(out, expected), (out, expected)
print("embedded + python updates:", [int(v) for v in out])
print("C-core participation revealed exactly alongside the Python one: OK")
