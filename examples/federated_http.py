"""A secure FedAvg round over the real REST protocol, in one process.

Spins up the HTTP server on a loopback port, registers a recipient, an
8-clerk committee, and three participants as ordinary `SdaClient`s
talking REST, then drives one `FederatedSession` round: encoded float
deltas go up, clerks decrypt/sum/re-encrypt, and the recipient reveals
the exact quantized mean.

    JAX_PLATFORMS=cpu python examples/federated_http.py
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from sda_tpu.client import SdaClient
from sda_tpu.crypto import MemoryKeystore
from sda_tpu.http import SdaHttpClient, SdaHttpServer
from sda_tpu.models import FederatedSession, FixedPointCodec
from sda_tpu.protocol import (
    AdditiveSharing,
    Aggregation,
    AggregationId,
    FullMasking,
    SodiumEncryption,
)
from sda_tpu.server import new_memory_server
from sda_tpu.store import Filebased

M31 = (1 << 31) - 1
DIM, N_PART = 32, 3

http_server = SdaHttpServer(new_memory_server(), bind="127.0.0.1:0")
http_server.start_background()
print("serving on", http_server.address)
tmp = tempfile.TemporaryDirectory()


def client(name):
    proxy = SdaHttpClient(http_server.address,
                          store=Filebased(f"{tmp.name}/{name}"))
    ks = MemoryKeystore()
    return SdaClient(SdaClient.new_agent(ks), ks, proxy)


recipient = client("recipient")
rkey = recipient.new_encryption_key()
recipient.upload_agent()
recipient.upload_encryption_key(rkey)

clerks = []
for i in range(8):
    c = client(f"clerk{i}")
    key = c.new_encryption_key()
    c.upload_agent()
    c.upload_encryption_key(key)
    clerks.append(c)

participants = []
for i in range(N_PART):
    p = client(f"part{i}")
    p.upload_agent()
    participants.append(p)

template = Aggregation(
    id=AggregationId.random(), title="fedavg-over-rest",
    vector_dimension=DIM, modulus=M31,
    recipient=recipient.agent.id, recipient_key=rkey,
    masking_scheme=FullMasking(M31),
    committee_sharing_scheme=AdditiveSharing(share_count=8, modulus=M31),
    recipient_encryption_scheme=SodiumEncryption(),
    committee_encryption_scheme=SodiumEncryption(),
)
codec = FixedPointCodec(M31, fractional_bits=16, max_summands=N_PART, clip=4.0)
session = FederatedSession(template, codec, recipient, clerks, participants)

rng = np.random.default_rng(7)
deltas = rng.normal(0, 1, size=(N_PART, DIM))
mean = session.round(list(deltas))

oracle = np.stack([codec.quantize(d) for d in deltas]).sum(0) \
    / codec.scale / N_PART
assert np.array_equal(mean, oracle), "secure mean must equal quantized mean"
print(f"revealed mean delta over {N_PART} participants "
      f"(first 4 dims): {np.round(mean[:4], 4)}")
print("exact vs plaintext quantized oracle: OK")

http_server.shutdown()
tmp.cleanup()
