"""Driver ``federated``: the secure sum through the real protocol stack,
one round at a time -- sealed boxes, the asyncio HTTP plane on loopback,
sqlite on disk, eight clerks, a recipient that reveals.

A round makes the public calls ``FederatedSession.round`` makes
(``sda_tpu/models/federated.py``): ``upload_aggregation``,
``begin_aggregation``, ``codec.encode`` + ``participate`` per device,
``end_aggregation``, ``run_chores(-1)`` for recipient and clerks,
``await_result``. Uploads and clerks run on thread pools, because the
session runs them one after another and no deployment does.

The server runs in this process, so the one process that may hold the
chip holds everything: role code above ``HOST_PATH_MAX`` dispatches to it.
"""

from __future__ import annotations

import concurrent.futures
import math
import tempfile

import numpy as np


def size_codec(prime: int, participants: int, clip: float):
    """The fixed-point codec as ``fl/scenario.py`` ``_make_codec`` sizes
    it: the aggregation modulus is the largest power of two (at most 2^24)
    with ``participants * m < p``, and the fractional grid is the widest
    that stays exact, at most 16 bits."""
    from sda_tpu.models import FixedPointCodec

    m_bits = min(24, (prime // max(2, participants)).bit_length() - 1)
    modulus = 1 << m_bits
    q_cap = (modulus // 2 - 1) // participants
    fractional_bits = min(16, int(math.floor(math.log2(q_cap / clip))))
    return FixedPointCodec(modulus, fractional_bits, participants, clip=clip)


class Federated:
    #: what open() acquires and close() releases, in order
    tmp = server = proxy = uploaders = chore_pool = None

    def open(self, cell, seed: int) -> None:
        from harness import load_module
        from schemes import packed_shamir
        from sda_tpu import obs
        from sda_tpu.client import SdaClient
        from sda_tpu.crypto import MemoryKeystore
        from sda_tpu.http import SdaAsyncHttpServer, SdaHttpClient
        from sda_tpu.protocol import (Aggregation, AggregationId, FullMasking,
                                      SodiumEncryption)
        from sda_tpu.server import new_sqlite_server

        config, traffic = cell.config, cell.traffic
        scheme = packed_shamir(config)
        n = scheme.share_count
        participants, dim = traffic["participants"], traffic["dim"]
        self.codec = size_codec(scheme.prime_modulus, participants,
                                config["codec"]["clip"])
        self.modulus = self.codec.modulus
        self.new_id = AggregationId.random

        self.tmp = tempfile.TemporaryDirectory(prefix="chipbench-fed-")
        self.server = SdaAsyncHttpServer(
            new_sqlite_server(f"{self.tmp.name}/store.sqlite"),
            bind="127.0.0.1:0")
        self.server.start_background()
        self.proxy = SdaHttpClient(self.server.address, token="chipbench",
                                   codec=config["wire_codec"])

        def new_client(with_key: bool):
            keystore = MemoryKeystore()
            client = SdaClient(SdaClient.new_agent(keystore), keystore, self.proxy)
            client.upload_agent()
            key = None
            if with_key:
                key = client.new_encryption_key()
                client.upload_encryption_key(key)
            return client, key

        self.recipient, recipient_key = new_client(True)
        self.clerks = [new_client(True)[0] for _ in range(n)]
        self.devices = [new_client(False)[0] for _ in range(participants)]
        self.template = Aggregation(
            id=self.new_id(), title="chipbench", vector_dimension=dim,
            modulus=self.modulus, recipient=self.recipient.agent.id,
            recipient_key=recipient_key,
            masking_scheme=FullMasking(self.modulus),
            committee_sharing_scheme=scheme,
            recipient_encryption_scheme=SodiumEncryption(),
            committee_encryption_scheme=SodiumEncryption())

        # a fixed population of update vectors from the seed, inside the
        # codec's range; every round sums the same vectors under fresh
        # masks, shares and keys
        rng = np.random.default_rng(seed)
        self.vectors = np.clip(
            rng.normal(0.0, traffic["value_sigma"], size=(participants, dim)),
            -self.codec.clip, self.codec.clip)
        reference = load_module(cell.home, "references", config["reference"])
        self.expected = reference.on_host(
            np.stack([self.codec.encode(v) for v in self.vectors]), self.modulus)

        self.uploaders = concurrent.futures.ThreadPoolExecutor(
            traffic["upload_threads"], thread_name_prefix="upload")
        self.chore_pool = concurrent.futures.ThreadPoolExecutor(
            n + 1, thread_name_prefix="clerk")
        self.span = obs.span
        self.lost = 0
        self.inexact = 0
        self.revealed = None

        self.facts = {"participants": participants, "dim": dim,
                      "elements_per_round": participants * dim,
                      "aggregation_modulus": self.modulus,
                      "fractional_bits": self.codec.fractional_bits,
                      "clerks": n}
        self.round(-1)  # warms every role-code program of this shape
        self.verify(-1)
        if self.inexact or self.lost:
            raise RuntimeError("the warm-up round did not reveal the plain sum")

    def round(self, _index: int) -> None:
        aggregation = self.template.replace(id=self.new_id())
        self.recipient.upload_aggregation(aggregation)
        self.recipient.begin_aggregation(aggregation.id)

        with self.span("codec.encode"):  # the codec has no span of its own
            encoded = [self.codec.encode(v) for v in self.vectors]

        uploads = [self.uploaders.submit(device.participate, e, aggregation.id)
                   for device, e in zip(self.devices, encoded)]
        for upload in uploads:  # returning is the acknowledgement
            upload.result()
        self.acknowledged = len(uploads)

        self.recipient.end_aggregation(aggregation.id)
        chores = [self.chore_pool.submit(agent.run_chores, -1)
                  for agent in (self.recipient, *self.clerks)]
        for chore in chores:
            chore.result()
        self.revealed = self.recipient.await_result(
            aggregation.id, deadline=120.0, poll_interval=0.05)

    def verify(self, _index: int) -> None:
        values = np.mod(self.revealed.values, self.modulus)
        self.inexact += int(not np.array_equal(values, self.expected))
        # every acknowledged participation is in the frozen snapshot
        self.lost += int(self.revealed.participations != self.acknowledged)

    def finish(self) -> int:
        """Rounds that revealed a wrong sum or lost an acknowledged upload."""
        return self.inexact + self.lost

    def close(self) -> None:
        for pool in (self.uploaders, self.chore_pool):
            if pool is not None:
                pool.shutdown(wait=True)
        if self.proxy is not None:
            self.proxy.close()
        if self.server is not None:
            self.server.shutdown()
        if self.tmp is not None:
            self.tmp.cleanup()


def setup(cell, seed: int, _devices, _rehearsal: bool) -> Federated:
    state = Federated()
    try:
        state.open(cell, seed)
    except BaseException:
        state.close()
        raise
    return state
