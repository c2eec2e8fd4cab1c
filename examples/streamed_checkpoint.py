"""A streamed round that dies mid-stream and resumes bit-identically.

StreamingAggregator processes a vector too large to hold per-participant
in memory, in (participant-chunk x dim-chunk) tiles with constant device
footprint, checkpointing an atomic fsync'd snapshot as it goes. This demo
injects a failure partway through the stream, then resumes from the
snapshot and proves the result equals an uninterrupted run exactly.

    JAX_PLATFORMS=cpu python examples/streamed_checkpoint.py
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

import numpy as np

from sda_tpu.mesh import StreamingAggregator, synthetic_block_provider32
from sda_tpu.protocol import FullMasking, PackedShamirSharing

P_TOTAL, DIM = 96, 30_000
scheme = PackedShamirSharing(3, 8, 4, 433, 354, 150)


def make_agg():
    return StreamingAggregator(scheme, FullMasking(433),
                               participants_chunk=16, dim_chunk=7_500)


provider = synthetic_block_provider32(433, seed=42, max_value=433)
key = jax.random.PRNGKey(0)

with tempfile.TemporaryDirectory() as tmp:
    ck = f"{tmp}/round.ckpt"

    # a provider that dies after a few chunks, like a preempted host mid-round
    calls = {"n": 0}

    def flaky(p0, p1, d0, d1):
        calls["n"] += 1
        if calls["n"] > 5:
            raise RuntimeError("injected failure (stream died)")
        return provider(p0, p1, d0, d1)

    try:
        make_agg().aggregate_blocks(flaky, P_TOTAL, DIM, key,
                                    checkpoint_path=ck,
                                    checkpoint_every_chunks=2)
    except RuntimeError as e:
        print(f"round died mid-stream as injected: {e}")

    # resume from the snapshot: only the remaining tiles are streamed
    resumed = {"n": 0}

    def counting(p0, p1, d0, d1):
        resumed["n"] += 1
        return provider(p0, p1, d0, d1)

    out = make_agg().aggregate_blocks(counting, P_TOTAL, DIM, key,
                                      checkpoint_path=ck,
                                      checkpoint_every_chunks=2)
    print(f"resumed run streamed {resumed['n']} blocks "
          f"(a fresh run would stream {(P_TOTAL // 16) * (DIM // 7500)})")

fresh = make_agg().aggregate(
    provider(0, P_TOTAL, 0, DIM).astype(np.int64), key)
assert np.array_equal(out, fresh), "resume must be bit-identical"
print("resumed aggregate == uninterrupted aggregate: OK (bit-identical)")
