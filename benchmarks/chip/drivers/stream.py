"""Driver ``stream``: the secure-sum round of a cohort larger than one
transfer, streamed through one chip block by block
(``sda_tpu.mesh.streaming.StreamingAggregator``), one round at a time.

The inputs are one int64 NumPy matrix in host memory, made on the host
from the seed: nothing of the set-up stands on the device, so the memory
peak is a round's. A round is ``agg.aggregate(inputs, key)``, which
returns when the aggregate is a NumPy array: per block of
``participants_chunk`` rows the transfer and one accumulate step, then
one reconstruction and the read-back. Every round is checked against the
blocked plain sum (``references/modsum_blocks.py``) between rounds.

The driver builds what its configuration states and refuses a file, or a
program, it cannot hold to it: the configuration's fourth guarantee bounds
the blocks in flight, so a program that states no such bound
(``streaming.BLOCKS_IN_FLIGHT``) is refused before anything is built.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

#: rows a host thread generates, and rows the reference sums, at a time
HOST_ROWS = 100


def build_aggregator(config: dict, participants_chunk: int | None = None,
                     interpret: bool = False):
    """The configuration's ``StreamingAggregator``: what a user writes,
    and nothing else. ``participants_chunk`` replaces the file's only for
    a rehearsal's toy shape."""
    import jax
    import jax.numpy as jnp

    from schemes import packed_shamir
    from sda_tpu.mesh import streaming
    from sda_tpu.protocol import FullMasking

    scheme = packed_shamir(config)
    if config["masking"] != "full":
        raise ValueError("driver 'stream' runs full masking; the "
                         f"configuration states {config['masking']!r}")
    if config["dim_chunk"] != "default":
        raise ValueError("driver 'stream' leaves dim_chunk at the library's "
                         f"default; the configuration states {config['dim_chunk']!r}")
    if config["layout"] != "1 chip, no mesh":
        raise ValueError("driver 'stream' runs one chip without a mesh; the "
                         f"configuration states layout {config['layout']!r}")
    bound = getattr(streaming, "BLOCKS_IN_FLIGHT", None)
    if bound != config["blocks_in_flight"]:
        raise ValueError(
            f"the configuration guarantees {config['blocks_in_flight']} blocks "
            f"in flight; the program's streaming.BLOCKS_IN_FLIGHT is {bound}")
    interpreted = {}
    if interpret:  # no Mosaic and no on-core PRNG off the chip
        interpreted = dict(
            pallas_interpret=True,
            pallas_external_bits_fn=lambda key, rows, draws, columns:
                jax.random.bits(key, (rows, 2 * draws, columns), jnp.uint32))
    agg = streaming.StreamingAggregator(
        scheme, FullMasking(scheme.prime_modulus),
        participants_chunk=participants_chunk or config["participants_chunk"],
        use_pallas=config["use_pallas"], **interpreted)
    if agg.pallas_active != config["use_pallas"]:
        raise RuntimeError("the aggregator did not take the configured kernel path")
    return agg


def host_inputs(seed: int, participants: int, dim: int, value_bits: int) -> np.ndarray:
    """``[participants, dim]`` int64 in ``[0, 2^value_bits)`` from ``seed``,
    made on the host ``HOST_ROWS`` rows a thread: the same seed gives the
    same matrix whatever the number of threads."""
    inputs = np.empty((participants, dim), dtype=np.int64)
    starts = range(0, participants, HOST_ROWS)
    streams = np.random.SeedSequence([seed, 0x1A7A]).spawn(len(starts))

    def fill(job):
        p0, stream = job
        rows = inputs[p0:p0 + HOST_ROWS]
        rows[...] = np.random.default_rng(stream).integers(
            0, 1 << value_bits, size=rows.shape, dtype=np.int64)

    with ThreadPoolExecutor(max_workers=os.cpu_count()) as pool:
        list(pool.map(fill, zip(starts, streams)))
    return inputs


class Stream:
    def __init__(self, cell, seed: int, devices, rehearsal: bool):
        import jax

        config, traffic = cell.config, cell.traffic
        if traffic["input"] != "host":
            raise ValueError("driver 'stream' streams a host matrix; the "
                             f"traffic states input {traffic['input']!r}")
        if len(devices) != 1:
            raise ValueError("driver 'stream' runs one chip")
        self.agg = build_aggregator(
            config, traffic.get("participants_chunk") if rehearsal else None,
            interpret=rehearsal)
        participants, dim = traffic["participants"], traffic["dim"]
        self.modulus = self.agg.modulus
        self.key = jax.random.PRNGKey(seed)
        self.fold_in = jax.random.fold_in

        self.inputs = host_inputs(seed, participants, dim, traffic["value_bits"])
        self.expected = cell_reference(cell).on_host_blocks(
            lambda p0, p1, d0, d1: self.inputs[p0:p1, d0:d1],
            participants, dim, self.modulus, HOST_ROWS)
        self.out = None
        self.inexact = 0
        chunk = self.agg.participants_chunk
        self.facts = {
            "participants": participants, "dim": dim,
            "elements_per_round": participants * dim,
            "input_itemsize": self.inputs.itemsize,
            "bytes_per_round": self.inputs.nbytes,
            "participants_chunk": chunk, "dim_chunk": self.agg.dim_chunk,
            "blocks_per_round": -(-participants // chunk) * -(-dim // self.agg.dim_chunk),
            "blocks_in_flight": config["blocks_in_flight"],
            "secret_count": self.agg.scheme.secret_count,
            "share_count": self.agg.scheme.share_count,
            "pallas_active": self.agg.pallas_active,
        }
        # warm every block shape of the round (compiles or loads from the
        # cache), and hold the warm-up round to the reference before any
        # round is timed
        self.round(-1)
        self.verify(-1)
        if self.finish():
            raise RuntimeError("the warm-up round did not reveal the plain sum")

    def round(self, index: int) -> None:
        key = self.fold_in(self.key, index + 1)  # a fresh key every round
        self.out = self.agg.aggregate(self.inputs, key)

    def verify(self, _index: int) -> None:
        self.inexact += int(not np.array_equal(self.out, self.expected))

    def finish(self) -> int:
        """Rounds that did not reveal the plain sum."""
        return self.inexact

    def close(self) -> None:
        self.inputs = self.expected = self.out = None


def cell_reference(cell):
    from harness import load_module

    return load_module(cell.home, "references", cell.config["reference"])


def setup(cell, seed: int, devices, rehearsal: bool) -> Stream:
    import jax

    # stream.acc_s_per_round reads the step's named scope off the
    # executable's op metadata, which JAX's persistent-cache key leaves out
    # by default: a step compiled by a program with other scopes would be
    # loaded in place of this program's (drivers/pod_additive.py; my chip
    # run, PR 29). With the metadata in the key each program runs its own.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return Stream(cell, seed, devices, rehearsal)
