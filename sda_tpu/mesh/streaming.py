"""Streamed secure-aggregation rounds for workloads larger than HBM.

SURVEY.md §7 hard part (f): the flagship configs (10k participants x
10M-dim vectors) cannot materialize [P, d] on one chip, let alone the
[P, n, B] share tensor. But the whole pipeline is a sum over participants
of per-participant shares, so it streams: tile the participant axis and
the dimension axis, push each [P_chunk, d_chunk] block through
mask -> share -> local combine on device, and fold it into running
[n, B_chunk] share and [d_chunk] mask accumulators. Peak memory is one
step's working set plus the block in transfer behind it, independent of P
(``BLOCKS_IN_FLIGHT``). Per dim-tile, reconstruction and unmasking run once
at the end.

Two drivers share that structure:

- ``StreamingAggregator`` — single chip.
- ``StreamedPod`` — the streamed x multi-chip composition (round-1 verdict:
  neither mode alone reached the 10k x 10M flagship). Blocks are sharded
  over the SimulatedPod ('p', 'd') mesh and every tile step is
  COLLECTIVE-FREE: each device folds its local share/mask sums into
  device-local accumulators, and the psum_scatter clerk transpose +
  all_gather + reconstruct run ONCE per dim tile at the end — ICI traffic
  is independent of the participant count.

The reference reaches the same scale by chunking vectors into
secret_count-sized batches and streaming participations through the server
one HTTP upload at a time (client/src/crypto/sharing/batched.rs:18-53,
server/src/snapshot.rs); here the chunk loop is a host-side driver around
jitted device steps (at most two compiled shapes per axis: full chunk and
remainder), with the uint32 Solinas fast path when the prime qualifies.
"""

from __future__ import annotations

import collections
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs import devprof
from ..protocol import LinearMaskingScheme, NoMasking
from ..utils import metrics, timed_phase
from .simpod import (
    _dim_grain,
    _Planned,
    _RoundPlan,
    _shard_map,
    _tile_key,
    make_mesh,
    refuse_reported,
)

#: get_block(p0, p1, d0, d1) -> integer array [p1-p0, d1-d0]
BlockProvider = Callable[[int, int, int, int], np.ndarray]

#: Blocks the tile loop keeps live on the device: the one its step reads
#: and the one in transfer behind it. Derived, not a parameter: one would
#: serialize transfer and step, and a third buys nothing -- a block's
#: transfer already runs under the step before it, and a second transfer
#: in flight only queues behind the first (on the v5e, 4 GiB or more of
#: outstanding transfers fall from 10 GB/s to 0.4: PERF.md, PR 33).
BLOCKS_IN_FLIGHT = 2


def array_block_provider(inputs) -> BlockProvider:
    """Adapt an in-memory (or np.memmap) [P, d] array to a BlockProvider."""

    def get_block(p0, p1, d0, d1):
        return inputs[p0:p1, d0:d1]

    return get_block


def _hash32(rows, cols, seed, xp):
    """Deterministic uint32 hash of absolute (participant, component)
    coordinates — one formula, two backends (numpy and jnp), bit-identical.
    Pure 32-bit ops only so the device path never needs emulated 64-bit
    multiplies on TPU."""
    u = (lambda v: xp.uint32(v))
    x = rows * u(0x9E3779B1) ^ cols * u(0x85EBCA77) ^ u(seed)
    x = x ^ (x >> u(16))
    x = x * u(0x7FEB352D)
    x = x ^ (x >> u(15))
    x = x * u(0x846CA68B)
    x = x ^ (x >> u(16))
    return x


def synthetic_block_provider32(
    modulus: int, seed: int = 0, max_value: Optional[int] = None
) -> BlockProvider:
    """Host (numpy) uint32 coordinate-hash blocks: ~10x faster than the
    splitmix64 provider, and bit-identical to the device generator below —
    the e2e streamed benches verify sampled device results against host
    column sums of the same virtual matrix."""
    bound_i = int(max_value if max_value is not None else modulus)
    if not 0 < bound_i <= 0xFFFFFFFF:
        raise ValueError("synthetic32 values must fit uint32")
    bound = np.uint32(bound_i)
    sd = np.uint32((seed ^ 0x5851F42D) & 0xFFFFFFFF)

    def get_block(p0, p1, d0, d1):
        with np.errstate(over="ignore"):
            rows = np.arange(p0, p1, dtype=np.uint32)[:, None]
            cols = np.arange(d0, d1, dtype=np.uint32)[None, :]
            return _hash32(rows, cols, sd, np) % bound

    return get_block


def synthetic_device_block_provider32(
    modulus: int, seed: int = 0, max_value: Optional[int] = None
) -> BlockProvider:
    """Device (jnp) twin of :func:`synthetic_block_provider32`: generates
    each block on the accelerator from its absolute coordinates, so
    flagship-scale end-to-end runs are not bottlenecked by host hashing or
    host->device bandwidth. Same virtual matrix, bit-identical values —
    exactness checks compare device aggregates against host-generated
    column sums. Benchmarks that use it label the record
    ``device_generated_inputs: true``; the host-fed path is measured
    separately."""
    bound = int(max_value if max_value is not None else modulus)
    if not 0 < bound <= 0xFFFFFFFF:
        raise ValueError("synthetic32 values must fit uint32")
    sd = (seed ^ 0x5851F42D) & 0xFFFFFFFF

    import functools

    # only the SHAPE is static: tile offsets are traced operands, so the
    # generator compiles once per block shape (2-3 shapes per run), not
    # once per tile — a flagship run has hundreds of distinct offsets and
    # per-tile retraces would feed serial compile time into the timed span
    @functools.partial(jax.jit, static_argnames=("rows", "cols"))
    def gen(p0, d0, *, rows, cols):
        r = p0 + jnp.arange(rows, dtype=jnp.uint32)[:, None]
        c = d0 + jnp.arange(cols, dtype=jnp.uint32)[None, :]
        return _hash32(r, c, jnp.uint32(sd), jnp) % jnp.uint32(bound)

    def get_block(p0, p1, d0, d1):
        return gen(jnp.uint32(p0), jnp.uint32(d0),
                   rows=int(p1 - p0), cols=int(d1 - d0))

    return get_block


def synthetic_block_provider(
    modulus: int, seed: int = 0, max_value: Optional[int] = None
) -> BlockProvider:
    """Deterministic pseudo-random blocks without materializing [P, d] —
    benchmark-scale inputs. Each element is a splitmix64-style hash of its
    absolute (participant, component) coordinates, so every tiling reads
    the same virtual matrix."""
    bound = np.uint64(max_value if max_value is not None else modulus)
    s = np.uint64(seed * 0x9E3779B97F4A7C15 & 0xFFFFFFFFFFFFFFFF)

    def _mix(z):
        z = (z + np.uint64(0x9E3779B97F4A7C15)) & np.uint64(0xFFFFFFFFFFFFFFFF)
        z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        return z ^ (z >> np.uint64(31))

    # uint32 blocks when values fit: half the host->device bytes, and the
    # device residue pass skips emulated 64-bit ops (fastfield.to_residues32)
    out_dtype = np.uint32 if int(bound) <= (1 << 32) else np.int64

    def get_block(p0, p1, d0, d1):
        with np.errstate(over="ignore"):
            rows = _mix(np.arange(p0, p1, dtype=np.uint64)[:, None] + s)
            cols = _mix(np.arange(d0, d1, dtype=np.uint64)[None, :] ^ s)
            vals = _mix(rows ^ cols)
        return (vals % bound).astype(out_dtype)

    return get_block


def _atomic_npz(path, **arrays):
    """Atomic, crash-durable npz write: temp file, fsync, rename, dir
    fsync — the durability primitive under every streamed snapshot."""
    import os
    import tempfile

    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path) or ".",
                               suffix=".tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            np.savez(f, **arrays)
            # data must reach stable storage BEFORE the rename lands, or a
            # power loss leaves a truncated snapshot at the destination
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        # ... and the rename itself must reach the journal: fsync the
        # containing directory, else a crash can roll back to the prior
        # snapshot (harmless to correctness, but the durability claim
        # would be false)
        try:
            dfd = os.open(os.path.dirname(path) or ".", os.O_RDONLY)
            try:
                os.fsync(dfd)
            finally:
                os.close(dfd)
        except OSError:
            pass  # platform without directory fsync
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def _snapshot_header(fingerprint, out, done_dims, di, pi):
    """The cursor/prefix fields every streamed snapshot carries — ONE
    definition shared by the single-process and multihost checkpointers
    so the formats cannot drift."""
    return {
        "fingerprint": np.frombuffer(fingerprint.encode(), dtype=np.uint8),
        "out": out[:done_dims],
        "done_dims": np.int64(done_dims),
        "di": np.int64(di),
        "pi": np.int64(pi),
    }


def _read_snapshot(path, fingerprint, keys=None):
    """Fingerprint-guarded snapshot read; ``keys=None`` loads every entry,
    a key list loads only those (npz members load lazily, so a cursor-only
    probe does not materialize accumulator payloads). Returns None for a
    missing/foreign/corrupt snapshot — never trusts one."""
    import os
    import zipfile

    if not os.path.exists(path):
        return None
    try:
        with np.load(path) as z:
            if bytes(z["fingerprint"]).decode() != fingerprint:
                return None  # different round/config: start fresh
            return {k: z[k] for k in (keys if keys is not None else z.files)}
    except (OSError, ValueError, KeyError, EOFError, zipfile.BadZipFile):
        return None  # unreadable/truncated snapshot: start fresh


def _checkpoint_load(path, fingerprint):
    return _read_snapshot(path, fingerprint,
                          keys=("out", "done_dims", "di", "pi",
                                "acc_shares", "acc_mask"))


class _FileCheckpointer:
    """Single-process snapshot/resume (the original streamed contract):
    one atomic npz at ``path``, fingerprint-guarded, removed on
    completion. ``restore_accs`` re-places loaded host accumulators
    (identity/`jnp.asarray` single-chip; mesh re-placement for pods)."""

    def __init__(self, path, fingerprint, restore_accs=None):
        self.path = path
        self.fingerprint = fingerprint
        self.restore_accs = restore_accs or (
            lambda aS, aM: (jnp.asarray(aS), jnp.asarray(aM)))

    def load(self):
        return _checkpoint_load(self.path, self.fingerprint)

    def restore(self, resume):
        return self.restore_accs(resume["acc_shares"], resume["acc_mask"])

    def save(self, out, done_dims, di, pi, acc_shares, acc_mask):
        _atomic_npz(
            self.path,
            **_snapshot_header(self.fingerprint, out, done_dims, di, pi),
            acc_shares=np.asarray(acc_shares),
            acc_mask=np.asarray(acc_mask),
        )

    def finish(self):
        import os

        try:
            os.unlink(self.path)
        except OSError:
            pass


def _drive_stream(owner, participants, dimension, key, **tile_loop):
    """One streamed round of ``owner`` (StreamingAggregator, StreamedPod
    and, via StreamedPod.drive_tiles, the multihost driver): the tile loop
    of :func:`_drive_tiles` under the root span ``stream.round``.

    Spans: ``stream.round`` (attributes ``participants``, ``dimension``,
    ``participants_chunk``, ``dim_chunk``, ``tiles``) over, per block,
    ``stream.feed`` (attributes ``bytes``, ``dtype``, ``shape``: the wait
    for the block before to land, then the hand-over of this one) and
    ``stream.dispatch``; ``stream.steps_sync`` wherever the loop waits for
    a step; per dim tile ``stream.finale`` and ``stream.readback``;
    ``stream.checkpoint`` where a snapshot is written. Counters at the
    same boundaries: ``mesh.stream.{rounds,blocks,bytes}``
    (docs/observability.md)."""
    pc, dc = owner.participants_chunk, owner.dim_chunk
    metrics.count("mesh.stream.rounds")
    with timed_phase("stream.round") as root:
        root.attributes.update(
            participants=int(participants), dimension=int(dimension),
            participants_chunk=pc, dim_chunk=dc,
            tiles=-(-participants // pc) * -(-dimension // dc))
        return _drive_tiles(owner, participants, dimension, key, **tile_loop)


def _drive_tiles(owner, participants, dimension, key, *, make_block,
                 make_accs, fetch, checkpoint_path=None,
                 checkpoint_every_chunks=16, restore_accs=None,
                 checkpointer=None):
    """THE streamed tile loop — one definition of the tile/key derivation
    and of the checkpoint/resume state machine. d-tiles outer, participant
    tiles inner, one accumulate step per tile, one finale per d-tile;
    snapshots every ``checkpoint_every_chunks`` chunks (0 = boundaries
    only) and at every d-tile boundary, removed on completion. Mask
    windows and share randomness depend on the tile indexing here — any
    change breaks resume bit-identity.

    Back-pressure: transfers and steps are asynchronous and a block is
    live from its transfer until its step has run, so before block ``i``
    is made the loop waits for step ``i - BLOCKS_IN_FLIGHT`` -- on the
    scalar every step returns beside its accumulators, which are donated
    to the next step and cannot be waited on once that is dispatched --
    and for block ``i - 1`` to have landed. At most ``BLOCKS_IN_FLIGHT``
    blocks of the loop's are on the device and one of them is in transfer,
    whatever the number of chunks; block ``i`` still lands under step
    ``i - 1``.
    """
    if key is None:
        from ..crypto.core import fresh_prng_key

        key = fresh_prng_key()
    pc, dc = owner.participants_chunk, owner.dim_chunk
    out = np.empty(dimension, dtype=np.int64)
    resume = None
    if checkpoint_path is not None and checkpointer is None:
        if jax.process_count() > 1:
            raise ValueError(
                "checkpoint_path is the single-process snapshot; for "
                "multihost rounds pass checkpoint_path to "
                "multihost.streamed_aggregate_process_local, which builds "
                "the per-process coordinated checkpointer"
            )
        checkpointer = _FileCheckpointer(
            checkpoint_path,
            owner._checkpoint_fingerprint(participants, dimension, key),
            restore_accs,
        )
    if checkpointer is not None:
        resume = checkpointer.load()
        if resume is not None:
            out[: int(resume["done_dims"])] = resume["out"]
    # ground truth for callers recording resumed runs (e.g. benches)
    owner.last_resumed = resume is not None
    resume_di = int(resume["di"]) if resume is not None else -1
    resume_pi = int(resume["pi"]) if resume is not None else 0
    empty = np.zeros((0,), owner._field.dtype)
    # uniform_tail: one step/finale shape for every tile — tails on BOTH
    # axes pad to the full chunk (dc is already grain-rounded); otherwise
    # the dim tail pads only to the grain and the participant tail keeps
    # its ragged (separately compiled) shape. Single-tile axes stay at
    # their natural size — there is no second shape to avoid
    uniform = bool(getattr(owner, "uniform_tail", False))
    uniform_d = uniform and dimension > dc
    uniform_p = uniform and participants > pc
    for di, d0 in enumerate(range(0, dimension, dc)):
        d1 = min(d0 + dc, dimension)
        d_size = dc if uniform_d else (
            -(-(d1 - d0) // owner._grain) * owner._grain)  # pad to grain
        if resume is not None and di < resume_di:
            continue  # completed tile: out prefix already restored
        if resume is not None and di == resume_di and resume_pi > 0:
            acc_shares, acc_mask = checkpointer.restore(resume)
            start_pi = resume_pi
        else:
            acc_shares, acc_mask = make_accs(d_size)
            start_pi = 0
        in_flight = collections.deque()  # the dispatched steps' scalars
        block = None
        for pi, p0 in enumerate(range(0, participants, pc)):
            if pi < start_pi:
                continue  # chunk already folded into the snapshot accs
            p1 = min(p0 + pc, participants)
            if len(in_flight) == BLOCKS_IN_FLIGHT:
                with timed_phase("stream.steps_sync"):
                    jax.block_until_ready(in_flight.popleft())
            with timed_phase("stream.feed") as feed:
                jax.block_until_ready(block)  # one transfer at a time
                block = make_block(p0, p1, d0, d1, d_size)
                if uniform_p and block.shape[0] < pc:
                    # ragged participant tail: zero rows aggregate as
                    # zero and their masks cancel within the tile, same
                    # argument as the zero columns
                    block = jnp.pad(
                        jnp.asarray(block),
                        ((0, pc - block.shape[0]), (0, 0)))
                metrics.count("mesh.stream.blocks")
                metrics.count("mesh.stream.bytes", block.nbytes)
                feed.attributes.update(bytes=block.nbytes,
                                       dtype=str(block.dtype),
                                       shape=list(block.shape))
            step = owner._steps.get(block.shape)
            if step is None:
                step = owner._steps[block.shape] = owner._step_fn(block.shape)
            with timed_phase("stream.dispatch"):
                acc_shares, acc_mask, done = step(
                    block, _tile_key(key, pi, di), key,
                    jnp.int32(p0), jnp.int32(d0 // 8),
                    acc_shares, acc_mask,
                )
            in_flight.append(done)
            if (checkpointer is not None
                    and checkpoint_every_chunks > 0
                    and (pi + 1) % checkpoint_every_chunks == 0):
                with timed_phase("stream.checkpoint"):
                    checkpointer.save(out, d0, di, pi + 1,
                                      acc_shares, acc_mask)
        # sync before the finale so stream.finale times the reconstruct
        # (for pods: psum_scatter + all_gather + reconstruct) alone, not
        # the queued accumulate backlog
        with timed_phase("stream.steps_sync"):
            jax.block_until_ready(acc_shares)
        final = owner._finals.get(d_size)
        if final is None:
            final = owner._finals[d_size] = owner._final_fn(d_size)
        with timed_phase("stream.finale"):
            total = jax.block_until_ready(final(acc_shares, acc_mask))
        with timed_phase("stream.readback"):
            out[d0:d1] = fetch(total)[: d1 - d0]
        if checkpointer is not None:
            with timed_phase("stream.checkpoint"):
                checkpointer.save(out, d1, di + 1, 0, empty, empty)
    if checkpointer is not None:
        checkpointer.finish()  # round complete
    return out


def _round_fingerprint(scheme, masking, participants, dimension, pc, dc,
                       pallas, survivors, key, extra=None):
    """sha256 over everything that determines a streamed round's bytes."""
    import hashlib

    from ..protocol.helpers import canonical_json

    payload = {
        "scheme": scheme.to_obj(),
        "masking": masking.to_obj(),
        "participants": int(participants),
        "dimension": int(dimension),
        "participants_chunk": int(pc),
        "dim_chunk": int(dc),
        "pallas": bool(pallas),
        "survivors": survivors,
        "key": np.asarray(
            jax.random.key_data(key) if jnp.issubdtype(
                getattr(key, "dtype", None), jax.dtypes.prng_key)
            else key).tolist(),
        **(extra or {}),
    }
    return hashlib.sha256(canonical_json(payload)).hexdigest()


class StreamingAggregator(_Planned):
    """Chunked single-chip rounds: fixed device memory for any P and d.

    Full scheme-lattice coverage like the pod modes: Packed-Shamir OR
    additive sharing x none/full/chacha masking — ChaCha seed masks are
    expanded on device per tile at the tile's (participant, dim) offset,
    so every tiling of the same round key sees the same masks.

    The memory bound, as kept: one step's working set (a block, its
    residue temporaries, the [n, B] and [d] accumulators) plus the block
    in transfer behind it — ``BLOCKS_IN_FLIGHT`` = 2 blocks of the tile
    loop's on the device at most, one of them in transfer, whatever the
    number of chunks (``_drive_tiles`` waits for the step two blocks back
    and for the block before to land before it makes a block). A provider
    that returns device arrays holds its own.
    """

    def __init__(
        self,
        sharing_scheme,
        masking_scheme: Optional[LinearMaskingScheme] = None,
        participants_chunk: int = 64,
        dim_chunk: int = 3 * (1 << 20),
        use_pallas: bool = False,
        pallas_interpret: bool = False,
        pallas_external_bits_fn=None,
        surviving_clerks=None,
        uniform_tail: bool = False,
    ):
        # the steps are jitted for the default device
        self._plan = _RoundPlan(sharing_scheme, masking_scheme, make_mesh(1, 1),
                                use_pallas, surviving_clerks, pallas_interpret,
                                pallas_external_bits_fn)
        # ChaCha seed masks expand a window of one per-participant stream at
        # each tile's dim offset, so tiles align to the 8-word block grain
        self._grain = _dim_grain(self.scheme, self.masking)
        self.participants_chunk = int(participants_chunk)
        self.dim_chunk = -(-int(dim_chunk) // self._grain) * self._grain
        # uniform_tail pads the LAST dim tile to the full dim_chunk width
        # (zero columns aggregate as zero; per-tile masks cancel), so every
        # tile shares ONE compiled step/finale shape — the tail shapes'
        # extra compiles cost more than the padded columns' compute when
        # dim_chunk ~ dim/ntiles. Exactness
        # pinned in tests/test_streaming.py (uniform-tail block).
        self.uniform_tail = bool(uniform_tail)
        self._steps = {}      # block shape -> jitted accumulate step
        self._finals = {}     # dim size -> jitted reconstruct+unmask

    # -- jitted pieces ---------------------------------------------------
    def _step_fn(self, block_shape):
        plan = self._plan
        f = plan.field

        def step(block, key, round_key, pid0, dblk0, acc_shares, acc_mask):
            x = f.to_residues(block)
            # pid0/dblk0 (traced) locate this tile in the global stream so
            # ChaCha seed masks expand the right window of each
            # participant's stream regardless of tiling
            shares, mask_sum = plan.local_step(
                x, key, round_key=round_key, pid_base=pid0, d_block0=dblk0)
            with jax.named_scope("sda.stream.acc"):
                acc_shares = f.add(acc_shares, shares)
                if mask_sum is not None:
                    acc_mask = f.add(acc_mask, mask_sum)
                # the undonated scalar _drive_tiles waits on
                return acc_shares, acc_mask, acc_shares[0, 0]

        # one "stream.step" profile for every block shape: the compiled-
        # shape registry is how the "at most 2-3 shapes per axis" claim
        # stays a tested property instead of a docstring
        return devprof.instrument("stream.step",
                                  jax.jit(step, donate_argnums=(5, 6)))

    def _final_fn(self, d_size):
        plan = self._plan
        masked = not isinstance(self.masking, NoMasking)

        def final(acc_shares, acc_mask):
            return plan.finale(acc_shares, acc_mask if masked else None,
                               d_size, collective=False)

        return devprof.instrument("stream.finale",
                                  jax.jit(final, donate_argnums=(0, 1)))

    # -- checkpoint/resume -----------------------------------------------
    # The reference is durable-by-construction (every protocol object is a
    # store row the moment it exists, SURVEY §5.4); a flagship streamed
    # round is minutes of accumulate steps, so the TPU-native mode gets
    # the same property: the driver can persist (completed output prefix,
    # in-flight accumulators, tile cursor) and resume mid-round. Tile keys
    # are a pure function of (round key, tile indices), so a resumed run
    # draws identical masks/shares and the result is bit-identical to an
    # uninterrupted one.

    def _checkpoint_fingerprint(self, participants, dimension, key):
        return _round_fingerprint(
            self.scheme, self.masking, participants, dimension,
            self.participants_chunk, self.dim_chunk, self.pallas_active,
            self.surviving_clerks, key,
            # tail padding changes accumulator shapes mid-round, so a
            # snapshot must never cross the setting (included only when
            # set: existing False-mode snapshots keep their fingerprint)
            extra={"uniform_tail": True} if self.uniform_tail else None,
        )

    # back-compat alias for the module-level snapshot loader
    _checkpoint_load = staticmethod(_checkpoint_load)

    # -- driver ----------------------------------------------------------
    def aggregate_blocks(
        self, get_block: BlockProvider, participants: int, dimension: int,
        key=None, *, checkpoint_path: Optional[str] = None,
        checkpoint_every_chunks: int = 16,
    ) -> np.ndarray:
        """Stream all blocks; returns the [dimension] aggregate (host array).

        ``checkpoint_path``: persist an atomic, fsync'd resume snapshot
        there every ``checkpoint_every_chunks`` participant chunks (0 =
        only at dim-tile boundaries) and at every dim-tile boundary; an
        existing snapshot for the identical round (scheme, shape,
        chunking, key — sha256 fingerprint) resumes where it left off,
        bit-identically. A snapshot from a different round, or a damaged
        one, is ignored, never trusted.
        """
        s = self.scheme
        acc_dtype = self._field.dtype

        def make_block(p0, p1, d0, d1, d_size):
            raw = get_block(p0, p1, d0, d1)
            real = d1 - d0
            if isinstance(raw, jax.Array):
                # device-generated block: pad on device, no host hop
                return (raw if d_size == real else
                        jnp.pad(raw, ((0, 0), (0, d_size - real))))
            host = np.asarray(raw)
            if d_size != real:  # zero columns sum to zero
                padded = np.zeros((host.shape[0], d_size), dtype=host.dtype)
                padded[:, :real] = host
                host = padded
            return jnp.asarray(host)

        def make_accs(d_size):
            B = d_size // s.input_size
            return (jnp.zeros((s.output_size, B), acc_dtype),
                    jnp.zeros((d_size,), acc_dtype))

        return _drive_stream(
            self, participants, dimension, key,
            make_block=make_block, make_accs=make_accs, fetch=np.asarray,
            checkpoint_path=checkpoint_path,
            checkpoint_every_chunks=checkpoint_every_chunks,
        )

    def aggregate(self, inputs, key=None, reported=None) -> np.ndarray:
        refuse_reported(reported, "StreamingAggregator")
        inputs = np.asarray(inputs)
        return self.aggregate_blocks(
            array_block_provider(inputs), inputs.shape[0], inputs.shape[1], key
        )


class StreamedPod(_Planned):
    """Streamed rounds over a SimulatedPod mesh — the flagship-scale mode.

    Host loop tiles (participants x dim); each tile step is a collective-
    free SPMD program folding device-local [n, B_loc] share and [d_loc]
    mask accumulators; one psum_scatter + all_gather + reconstruct runs per
    dim tile at the end. Covers the full scheme lattice (additive/packed x
    none/full/chacha) via the simpod stage helpers. Peak device memory is
    one block shard plus accumulators — independent of total participants.
    """

    def __init__(
        self,
        sharing_scheme,
        masking_scheme: Optional[LinearMaskingScheme] = None,
        mesh: Optional[Mesh] = None,
        participants_chunk: int = 64,
        dim_chunk: int = 3 * (1 << 20),
        use_pallas: bool = False,
        pallas_interpret: bool = False,
        pallas_external_bits_fn=None,
        surviving_clerks=None,
        uniform_tail: bool = False,
    ):
        self._plan = _RoundPlan(sharing_scheme, masking_scheme, mesh,
                                use_pallas, surviving_clerks, pallas_interpret,
                                pallas_external_bits_fn)
        p_shards, d_shards = self.mesh.devices.shape
        grain = _dim_grain(self.scheme, self.masking) * d_shards
        self._grain = grain
        # round the tile sizes up to the mesh grain
        self.participants_chunk = -(-int(participants_chunk) // p_shards) * p_shards
        self.dim_chunk = -(-int(dim_chunk) // grain) * grain
        # uniform_tail pads the LAST dim tile to the full dim_chunk width
        # (zero columns aggregate as zero; per-tile masks cancel), so every
        # tile shares ONE compiled step/finale shape — and a DIFFERENT tile
        # count (a different model dim at the same tile width) reuses the
        # exact same compiled per-tile program. The model-scale driver
        # (mesh/devscale.py) runs with this on; exactness pinned in
        # tests/test_devscale.py. The participant axis is always uniform
        # here (make_block pads every block to participants_chunk rows).
        self.uniform_tail = bool(uniform_tail)
        self._steps = {}      # local block shape -> jitted accumulate step
        self._finals = {}     # dim-tile size -> jitted collective finale

    # -- jitted pieces ---------------------------------------------------
    def _acc_shapes(self, d_size: int):
        p_shards, _ = self.mesh.devices.shape
        n = self.scheme.output_size
        B = d_size // self.scheme.input_size
        return (p_shards * n, B), (p_shards, d_size)

    def _new_accs(self, d_size: int):
        sharding = NamedSharding(self.mesh, P("p", "d"))
        (sS, sM) = self._acc_shapes(d_size)
        dt = self._field.dtype
        return (
            jax.device_put(jnp.zeros(sS, dt), sharding),
            jax.device_put(jnp.zeros(sM, dt), sharding),
        )

    def _step_fn(self, block_shape):
        plan = self._plan
        f, draws = plan.field, plan.draws

        def local_step(block, tile_key, round_key, tile_base, d_block_base,
                       acc_shares, acc_mask):
            # block [Pc_loc, d_loc]; acc_shares [n, B_loc]; acc_mask [1, d_loc]
            Pc_loc, d_loc = block.shape
            with jax.named_scope(draws):
                pi = jax.lax.axis_index("p")
                di = jax.lax.axis_index("d")
                dev_key = jax.random.fold_in(
                    jax.random.fold_in(tile_key, pi), di)
            x = f.to_residues(block)
            with jax.named_scope(draws):
                pid0 = tile_base + pi * Pc_loc
                dblk0 = d_block_base + di * (d_loc // 8)
            shares, local_mask_sum = plan.local_step(
                x, dev_key, round_key=round_key, pid_base=pid0,
                d_block0=dblk0)
            with jax.named_scope("sda.stream.acc"):
                acc_shares = f.add(acc_shares, shares)
                if local_mask_sum is not None:
                    acc_mask = f.add(acc_mask, local_mask_sum[None, :])
                # the undonated handle _drive_tiles waits on: one element
                # a device, so the wait covers every device and needs no
                # collective
                return acc_shares, acc_mask, acc_shares[:1, :1]

        fn = _shard_map(
            local_step,
            mesh=self.mesh,
            in_specs=(P("p", "d"), P(), P(), P(), P(), P("p", "d"), P("p", "d")),
            out_specs=(P("p", "d"), P("p", "d"), P("p", "d")),
        )
        return devprof.instrument("stream.pod.step",
                                  jax.jit(fn, donate_argnums=(5, 6)))

    def _final_fn(self, d_size: int):
        plan = self._plan
        masked = not isinstance(self.masking, NoMasking)

        def local_final(acc_shares, acc_mask):
            # acc_mask [1, d_loc]: this device's row of the mask sums
            return plan.finale(acc_shares, acc_mask if masked else None,
                               acc_mask.shape[-1])

        fn = _shard_map(
            local_final,
            mesh=self.mesh,
            in_specs=(P("p", "d"), P("p", "d")),
            out_specs=P("d"),
        )
        return devprof.instrument("stream.pod.finale",
                                  jax.jit(fn, donate_argnums=(0, 1)))

    # -- driver ----------------------------------------------------------
    def aggregate_blocks(
        self, get_block: BlockProvider, participants: int, dimension: int,
        key=None, *, checkpoint_path: Optional[str] = None,
        checkpoint_every_chunks: int = 16,
    ) -> np.ndarray:
        """Stream all blocks; returns the [dimension] aggregate (host array).

        ``checkpoint_path``: same atomic snapshot / bit-identical resume
        contract as StreamingAggregator (single-process; the fingerprint
        additionally pins the mesh shape). Loaded accumulators are
        re-placed onto the mesh with the pod's ('p', 'd') sharding.
        """
        sharding = NamedSharding(self.mesh, P("p", "d"))

        def make_block(p0, p1, d0, d1, d_size):
            pc = self.participants_chunk
            raw = get_block(p0, p1, d0, d1)
            if isinstance(raw, jax.Array):
                # device-generated block: pad on device, reshard, no host hop
                if raw.shape != (pc, d_size):
                    raw = jnp.pad(raw, ((0, pc - raw.shape[0]),
                                        (0, d_size - raw.shape[1])))
                return jax.device_put(raw, sharding)
            host = np.asarray(raw)
            if host.shape != (pc, d_size):  # zero-pad the edge tiles
                padded = np.zeros((pc, d_size), dtype=host.dtype)
                padded[: host.shape[0], : host.shape[1]] = host
                host = padded
            # host block straight onto the mesh, shard by shard (no
            # whole-block stop on device 0)
            return jax.device_put(host, sharding)

        def restore_accs(acc_shares_np, acc_mask_np):
            return (
                jax.device_put(acc_shares_np, sharding),
                jax.device_put(acc_mask_np, sharding),
            )

        return self.drive_tiles(
            participants, dimension, key,
            make_block=make_block, make_accs=self._new_accs,
            fetch=np.asarray,
            checkpoint_path=checkpoint_path,
            checkpoint_every_chunks=checkpoint_every_chunks,
            restore_accs=restore_accs,
        )

    def _checkpoint_fingerprint(self, participants, dimension, key):
        # tail padding changes accumulator shapes mid-round, so a snapshot
        # must never cross the uniform_tail setting (included only when
        # set: existing False-mode snapshots keep their fingerprint)
        extra = {"mesh": list(self.mesh.devices.shape)}
        if self.uniform_tail:
            extra["uniform_tail"] = True
        return _round_fingerprint(
            self.scheme, self.masking, participants, dimension,
            self.participants_chunk, self.dim_chunk, self.pallas_active,
            self.surviving_clerks, key,
            extra=extra,
        )

    def drive_tiles(
        self, participants: int, dimension: int, key,
        *, make_block, make_accs, fetch,
        checkpoint_path: Optional[str] = None,
        checkpoint_every_chunks: int = 16, restore_accs=None,
        checkpointer=None,
    ) -> np.ndarray:
        """The tile loop shared by single-host streaming and the multihost
        driver (mesh/multihost.py): d-tiles outer, participant tiles inner,
        one accumulate step per tile, one collective finale per d-tile.

        ``make_block(p0, p1, d0, d1, d_size)`` supplies each global
        [participants_chunk, d_size] device block; ``make_accs(d_size)``
        the zeroed (shares, mask) accumulators; ``fetch(arr)`` brings a
        d-sharded finale result to host numpy. The tile/key derivation here
        is THE definition — mask windows and share randomness depend on it.

        ``checkpoint_path`` (single-process only): same atomic snapshot /
        bit-identical resume contract as StreamingAggregator;
        ``restore_accs(acc_shares_np, acc_mask_np)`` re-places loaded host
        accumulators onto the mesh (defaults to plain ``jnp.asarray``).
        """
        return _drive_stream(
            self, participants, dimension, key,
            make_block=make_block, make_accs=make_accs, fetch=fetch,
            checkpoint_path=checkpoint_path,
            checkpoint_every_chunks=checkpoint_every_chunks,
            restore_accs=restore_accs, checkpointer=checkpointer,
        )

    def aggregate(self, inputs, key=None, reported=None) -> np.ndarray:
        refuse_reported(reported, "StreamedPod")
        inputs = np.asarray(inputs)
        return self.aggregate_blocks(
            array_block_provider(inputs), inputs.shape[0], inputs.shape[1], key
        )
