"""Multi-host execution: the DCN-scale story made runnable.

The reference scales across machines with a REST broker; the pod modes
replace that with XLA collectives over ICI (SURVEY §5.8). This module
closes the remaining gap — *multi-controller* runs where each host owns a
process-local slice of the participants and the collectives ride ICI
within a host/slice and DCN across them:

- ``initialize()`` wraps ``jax.distributed.initialize`` (call before any
  jax backend touch; on TPU pods the arguments are auto-detected).
- ``aggregate_process_local(pod, local_inputs)`` runs one full secure-
  aggregation round where every process contributes its own participant
  rows: inputs are assembled into a global array with
  ``jax.make_array_from_process_local_data`` (no host ever materializes
  the global input), the pod's SPMD round runs once, and every process
  receives the full [d] aggregate.

Pair the mesh with ``make_multislice_mesh(n_slices=process_count, ...)``
so each process's devices form one contiguous slice block of the ``p``
axis — then participant data never crosses hosts; only the clerk-combine
reduction does (one DCN step, SURVEY §2.4's committee parallelism).

Tested for real with two OS processes over gRPC on CPU meshes
(tests/test_multihost.py) — the same code path multi-host TPU uses.
"""

from __future__ import annotations

from typing import Optional

import numpy as np


def initialize(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """``jax.distributed.initialize`` with explicit args (CPU/GPU fleets)
    or auto-detection (TPU pods). Must run before any jax backend init.
    On CPU fleets set the per-process device count via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``."""
    import jax

    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


def aggregate_process_local(pod, local_inputs, key=None, reported=None):
    """One secure-aggregation round over process-local participant rows.

    Every process passes its own ``[P_local, d]`` block (same ``d``
    everywhere; ragged ``P_local`` is fine — blocks are zero-padded to the
    max, and zero rows aggregate as zero with their masks cancelling).
    Returns the full [d] aggregate as host numpy, identical on every
    process. ``reported`` is refused (``simpod.refuse_reported``).
    """
    import math

    import jax
    import jax.numpy as jnp
    from jax.experimental import multihost_utils
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..crypto.core import fresh_prng_key
    from ..utils import timed_phase
    from .simpod import refuse_reported

    refuse_reported(reported, "multihost.aggregate_process_local")
    inputs = np.asarray(local_inputs)
    if inputs.ndim != 2:
        raise ValueError("local_inputs must be [P_local, d]")
    nproc = jax.process_count()
    P_local, d_total = inputs.shape

    # processes must agree on the dimension; ragged participant counts are
    # fine — every process sizes its block to the max, and zero rows
    # aggregate as zero with their masks cancelling
    shapes = multihost_utils.process_allgather(
        jnp.asarray([P_local, d_total], dtype=jnp.int32)
    ).reshape(nproc, 2)
    if not (shapes[:, 1] == d_total).all():
        raise ValueError(
            f"process-local dimensions disagree: {shapes[:, 1].tolist()}"
        )
    P_local = int(shapes[:, 0].max())  # sizing only; `padded` zero-fills

    P_global = P_local * nproc
    # each process's devices must tile whole, contiguous p-rows of the mesh
    # (jax.make_array_from_process_local_data maps local blocks onto the
    # process-addressed extent) — make_multislice_mesh(n_slices=nproc, ...)
    # produces exactly this layout
    _check_mesh_process_split(pod.mesh, nproc)
    p_shards = pod.mesh.devices.shape[0]
    # the participant axis must honor BOTH grains: the mesh p axis (via
    # pod.padded_shape) and an integer per-process row count
    p_grain = math.lcm(p_shards, nproc)
    P_lift = -(-P_global // p_grain) * p_grain
    P_pad, d_pad = pod.padded_shape(P_lift, d_total)
    assert P_pad == P_lift and P_pad % nproc == 0
    P_pad_local = P_pad // nproc
    padded = np.zeros((P_pad_local, d_pad), dtype=inputs.dtype)
    padded[: inputs.shape[0], :d_total] = inputs

    if key is None:
        key = fresh_prng_key()
    # one round key for the whole pod: process 0's key wins
    key = multihost_utils.broadcast_one_to_all(key)

    step = pod._get_step(P_pad, d_pad)

    sharding = NamedSharding(pod.mesh, P("p", "d"))
    with timed_phase("mesh.multihost_round"):
        global_inputs = jax.make_array_from_process_local_data(
            sharding, padded, (P_pad, d_pad)
        )
        out = step(global_inputs, key)
        # out is dim-sharded across the global mesh; allgather to every host
        result = multihost_utils.process_allgather(out, tiled=True)
    return np.asarray(result)[:d_total]


def _check_mesh_process_split(mesh, nproc: int) -> None:
    import jax

    p_shards, d_shards = mesh.devices.shape
    n_local = len(jax.local_devices())
    if p_shards % nproc or (p_shards // nproc) * d_shards != n_local:
        raise ValueError(
            f"mesh ({p_shards}, {d_shards}) does not split its p axis "
            f"evenly over {nproc} processes x {n_local} local devices; "
            f"build it with make_multislice_mesh(n_slices={nproc}, "
            f"p_per_slice={n_local}//d_shards, d_shards)"
        )


class _MultihostCheckpointer:
    """Coordinated per-process snapshots for multihost streamed rounds.

    Every process snapshots its OWN addressable shards of the global
    accumulators (plus the — identical-everywhere — completed output
    prefix and tile cursor) to ``path.r{rank}of{n}`` at the same
    deterministic loop boundaries, rotating TWO slots. A crash can leave
    ranks one boundary apart (saves are lockstep but not atomic across
    processes), so resume picks the newest cursor EVERY rank still holds:
    each rank allgathers its available cursors and the same minimum is
    chosen everywhere; if the spread exceeds the two-slot history the
    round restarts from scratch rather than resuming inconsistently.
    Accumulator shards are re-placed by global index, so resume is
    bit-identical to an uninterrupted run (same tile/key derivation).
    """

    SLOTS = 2

    def __init__(self, path, spod, fingerprint):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        self.nproc = jax.process_count()
        self.rank = jax.process_index()
        self.fingerprint = f"{fingerprint}|nproc={self.nproc}|rank={self.rank}"
        base = f"{path}.r{self.rank}of{self.nproc}"
        self.paths = [f"{base}.{s}" for s in ("a", "b")]
        self.sharding = NamedSharding(spod.mesh, P("p", "d"))
        self._slot = 0

    # -- save --------------------------------------------------------------

    def _acc_payload(self, name, acc):
        payload = {}
        if isinstance(acc, np.ndarray):  # d-tile boundary: empty acc
            payload[f"{name}_host"] = acc
            return payload
        payload[f"{name}_shape"] = np.asarray(acc.shape, dtype=np.int64)
        for j, shard in enumerate(acc.addressable_shards):
            starts = [
                (s.start if s.start is not None else 0)
                for s in shard.index
            ]
            payload[f"{name}_{j}_start"] = np.asarray(starts, dtype=np.int64)
            payload[f"{name}_{j}_data"] = np.asarray(shard.data)
        return payload

    def save(self, out, done_dims, di, pi, acc_shares, acc_mask):
        from .streaming import _atomic_npz, _snapshot_header

        payload = _snapshot_header(self.fingerprint, out, done_dims, di, pi)
        payload.update(self._acc_payload("accS", acc_shares))
        payload.update(self._acc_payload("accM", acc_mask))
        _atomic_npz(self.paths[self._slot], **payload)
        self._slot ^= 1

    # -- load / coordinate -------------------------------------------------

    def _local_candidates(self):
        """cursor -> path, probing ONLY the cursor header (no accumulator
        payloads are materialized until the fleet has picked a target)."""
        from .streaming import _read_snapshot

        cands = {}
        for path in self.paths:
            header = _read_snapshot(path, self.fingerprint,
                                    keys=("done_dims", "di", "pi"))
            if header is not None:
                cursor = (int(header["di"]), int(header["pi"]),
                          int(header["done_dims"]))
                cands[cursor] = path
        return cands

    def load(self):
        import jax.numpy as jnp
        from jax.experimental import multihost_utils

        from .streaming import _read_snapshot

        cands = self._local_candidates()
        # encode this rank's available cursors as a fixed [SLOTS, 3] block
        # (-1 rows = no snapshot) and allgather — every rank computes the
        # SAME resume decision from the identical gathered table
        enc = np.full((self.SLOTS, 3), -1, dtype=np.int64)
        for j, cursor in enumerate(sorted(cands)[: self.SLOTS]):
            enc[j] = cursor
        table = np.asarray(multihost_utils.process_allgather(
            jnp.asarray(enc))).reshape(self.nproc, self.SLOTS, 3)
        per_rank = []
        for r in range(self.nproc):
            have = {tuple(int(v) for v in row)
                    for row in table[r] if row[0] >= 0}
            if not have:
                return None  # a rank with no snapshot: fresh start
            per_rank.append(have)
        target = min(max(have) for have in per_rank)
        if any(target not in have for have in per_rank):
            return None  # spread beyond history: restart, never mix
        payload = _read_snapshot(cands[target], self.fingerprint)
        # the full-read outcome must stay a FLEET decision: a snapshot
        # lost between probe and read on one rank must send every rank
        # down the fresh-start path together, not split them
        ok = np.asarray(multihost_utils.process_allgather(
            jnp.asarray([1 if payload is not None else 0])))
        if int(ok.sum()) != self.nproc:
            return None
        return {
            "out": payload["out"],
            "done_dims": payload["done_dims"],
            "di": payload["di"],
            "pi": payload["pi"],
            "_payload": payload,
        }

    def restore(self, resume):
        import jax

        payload = resume["_payload"]

        def rebuild(name):
            shape = tuple(int(v) for v in payload[f"{name}_shape"])
            blocks = {}
            j = 0
            while f"{name}_{j}_data" in payload:
                starts = tuple(int(v) for v in payload[f"{name}_{j}_start"])
                blocks[starts] = payload[f"{name}_{j}_data"]
                j += 1

            def cb(index):
                starts = tuple(
                    (s.start if s.start is not None else 0) for s in index
                )
                return blocks[starts]

            return jax.make_array_from_callback(shape, self.sharding, cb)

        return rebuild("accS"), rebuild("accM")

    def finish(self):
        import os

        for path in self.paths:
            try:
                os.unlink(path)
            except OSError:
                pass


def streamed_aggregate_process_local(
    spod, get_local_block, local_participants: int, dimension: int, key=None,
    *, checkpoint_path=None, checkpoint_every_chunks: int = 16,
):
    """Flagship-scale multihost rounds: every process STREAMS its own
    participant rows through the StreamedPod tile loop.

    ``get_local_block(lp0, lp1, d0, d1)`` returns this process's local rows
    ``[lp0:lp1]`` for dim window ``[d0:d1)`` (short or empty edge blocks
    are zero-padded here, so ragged per-process ``local_participants`` is
    fine). All processes iterate in lockstep to the max local count — each
    global tile is assembled from per-process local blocks with
    ``make_array_from_process_local_data``, so no host ever materializes a
    global tile, let alone the global matrix. Aggregation is a sum, so the
    (process-major) global participant ordering is irrelevant to the
    result. Returns the [dimension] aggregate on every process.

    ``checkpoint_path``: coordinated multi-process resume — every process
    snapshots its own accumulator shards at the same loop boundaries
    (two-slot history; see _MultihostCheckpointer) and a relaunched fleet
    resumes bit-identically from the newest cursor all ranks still hold.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import multihost_utils
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..crypto.core import fresh_prng_key
    from ..utils import timed_phase

    nproc = jax.process_count()
    _check_mesh_process_split(spod.mesh, nproc)
    shapes = multihost_utils.process_allgather(
        jnp.asarray([local_participants, dimension], dtype=jnp.int32)
    ).reshape(nproc, 2)
    if not (shapes[:, 1] == dimension).all():
        raise ValueError(
            f"process-local stream dimensions disagree: {shapes[:, 1].tolist()}"
        )
    # ragged local counts: iterate to the max, but never ask the caller's
    # provider for rows beyond what IT declared — short/empty blocks are
    # zero-padded below and zeros aggregate as zero
    my_count = local_participants
    local_participants = int(shapes[:, 0].max())

    if key is None:
        key = fresh_prng_key()
    key = multihost_utils.broadcast_one_to_all(key)

    pc = spod.participants_chunk
    # StreamedPod rounds pc up to a multiple of p_shards, and the mesh check
    # guarantees nproc divides p_shards — so whole local rows per tile
    assert pc % nproc == 0, (pc, nproc)
    pc_local = pc // nproc
    sharding = NamedSharding(spod.mesh, P("p", "d"))
    dt = spod._field.dtype

    def zeros_global(shape):
        def cb(index):
            sizes = tuple(
                (s.stop if s.stop is not None else dim)
                - (s.start if s.start is not None else 0)
                for s, dim in zip(index, shape)
            )
            return np.zeros(sizes, dt)

        return jax.make_array_from_callback(shape, sharding, cb)

    def make_accs(d_size):
        sS, sM = spod._acc_shapes(d_size)
        return zeros_global(sS), zeros_global(sM)

    def make_block(p0, p1, d0, d1, d_size):
        # global tile rows [p0:p1) map process-major onto local rows
        lp0 = min(p0 // nproc, my_count)
        lp1 = min(p1 // nproc, my_count)
        host = np.asarray(get_local_block(lp0, max(lp0, lp1), d0, d1))
        if host.shape != (pc_local, d_size):
            padded = np.zeros((pc_local, d_size), dtype=host.dtype)
            padded[: host.shape[0], : host.shape[1]] = host
            host = padded
        return jax.make_array_from_process_local_data(
            sharding, host, (pc, d_size)
        )

    def fetch(arr):
        return np.asarray(multihost_utils.process_allgather(arr, tiled=True))

    checkpointer = None
    if checkpoint_path is not None:
        checkpointer = _MultihostCheckpointer(
            checkpoint_path, spod,
            spod._checkpoint_fingerprint(
                local_participants * nproc, dimension, key),
        )

    with timed_phase("mesh.multihost_streamed_round"):
        # drive over the GLOBAL participant count so every process iterates
        # the identical tile sequence in lockstep
        return spod.drive_tiles(
            local_participants * nproc, dimension, key,
            make_block=make_block, make_accs=make_accs, fetch=fetch,
            checkpointer=checkpointer,
            checkpoint_every_chunks=checkpoint_every_chunks,
        )
