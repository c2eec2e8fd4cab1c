"""Model-scale device plane: pjit-sharded, HBM-streamed, Pallas-fused
rounds at FL-model dimension (dim >= 1e8).

SDA's original use case is aggregating locally trained ML models, yet
until this module the full mask -> share -> combine -> reconstruct round
at model dimension was never one benched configuration — the parts
existed (``fields/pallas_round`` fused kernel, ``fields/dimtile`` tile
scan, ``mesh/simpod`` shard stages, ``mesh/streaming`` block providers,
devprof HBM watermarks and roofline) but nothing composed them. Three
pieces close that gap:

- **The watermark tile rule** (:func:`watermark_dim_tile`): the dim-tile
  width is DERIVED from the devprof per-device HBM watermark
  (``obs.devprof.hbm_watermark``) and an explicit per-column byte model
  of the sharded round stage — not a magic chunk constant. Peak HBM
  stays under the watermark at any dimension by construction; every
  devscale record reports ``hbm_peak_bytes / watermark``.

- **The sharded scan round** (:class:`ModelScaleRound`): ONE jitted
  ``shard_map`` program over the ``('p', 'd')`` mesh whose per-device
  body streams its local dim shard through the
  :func:`~sda_tpu.fields.dimtile.scan_dim_tiles` schedule — per tile:
  mask + share + local combine (the fused Pallas kernel when active,
  dispatched per shard with per-(seed, shard, tile) PRNG keys), one
  ``psum_scatter`` clerk transpose, reconstruct, unmask. Peak live
  memory per device is one tile's intermediates, so the program holds
  the watermark even when the full-width round would not. Bit-exact vs
  the XLA lane and the host oracle for any keys — masks cancel within
  each tile and random polynomial rows are annihilated by
  reconstruction.

- **The host->device sink** (:class:`DeviceTileSink`,
  :class:`DeviceTileCombiner`): the clerk decrypt pipeline
  (``crypto/batch.prefetch_map``) lands decoded ``[B, tile]`` share
  bundles directly as device-resident tiles — decode runs on the
  bounded crypto pool while the PREVIOUS tile's host->HBM transfer and
  device fold are in flight (double buffering), so the streamed drivers
  consume device arrays instead of host arrays. ``DeviceTileCombiner``
  is the clerk-side consumer (``SDA_CLERK_DEVICE_TILES=1``), bit-exact
  with ``crypto.sharing.mod_combine``.

The benched configuration itself (profile, record, regression tags)
lives in ``loadgen/devscale.py`` behind ``sda-sim --devscale``;
docs/performance.md "Model scale" has the contract.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import numpy as np

from ..fields.dimtile import scan_dim_tiles, tile_plan
from ..fields.ops import FieldOps
from ..obs import devprof
from ..utils import metrics, timed_phase
from .simpod import _dim_grain, _Planned, _RoundPlan, _shard_map, _tile_key

__all__ = [
    "DeviceTileCombiner",
    "DeviceTileSink",
    "ModelScaleRound",
    "bytes_per_dim_column",
    "stream_schedule",
    "watermark_dim_tile",
]


# ---------------------------------------------------------------------------
# The watermark tile-width rule


def bytes_per_dim_column(scheme, masking, local_rows: int,
                         pallas: bool = False) -> int:
    """Conservative per-device HBM bytes one LOCAL dim column costs the
    sharded round stage — the denominator of the watermark tile rule.

    The model counts every live uint32 lane of the per-tile stage body
    (S = participant rows resident on this device, k/t/n/m2/r from the
    scheme; 4 bytes per lane):

    - input block + residue copy, double-buffered against the next
      tile's host->HBM landing: ``3 * S``
    - full-mask draws ``[S, d]``: ``S`` (the Pallas kernel draws
      on-core, but the XLA lane's bound is kept — the rule must hold
      for whichever lane dispatches);
    - share randomness ``[S, t, B]``: ``S * t / k``;
    - matmul operands+result ``[m2, B] + [n, B]``: ``(m2 + n) / k``;
    - accumulators / clerk rows / reconstruct output:
      ``(2n + r) / k + 2``.

    A 25% allocator-slack factor tops it off. The point is not byte
    accuracy — it is that the tile width SCALES from the watermark and
    the scheme instead of being a constant someone measured once.
    """
    k = int(getattr(scheme, "secret_count", 1) or 1)
    t = int(getattr(scheme, "privacy_threshold", 0) or 0)
    n = int(scheme.output_size)
    m2 = 1 + k + t
    r = int(getattr(scheme, "reconstruction_threshold", n) or n)
    S = max(1, int(local_rows))
    from ..protocol import NoMasking

    mask_rows = 0 if isinstance(masking, (NoMasking, type(None))) else 1
    lanes = (
        3 * S                      # block + residues, double-buffered
        + mask_rows * S            # mask draws
        + S * t / k                # share randomness
        + (m2 + n) / k             # matmul operands + result
        + (2 * n + r) / k + 2      # accs + gathered rows + output
    )
    del pallas  # the XLA bound covers the fused kernel too
    return max(16, int(math.ceil(lanes * 4 * 1.25)))


def watermark_dim_tile(
    scheme,
    masking=None,
    *,
    participants_chunk: int,
    p_shards: int,
    d_shards: int,
    pallas: bool = False,
    watermark_bytes: Optional[int] = None,
    dim: Optional[int] = None,
) -> int:
    """The GLOBAL dim-tile width the HBM watermark affords.

    ``watermark // bytes_per_dim_column`` local columns fit one device;
    times ``d_shards`` for the global width, rounded DOWN to the
    mesh/scheme grain (whole packing columns x whole ChaCha blocks x
    d_shards — a tile must be a complete round over its own columns on
    every shard). Clamped to at least one grain and, when ``dim`` is
    given, to the grain-rounded dimension (no tile wider than the
    workload). ``watermark_bytes=None`` reads the live
    :func:`~sda_tpu.obs.devprof.hbm_watermark`.
    """
    from ..protocol import NoMasking

    masking = masking if masking is not None else NoMasking()
    budget = int(watermark_bytes if watermark_bytes is not None
                 else devprof.hbm_watermark())
    # whole packing columns x whole ChaCha blocks, like the scan lane
    grain_loc = math.lcm(_dim_grain(scheme, masking), 8)
    grain = grain_loc * int(d_shards)
    local_rows = -(-int(participants_chunk) // int(p_shards))
    per_col = bytes_per_dim_column(scheme, masking, local_rows, pallas)
    cols_loc = max(grain_loc, budget // per_col)
    tile = max(grain, (cols_loc * int(d_shards)) // grain * grain)
    if dim is not None:
        tile = min(tile, -(-int(dim) // grain) * grain)
    return tile


# ---------------------------------------------------------------------------
# The sharded scan round: one program, tiles streamed inside it


class ModelScaleRound(_Planned):
    """One jitted shard_map round whose per-device body scans dim tiles.

    The pjit x scan x Pallas composition: the ``[P, dim]`` combine is
    sharded over the ``('p', 'd')`` mesh, each device streams its local
    dim shard through :func:`scan_dim_tiles` at the watermark-derived
    tile width, and the per-tile mask+share+combine runs the fused
    Pallas kernel when active (per-(seed, shard, tile) PRNG keys via
    ``_tile_key`` / the scan's per-tile ``fold_in``). Collectives run
    per tile inside the scan: one ``psum_scatter`` clerk transpose over
    ``'p'``, one ``all_gather``, one mask ``psum``.

    Use this lane when the sharded INPUT fits device memory (the tile
    schedule bounds every intermediate); for inputs larger than memory
    compose :class:`~sda_tpu.mesh.streaming.StreamedPod` with the same
    watermark tile width instead (loadgen/devscale.py drives both).
    """

    def __init__(
        self,
        sharing_scheme,
        masking_scheme=None,
        mesh=None,
        dim_tile: Optional[int] = None,
        use_pallas: bool = False,
        pallas_interpret: bool = False,
        pallas_external_bits_fn=None,
        surviving_clerks=None,
        participants_chunk: int = 8,
    ):
        self._plan = _RoundPlan(sharing_scheme, masking_scheme, mesh,
                                use_pallas, surviving_clerks, pallas_interpret,
                                pallas_external_bits_fn)
        s = self.scheme
        p_shards, d_shards = self.mesh.devices.shape
        # tile grain: whole packing columns AND whole ChaCha blocks (the
        # per-tile d_block0 window arithmetic needs 8-aligned widths),
        # same rule as mesh.single_chip_round's tiled schedule
        self._grain_loc = math.lcm(_dim_grain(s, self.masking), 8)
        self._grain = self._grain_loc * d_shards
        if dim_tile is None:
            dim_tile = watermark_dim_tile(
                s, self.masking, participants_chunk=participants_chunk,
                p_shards=p_shards, d_shards=d_shards,
                pallas=self.pallas_active)
        # the per-DEVICE scan width; the global tile is d_shards of these
        self.dim_tile = max(self._grain,
                            int(dim_tile) // self._grain * self._grain)
        self._tile_loc = self.dim_tile // d_shards
        self._step = None
        self._step_shape = None

    def _local_round(self, inputs, key):
        """Per-device body: scan the local [P_loc, d_loc] shard in tiles."""
        import jax

        plan = self._plan
        f, draws = plan.field, plan.draws
        P_loc, d_loc = inputs.shape
        with jax.named_scope(draws):
            pi = jax.lax.axis_index("p")
            di = jax.lax.axis_index("d")

        def one_tile(blk, round_key, tile_key, i, width):
            with jax.named_scope(draws):
                # per-(seed, shard, tile) randomness: scan_dim_tiles folded
                # the tile index into tile_key; _tile_key separates shards
                dev_key = _tile_key(tile_key, pi, di)
                # global stream coordinates of this tile (ChaCha windows)
                d_block0 = (di * d_loc + i * width) // 8
            x = f.to_residues(blk)
            with jax.named_scope(draws):
                pid0 = pi * P_loc
            shares, mask_sum = plan.local_step(
                x, dev_key, round_key=round_key, pid_base=pid0,
                d_block0=d_block0)
            return plan.finale(shares, mask_sum, width)

        return scan_dim_tiles(one_tile, self._grain_loc, self._tile_loc)(
            inputs, key)

    def _build(self, P_pad: int, d_pad: int):
        import jax
        from jax.sharding import PartitionSpec as P

        fn = _shard_map(
            self._local_round, mesh=self.mesh,
            in_specs=(P("p", "d"), P()), out_specs=P("d"))
        # ONE devprof stage for the whole sharded scan round: repeated
        # same-shape rounds must register a single compiled shape, and a
        # dim change re-tiles via the scan length without touching the
        # per-tile body (tests/test_devprof.py model-scale tripwire)
        return devprof.instrument("devscale.round", jax.jit(fn))

    def padded_shape(self, P_total: int, d_total: int) -> Tuple[int, int]:
        p_shards, _ = self.mesh.devices.shape
        return (
            -(-P_total // p_shards) * p_shards,
            -(-d_total // self._grain) * self._grain,
        )

    def _get_step(self, P_pad: int, d_pad: int):
        shape = (P_pad, d_pad)
        if self._step is None or self._step_shape != shape:
            self._step = self._build(*shape)
            self._step_shape = shape
        return self._step

    def aggregate(self, inputs, key=None):
        """[P, d] participant inputs -> [d] aggregate (one full round)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        inputs = np.asarray(inputs)
        if key is None:
            from ..crypto.core import fresh_prng_key

            key = fresh_prng_key()
        P_total, d_total = inputs.shape
        P_pad, d_pad = self.padded_shape(P_total, d_total)
        if (P_pad, d_pad) != (P_total, d_total):
            # zero rows/columns aggregate as zero; masks on the padding
            # cancel like any other mask; stripped below
            padded = np.zeros((P_pad, d_pad), dtype=inputs.dtype)
            padded[:P_total, :d_total] = inputs
            inputs = padded
        step = self._get_step(P_pad, d_pad)
        sharding = NamedSharding(self.mesh, P("p", "d"))
        with timed_phase("devscale.round"):
            # host array straight onto the mesh, shard by shard
            device_inputs = jax.device_put(inputs, sharding)
            out = step(device_inputs, key)
            out.block_until_ready()
        return out[:d_total]


# ---------------------------------------------------------------------------
# Host -> device sink: the clerk pipeline lands device-resident tiles


def stream_schedule(participants: int, dimension: int, pc: int, dc: int,
                    grain: int, uniform_tail: bool = True):
    """The (p0, p1, d0, d1, d_size) block sequence the streamed drivers
    request, in drive order (d-tiles outer, participant tiles inner) —
    mirrors ``mesh.streaming._drive_stream`` so a prefetching sink can
    stay one block ahead of the consumer. The sink VERIFIES each request
    against this prediction and falls back to direct decode on any
    mismatch, so a schedule drift degrades to synchronous, never to
    wrong data."""
    uniform_d = uniform_tail and dimension > dc
    out = []
    for d0 in range(0, dimension, dc):
        d1 = min(d0 + dc, dimension)
        d_size = dc if uniform_d else -(-(d1 - d0) // grain) * grain
        for p0 in range(0, participants, pc):
            out.append((p0, min(p0 + pc, participants), d0, d1, d_size))
    return out


class DeviceTileSink:
    """Double-buffered host->HBM landing of decoded share tiles.

    ``decode(p0, p1, d0, d1) -> [rows, cols] host array`` is the clerk
    pipeline's product (a decoded share bundle — in the benched drill, a
    host-side block generator standing in for the decrypt stage). The
    sink runs decode on the bounded crypto pool
    (``crypto.batch.submit``), pads the block to the uniform step shape,
    and lands it on the mesh with ``jax.device_put`` — keeping
    ``prefetch`` future blocks in flight while the consumer combines the
    current one, so host decode/decrypt overlaps the host->HBM transfer
    and the device fold. ``provider()`` adapts the sink to the streamed
    drivers' ``BlockProvider`` seam: the drivers see device-resident
    tiles, never host arrays.
    """

    def __init__(self, decode, participants: int, dimension: int,
                 participants_chunk: int, dim_chunk: int, *,
                 grain: int = 1, uniform_tail: bool = True,
                 sharding=None, dtype=None, prefetch: int = 1):
        from ..crypto import batch as crypto_batch

        self._decode = decode
        self._sharding = sharding
        self._dtype = dtype
        self._batch = crypto_batch
        self._prefetch = max(0, int(prefetch))
        self._schedule = stream_schedule(
            participants, dimension, participants_chunk, dim_chunk,
            grain, uniform_tail)
        self._pc = int(participants_chunk)
        self._next = 0       # next schedule index to launch
        self._queue = []     # [(coords, handle)] in flight, oldest first
        self._fill()

    def _fill(self) -> None:
        while (self._next < len(self._schedule)
               and len(self._queue) < self._prefetch + 1):
            coords = self._schedule[self._next]
            self._queue.append((coords, self._land(coords)))
            self._next += 1

    def _land(self, coords):
        p0, p1, d0, d1, d_size = coords

        def job():
            import jax
            import jax.numpy as jnp

            host = np.asarray(self._decode(p0, p1, d0, d1))
            if self._dtype is not None:
                host = host.astype(self._dtype, copy=False)
            if host.shape != (self._pc, d_size):
                padded = np.zeros((self._pc, d_size), dtype=host.dtype)
                padded[: host.shape[0], : host.shape[1]] = host
                host = padded
            # a sharded landing goes host -> each device's own shard,
            # never through a whole-block copy on device 0
            if self._sharding is not None:
                return jax.device_put(host, self._sharding)
            return jnp.asarray(host)

        return self._batch.submit(job)

    def provider(self):
        """A ``BlockProvider`` serving device-resident tiles in stream
        order (prefetched); out-of-order requests decode synchronously."""

        def get_block(p0, p1, d0, d1):
            if self._queue and self._queue[0][0][:4] == (p0, p1, d0, d1):
                _, handle = self._queue.pop(0)
                self._fill()  # keep the pipeline primed
                metrics.count("devscale.sink.hit")
                return handle.result()
            # drift between consumer and predicted schedule: stay correct
            metrics.count("devscale.sink.miss")
            return np.asarray(self._decode(p0, p1, d0, d1))

        return get_block


class DeviceTileCombiner:
    """Device-resident clerk combine: fold decoded share bundles into a
    tiled device accumulator, bit-exact with
    ``crypto.sharing.mod_combine``.

    The clerk hot path's per-bundle ``[B, dim]`` fold runs as uniform
    ``[B, tile]`` device tiles (width from the HBM watermark unless
    given): each tile is ``device_put`` while the PREVIOUS tile folds,
    so the host->HBM transfer overlaps the device adds, and the decrypt
    pipeline (``prefetch_map``) overlaps both. One compiled fold shape
    per (rows, tile) — repeated bundles never retrace. Enabled on the
    clerk via ``SDA_CLERK_DEVICE_TILES=1``
    (``client.process_clerking_job``).
    """

    def __init__(self, modulus: int, dim_tile: Optional[int] = None):
        self._f = FieldOps.create(int(modulus))
        self._dim_tile = None if dim_tile is None else max(128, int(dim_tile))
        self._tiles = None     # list of per-tile device accumulators
        self._dim = None
        self._folds = 0
        self._step = None

    def _plan(self, rows: int, dim: int):
        import jax.numpy as jnp

        if self._dim_tile is None:
            # watermark rule, combiner flavor: the live set per tile is
            # the [rows, tile] bundle (double-buffered), its residue
            # copy, and the accumulator — ~ (2*rows + 2) uint32/int64
            # lanes per column, 25% slack
            lane = 4 if self._f.sp is not None else 8
            per_col = int((2 * rows + 2) * lane * 1.25)
            self._dim_tile = max(128, devprof.hbm_watermark() // per_col)
        plan = tile_plan(dim, 1, self._dim_tile)
        self._dim = dim
        self._plan_t = plan
        self._tiles = [jnp.zeros((plan.width,), self._f.dtype)
                       for _ in range(plan.n_tiles)]

    def _fold_step(self):
        import jax

        if self._step is None:
            f = self._f

            def step(acc, blk):
                return f.add(acc, f.sum(f.to_residues(blk), axis=0))

            self._step = devprof.instrument(
                "devscale.clerk_combine", jax.jit(step))
        return self._step

    def fold(self, share_rows) -> None:
        """Fold one decoded bundle (``[B, dim]`` array or sequence of
        ``[dim]`` vectors) into the device accumulator."""
        import jax.numpy as jnp

        stacked = np.asarray(share_rows, dtype=np.int64)
        if stacked.ndim == 1:
            stacked = stacked[None, :]
        if self._tiles is None:
            self._plan(stacked.shape[0], stacked.shape[1])
        if stacked.shape[1] != self._dim:
            raise ValueError(
                f"bundle dim {stacked.shape[1]} != combiner dim {self._dim}")
        plan = self._plan_t
        if plan.pad:
            stacked = np.pad(stacked, ((0, 0), (0, plan.pad)))
        step = self._fold_step()
        # land tile j+1 while tile j folds: transfer overlaps compute
        pending = jnp.asarray(stacked[:, : plan.width])
        for j in range(plan.n_tiles):
            current = pending
            if j + 1 < plan.n_tiles:
                lo = (j + 1) * plan.width
                pending = jnp.asarray(stacked[:, lo: lo + plan.width])
            self._tiles[j] = step(self._tiles[j], current)
        self._folds += 1
        metrics.count("devscale.clerk_combine.bundles")

    @property
    def folded(self) -> int:
        return self._folds

    def result(self) -> np.ndarray:
        """The combined [dim] int64 vector (canonical residues)."""
        if self._tiles is None:
            return np.zeros(0, dtype=np.int64)
        f = self._f
        parts = [np.asarray(f.to_int64(t)) for t in self._tiles]
        return np.concatenate(parts)[: self._dim]
