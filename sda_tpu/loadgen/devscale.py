"""The model-scale device-plane bench: ``sda-sim --devscale``.

ROADMAP item "device plane at model scale" made benchable: the full
mask -> share -> combine -> reconstruct round at FL-model dimension
(dim >= 1e8), sharded over the ``('p', 'd')`` mesh, streamed through
HBM at the watermark-derived tile width, Pallas-fused when active, with
the clerk-pipeline-fed device-tile sink exercised in the same run. One
BENCH-style record:

- headline ``value`` = ``participants * dim / round_seconds_marginal``
  (elements/sec through the complete round, marginal over the warm
  rounds — round 1 pays the compiles);
- ``exact`` — bit-exactness vs the host oracle lane (full column sums
  at drill dims, seeded sampled windows at model scale where the host
  cannot afford the full object-dtype reference);
- ``retraces == 0`` across rounds and one compiled shape per stage
  (uniform tails — the devprof tripwire, recorded not just asserted);
- the ``cost`` block (FLOPs/bytes XLA counts from the shapes) and the
  ``hbm`` watermark advisory (``hbm_peak_bytes / watermark``); on a
  device with a complete sourced row in ``devprof.CHIP_PEAKS`` also the
  ``roofline`` block and ``roofline_utilization`` — a CPU run has
  neither, nor today a v5e run (obs/regress.py reports both as advisory metrics);
- comparability tags ``dim / p_shards / d_shards / pallas`` so this
  record NEVER gates against single-chip or different-topology history.

Every record names its device (``platform``, ``device_kind``,
``device_count``); ``host_scaled`` marks a CPU run — same schedule, same
verdicts, no device timing — and ``pallas_interpret`` a kernel that was
interpreted rather than compiled by Mosaic.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Optional

import numpy as np

__all__ = ["DevScaleProfile", "run_devscale"]


@dataclass
class DevScaleProfile:
    """Knobs for the model-scale round bench (``sda-sim --devscale``)."""

    dim: int = 100_000_000            # target dimension (>= 1e8 = ROADMAP rung)
    family: Optional[str] = None      # mobilelite | lora | devscale (sets dim)
    participants: int = 8
    participants_chunk: int = 8
    p_shards: Optional[int] = None    # default: gcd(devices, committee)
    d_shards: Optional[int] = None
    clerks: int = 8
    modulus_bits: int = 28            # Solinas prime -> uint32 fast path
    mask: str = "full"                # none | full | chacha
    dim_tile: Optional[int] = None    # None -> watermark rule
    pallas: bool = False
    pallas_interpret: bool = False    # CPU drills: interpret-mode kernel
    rounds: int = 3                   # 1 warm + (rounds-1) timed
    seed: int = 0
    scan_lane: Optional[bool] = None  # ModelScaleRound A/B (auto: small dims)
    clerk_fed: bool = True            # DeviceTileSink-fed round
    oracle_windows: int = 4
    oracle_window_cols: int = 4096

    def validate(self) -> None:
        if self.dim <= 0 and not self.family:
            raise ValueError("dim must be positive (or set family)")
        if self.participants <= 0:
            raise ValueError("participants must be positive")
        if self.rounds < 2:
            raise ValueError("rounds must be >= 2 (round 1 is the warmup)")
        if self.mask not in ("none", "full", "chacha"):
            raise ValueError(f"unknown mask {self.mask!r}")


def _oracle_check(out, host_provider, participants, dim, modulus, profile):
    """Bit-exactness vs the host oracle lane: full column sums when the
    host can afford them, seeded sampled windows at model scale."""
    full = dim <= (1 << 17)
    windows = []
    if full:
        windows.append((0, dim))
    else:
        w = min(int(profile.oracle_window_cols), dim)
        rng = np.random.default_rng(profile.seed ^ 0x0AC1E)
        offsets = {0, dim - w}
        for _ in range(max(0, int(profile.oracle_windows) - 2)):
            offsets.add(int(rng.integers(0, max(1, dim - w))))
        windows = sorted((o, o + w) for o in offsets)
    checked = 0
    for d0, d1 in windows:
        block = np.asarray(
            host_provider(0, participants, d0, d1)).astype(np.int64)
        expected = block.sum(axis=0) % modulus
        if not np.array_equal(np.asarray(out[d0:d1]), expected):
            return False, {"mode": "full" if full else "sampled",
                           "windows": len(windows), "cols": checked,
                           "failed_window": [d0, d1]}
        checked += d1 - d0
    return True, {"mode": "full" if full else "sampled",
                  "windows": len(windows), "cols": checked}


def run_devscale(profile: DevScaleProfile) -> dict:
    """Run the model-scale round bench and return the BENCH record."""
    profile.validate()
    import jax

    from .. import obs
    from ..fields import numtheory
    from ..mesh import (
        DeviceTileSink,
        ModelScaleRound,
        StreamedPod,
        default_mesh_shape,
        make_mesh,
        watermark_dim_tile,
    )
    from ..mesh.streaming import (
        synthetic_block_provider32,
        synthetic_device_block_provider32,
    )
    from ..obs import devprof
    from ..protocol import (
        ChaChaMasking,
        FullMasking,
        NoMasking,
        PackedShamirSharing,
    )
    from ..utils import metrics

    dim = int(profile.dim)
    family = profile.family
    if family:
        from ..fl.flagship import flagship_dim

        dim = flagship_dim(family)

    k = 3
    t, p, w2, w3 = numtheory.generate_packed_params(
        k, profile.clerks, profile.modulus_bits)
    scheme = PackedShamirSharing(k, profile.clerks, t, p, w2, w3)
    masking = {
        "none": NoMasking(),
        "full": FullMasking(p),
        "chacha": ChaChaMasking(p, dim, 128),
    }[profile.mask]

    n_devices = len(jax.devices())
    p_shards = profile.p_shards or default_mesh_shape(
        n_devices, scheme.output_size)[0]
    d_shards = profile.d_shards or (n_devices // p_shards)
    mesh = make_mesh(p_shards, d_shards)
    from ..utils.backend import device_record

    device = device_record()
    cpu = device["platform"] == "cpu"

    obs.reset_all()
    devprof.install_monitoring()
    devprof.enable_cost_analysis()

    watermark = devprof.hbm_watermark()
    dim_tile = profile.dim_tile or watermark_dim_tile(
        scheme, masking, participants_chunk=profile.participants_chunk,
        p_shards=p_shards, d_shards=d_shards, pallas=profile.pallas,
        watermark_bytes=watermark, dim=dim)

    pallas_kwargs = {}
    if profile.pallas:
        pallas_kwargs = dict(use_pallas=True,
                             pallas_interpret=profile.pallas_interpret)
        if profile.pallas_interpret:
            # interpret mode cannot run the TPU PRNG primitive: inject
            # the external-randomness stream (pallas_round.py contract)
            import jax.numpy as jnp

            def external_bits(key, P, draws, B):
                return jax.random.bits(key, (P, 2 * draws, B),
                                       dtype=jnp.uint32)

            pallas_kwargs["pallas_external_bits_fn"] = external_bits

    pod = StreamedPod(
        scheme, masking, mesh=mesh,
        participants_chunk=profile.participants_chunk,
        dim_chunk=dim_tile, uniform_tail=True, **pallas_kwargs)
    dev_provider = synthetic_device_block_provider32(p, seed=profile.seed)
    host_provider = synthetic_block_provider32(p, seed=profile.seed)
    key = jax.random.PRNGKey(profile.seed)
    P_total = profile.participants

    wall0 = time.perf_counter()
    out = pod.aggregate_blocks(dev_provider, P_total, dim, key)
    warm_s = time.perf_counter() - wall0
    out_warm = np.asarray(out)  # round-key reveal, reused by the sink A/B

    def _stage_compiles():
        return {name: (devprof.profile(name).compiles,
                       len(devprof.profile(name).shapes))
                for name in ("stream.pod.step", "stream.pod.finale")}

    compiles_after_warm = _stage_compiles()
    t0 = time.perf_counter()
    for r in range(1, profile.rounds):
        out = pod.aggregate_blocks(dev_provider, P_total, dim,
                                   jax.random.fold_in(key, r))
    timed_s = time.perf_counter() - t0
    per_round = timed_s / max(1, profile.rounds - 1)
    compiles_after = _stage_compiles()
    retraces = metrics.counter_report("xla.compile.retrace").get(
        "xla.compile.retrace", 0)
    warm_reused = compiles_after == compiles_after_warm

    exact, oracle = _oracle_check(
        out, host_provider, P_total, dim, p, profile)

    # -- clerk-pipeline-fed device tiles: the decode stage (standing in
    # for the decrypt pipeline's product) runs on the crypto pool, lands
    # on the mesh double-buffered, and the SAME round key must reveal
    # the SAME bytes as the device-generated lane
    clerk_fed = None
    if profile.clerk_fed:
        from jax.sharding import NamedSharding, PartitionSpec as P

        sink = DeviceTileSink(
            host_provider, P_total, dim, pod.participants_chunk,
            pod.dim_chunk, grain=pod._grain, uniform_tail=True,
            sharding=NamedSharding(pod.mesh, P("p", "d")))
        s0 = time.perf_counter()
        out_sink = pod.aggregate_blocks(sink.provider(), P_total, dim, key)
        sink_s = time.perf_counter() - s0
        # same round key as the warm round -> identical randomness ->
        # the sink-fed reveal must reproduce the device-generated bytes
        clerk_fed = {
            "exact": bool(np.array_equal(np.asarray(out_sink), out_warm)),
            "round_seconds": round(sink_s, 4),
            "sink_hits": metrics.counter_report("devscale.sink.").get(
                "devscale.sink.hit", 0),
            "sink_misses": metrics.counter_report("devscale.sink.").get(
                "devscale.sink.miss", 0),
        }

    # -- the single-program scan lane (pjit x scan_dim_tiles x pallas):
    # A/B'd when the sharded input is small enough to materialize
    scan_lane = profile.scan_lane
    if scan_lane is None:
        scan_lane = dim * P_total <= (1 << 24)
    scan = None
    if scan_lane:
        inputs = np.asarray(host_provider(0, P_total, 0, dim))
        msr = ModelScaleRound(scheme, masking, mesh=mesh,
                              dim_tile=dim_tile, **pallas_kwargs)
        s0 = time.perf_counter()
        out_scan = np.asarray(msr.aggregate(inputs, key))
        scan_s = time.perf_counter() - s0
        expected = inputs.astype(np.int64).sum(axis=0) % p
        scan = {
            "exact": bool(np.array_equal(out_scan, expected)),
            "round_seconds": round(scan_s, 4),
            "dim_tile": msr.dim_tile,
        }

    wall = time.perf_counter() - wall0
    roofline = devprof.roofline(seconds=wall)
    hbm = devprof.watermark_report()
    value = P_total * dim / per_round if per_round > 0 else 0

    tiles = -(-dim // pod.dim_chunk)
    record = {
        "metric": ("model-scale device round elements/sec "
                   "(packed-Shamir n=%d, %s mask, sharded+streamed)"
                   % (profile.clerks, profile.mask)),
        "value": round(value),
        "unit": "elements/sec",
        **device,
        "pallas": bool(pod.pallas_active),
        "pallas_interpret": bool(pod.pallas_active
                                 and profile.pallas_interpret),
        "dim": dim,
        "participants": P_total,
        "p_shards": p_shards,
        "d_shards": d_shards,
        "dim_tile": pod.dim_chunk,
        "tiles": tiles,
        "participants_chunk": pod.participants_chunk,
        "tile_rule": ("explicit" if profile.dim_tile
                      else "hbm_watermark"),
        "rounds": profile.rounds,
        "round_seconds_marginal": round(per_round, 4),
        "compile_seconds": round(max(0.0, warm_s - per_round), 2),
        "exact": bool(exact),
        "oracle": oracle,
        "retraces": int(retraces),
        "warm_program_reused": bool(warm_reused),
        "compiled_shapes": {name: shapes for name, (comp, shapes)
                            in compiles_after.items()},
        "cost": devprof.cost_totals(),
        "hbm": hbm,
        "hbm_watermark_ratio": hbm.get("hbm_watermark_ratio"),
        "host_scaled": cpu,
        "seed": profile.seed,
        "xla": devprof.compile_totals(),
    }
    if roofline is not None:
        record["roofline"] = roofline
        record["roofline_utilization"] = roofline.get("utilization")
    if family:
        record["family"] = family
    if clerk_fed is not None:
        record["clerk_fed"] = clerk_fed
    if scan is not None:
        record["scan_lane"] = scan
    if cpu:
        record["note"] = ("CPU run: same schedule and verdicts as on a "
                          "chip; its seconds and rates are not device "
                          "metrics")
    record["ok"] = bool(
        exact and retraces == 0 and warm_reused
        and (clerk_fed is None or clerk_fed["exact"])
        and (scan is None or scan["exact"])
        and hbm.get("within_watermark", True))
    return record
