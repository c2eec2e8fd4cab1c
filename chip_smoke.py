"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the main path once on every visible chip, through the
entry point a user calls (``sda_tpu.cli.sim.main(argv)``, in-process), and
checks every result bit-exact against the plaintext sum:

- pod, flagship shape (100 x 999,999, packed Shamir n=8, full mask) through
  ``SimulatedPod`` — the XLA step, the Mosaic-compiled fused Pallas step with
  the on-core PRNG, and ``StreamingAggregator`` with device ChaCha masks;
  and the same shape under upstream's other scheme, additive 3-of-3 sharing
  with ChaCha masks from 128-bit seeds, through ``SimulatedPod``'s XLA step
  (the fused kernel serves no additive scheme). ChaCha masks run under both
  steps: ``pod.flagship.packed_chacha_pallas`` is the KERNEL under them (the
  masks' sum expanded 8 rows at a time in front of the kernel's mask-free
  variant: the chip benchmark's configuration ``pod-packed8-chacha``);
  ``pod.flagship.streaming_chacha`` and ``pod.flagship.additive_chacha`` are
  the XLA step;
- pod, a cohort streamed in blocks — 600 rows of that width through
  ``StreamingAggregator`` in two blocks of 300 with the fused kernel (the
  chip benchmark's configuration ``stream-packed8``);
- pod, a model's full width — MobileLite's default update vector (~3.7M)
  through ``StreamedPod`` and ``ModelScaleRound`` with the fused kernel, tile
  width from the live ``memory_stats()["bytes_limit"]``;
- federated, trainer + server — LeNet at full width (61,706 elements): real
  local training, sealed boxes, the async ``sdad`` plane over sqlite, clerks,
  reveal; share generation, clerk combine and reconstruction sit above
  ``HOST_PATH_MAX`` and dispatch to the chip.

Cheapest phase first; the first failure ends the run. There is no CPU mode:
without a TPU the script exits non-zero before any phase. The phases are
importable functions taking their sizes, so tests/test_chip_smoke.py runs
them at toy size on the CPU. Chip seconds printed here are information for
whoever reads the log, not metrics.

Last line of stdout on success:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import contextlib
import io
import json
import sys
import time


class PhaseFailed(Exception):
    """A phase ran to the end and its own verdict is wrong."""


def run_sim(argv: list) -> dict:
    """``sda-sim ARGV`` in this process; its one JSON result line, parsed,
    plus the exit code (``rc``) and the whole call's wall seconds."""
    from sda_tpu.cli import sim

    out = io.StringIO()
    t0 = time.perf_counter()
    with contextlib.redirect_stdout(out):
        rc = sim.main([str(a) for a in argv])
    wall_s = time.perf_counter() - t0
    lines = out.getvalue().strip().splitlines()
    if not lines:
        raise PhaseFailed(f"sda-sim {' '.join(map(str, argv))}: rc={rc}, "
                          f"no result line")
    record = json.loads(lines[-1])
    record["rc"] = rc
    record["wall_s"] = round(wall_s, 2)
    return record


def _require(record: dict, what: str, **expected) -> None:
    for key, want in expected.items():
        if record.get(key) != want:
            raise PhaseFailed(
                f"{what}: {key}={record.get(key)!r}, expected {want!r}")


def _summary(record: dict, **extra) -> dict:
    xla = record.get("xla") or {}
    compile_hist = xla.get("compile_seconds") or {}
    return {
        "platform": record["platform"],
        "device_kind": record["device_kind"],
        "device_count": record["device_count"],
        "exact": record["exact"],
        "wall_s": record["wall_s"],
        "compile_s": round(float(compile_hist.get("sum") or 0.0), 2),
        "backend_compiles": xla.get("backend_compiles"),
        "cache": xla.get("cache"),
        **extra,
    }


def pod_round(participants: int, dim: int, *, clerks: int = 8,
              sharing: str = "packed", mask: str = "full",
              pallas: bool = False, streaming: bool = False,
              participants_chunk: int | None = None) -> dict:
    """One pod round at the given shape, verified against the plain sum.
    ``participants_chunk``: the rows a streamed round folds at a time."""
    argv = ["--participants", participants, "--dim", dim, "--clerks", clerks,
            "--sharing", sharing, "--mask", mask, "--verify"]
    if pallas:
        argv.append("--pallas")
    if streaming:
        argv.append("--streaming")
    if participants_chunk is not None:
        argv += ["--participants-chunk", participants_chunk]
    record = run_sim(argv)
    what = f"pod {record.get('mode')} (pallas={pallas})"
    _require(record, what, rc=0, exact=True, pallas=pallas)
    # sda-sim times the second (warm) pod round; the streamed mode makes
    # one pass, so its seconds include the compiles
    return _summary(record, mode=record["mode"], pallas=record["pallas"],
                    round_s=record["seconds"], round_is_warm=not streaming)


def model_scale_round(family: str | None = None, dim: int | None = None, *,
                      participants: int = 4, shards: str | None = None,
                      rounds: int = 3) -> dict:
    """``sda-sim --devscale --devscale-pallas``: the sharded+streamed round
    and the single-program ``ModelScaleRound`` scan lane at a model's
    width, fused kernel on, tile from the HBM watermark. ``participants``
    keeps the materialised input under the scan lane's 2^24-element gate
    (loadgen/devscale.py) at MobileLite's width."""
    argv = ["--devscale", "--devscale-pallas",
            "--devscale-participants", participants,
            "--devscale-rounds", rounds]
    argv += ["--devscale-family", family] if family else ["--devscale-dim", dim]
    if shards:
        argv += ["--devscale-shards", shards]
    record = run_sim(argv)
    what = f"model scale {family or dim}"
    _require(record, what, rc=0, ok=True, exact=True, pallas=True,
             retraces=0, tile_rule="hbm_watermark")
    scan = record.get("scan_lane")
    if not scan or scan["exact"] is not True:
        raise PhaseFailed(f"{what}: ModelScaleRound scan lane {scan!r}")
    return _summary(
        record, dim=record["dim"], pallas=True,
        pallas_interpret=record["pallas_interpret"],
        mesh=[record["p_shards"], record["d_shards"]],
        dim_tile=record["dim_tile"], tiles=record["tiles"],
        retraces=record["retraces"],
        watermark_bytes=record["hbm"]["watermark_bytes"],
        round_s=record["round_seconds_marginal"], round_is_warm=True,
        scan_lane_s=scan["round_seconds"])


def federated_rounds(family: str, participants: int, rounds: int) -> dict:
    """``sda-sim --fl`` over the async HTTP plane and a sqlite store: judged
    on exactness, client failures and leaks — not on accuracy."""
    record = run_sim([
        "--fl", "--fl-family", family, "--participants", participants,
        "--fl-rounds", rounds, "--fl-http", "--async-http",
        "--fl-store", "sqlite", "--fl-target", 0])
    _require(record, f"federated {family}", rc=0, exact=True,
             client_failures=0, http_plane="async")
    if record.get("leaks", 0) != 0:
        raise PhaseFailed(f"federated {family}: leaks={record['leaks']}")
    return _summary(record, family=family, dim=record["dim"],
                    rounds_run=record["rounds_run"],
                    client_failures=record["client_failures"],
                    leaks=record.get("leaks", 0))


def main() -> int:
    from sda_tpu import native
    from sda_tpu.utils.backend import arm_compile_cache, require_tpu

    device = require_tpu()  # raises: no TPU, no smoke
    cache_dir = arm_compile_cache()
    print(json.dumps({"smoke": "start", **device,
                      "compile_cache_dir": cache_dir,
                      "native_available": native.available()}), flush=True)

    flagship = dict(participants=100, dim=999_999)
    # cheapest first, by the cold seconds of the first one-chip run
    # (6 / 27 / 32 / 46 / 91 s, CHANGES.md PR 22)
    phases = [
        ("pod.flagship.pallas", lambda: pod_round(**flagship, pallas=True)),
        ("federated.lenet", lambda: federated_rounds("lenet", 8, 2)),
        ("pod.flagship.xla", lambda: pod_round(**flagship)),
        ("pod.flagship.additive_chacha",
         lambda: pod_round(**flagship, clerks=3, sharing="additive",
                           mask="chacha")),
        ("pod.model_scale.mobilelite",
         lambda: model_scale_round("mobilelite")),
        ("pod.flagship.streaming_chacha",
         lambda: pod_round(**flagship, mask="chacha", streaming=True)),
        # the configuration pod-packed8-chacha (benchmarks/chip) at the
        # flagship's rows: the fused kernel behind blocked ChaCha masks
        ("pod.flagship.packed_chacha_pallas",
         lambda: pod_round(**flagship, pallas=True, mask="chacha")),
        # the configuration stream-packed8 (benchmarks/chip): two blocks of
        # 300 int64 rows through the fused kernel under traced tile offsets
        ("stream.packed_pallas",
         lambda: pod_round(600, 999_999, pallas=True, streaming=True,
                           participants_chunk=300)),
    ]
    cache = {"hit": 0, "miss": 0}
    t0 = time.perf_counter()
    for name, phase in phases:
        result = phase()  # a raise ends the run: fail fast, non-zero exit
        # every phase must have run on the chip this process holds, with
        # the kernel compiled by Mosaic where a kernel was asked for
        _require(result, name, platform="tpu",
                 device_count=device["device_count"])
        if result.get("pallas_interpret"):
            raise PhaseFailed(f"{name}: Pallas kernel was interpreted")
        for k in cache:
            cache[k] += int((result.get("cache") or {}).get(k) or 0)
        print(json.dumps({"phase": name, **result}), flush=True)
    print(json.dumps({"smoke": "done",
                      "wall_s": round(time.perf_counter() - t0, 1),
                      "compile_cache_dir": cache_dir, "cache": cache}),
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["device_kind"],
        "count": device["device_count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
