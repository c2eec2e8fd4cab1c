"""Device timing by chained dispatch, and the kernel-knob plumbing.

JAX dispatch is asynchronous, so a timing is only honest if the device has
finished when the clock stops. ``marginal_seconds`` measures the MARGINAL
cost of one repetition: dispatch a chain of r reps whose outputs the next
rep does not need (the device serializes them anyway), force completion
with one tiny ``device_get``, and difference two chain lengths so fixed
host and dispatch overheads cancel:

    per_rep = (T(r2) - T(r1)) / (r2 - r1)

On a local chip a loop of single calls that each end in
``block_until_ready`` must agree with it up to the per-call dispatch
overhead the chain hides; ``bench.py`` reports both
(``round_seconds_marginal`` / ``round_seconds_blocked``) so the agreement
is checked on every run rather than assumed.
"""

from __future__ import annotations

import time
from typing import Callable, Tuple


def chain_seconds(dispatch: Callable[[int], object], reps: int) -> float:
    """Wall time to dispatch ``reps`` calls and drain the device queue.

    ``dispatch(i)`` must issue rep ``i`` and return a jax array (any
    shape); completion is forced with a single elementwise D2H get.
    """
    import jax
    import jax.numpy as jnp

    t0 = time.perf_counter()
    out = None
    for i in range(reps):
        out = dispatch(i)
    jax.device_get(jnp.ravel(out)[0])
    return time.perf_counter() - t0


def marginal_seconds(
    dispatch: Callable[[int], object],
    target_seconds: float = 10.0,
    max_reps: int = 64,
) -> Tuple[float, dict]:
    """Marginal per-rep seconds of ``dispatch``, with diagnostics.

    Probes one rep to size the chains, then returns
    ``(T(r2) - T(r1)) / (r2 - r1)`` with r2 ~ target_seconds of work.
    The dict records the raw chain timings for the bench JSON.
    """
    probe = chain_seconds(dispatch, 1)  # includes fixed overhead: overestimates
    r2 = int(min(max_reps, max(10, round(target_seconds / max(probe, 1e-4)))))
    r1 = max(1, r2 // 5)
    t1 = chain_seconds(dispatch, r1)
    t2 = chain_seconds(dispatch, r2)
    if t2 > t1 and r2 > r1:
        per = (t2 - t1) / (r2 - r1)
    else:  # noise swamped the difference; fall back to the long chain
        per = t2 / r2
    info = {
        "timing": "chained-dispatch marginal (cancels fixed overhead)",
        "probe_s": round(probe, 4),
        "chain": {"r1": r1, "t1_s": round(t1, 4), "r2": r2, "t2_s": round(t2, 4)},
        "fixed_overhead_s": round(max(t1 - r1 * per, 0.0), 4),
    }
    return per, info


def _knobs_record() -> dict:
    """The committed kernel-knob record benchmarks/PALLAS_KNOBS.json,
    or {} when absent/unreadable.
    Resolved relative to this package's repo checkout."""
    import json
    import os

    try:
        path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))),
            "benchmarks", "PALLAS_KNOBS.json")
        with open(path) as f:
            rec = json.load(f)
        return rec if isinstance(rec, dict) else {}
    except (OSError, ValueError):
        return {}


def pallas_knobs():
    """(p_block, tile) kernel-tuning knobs: SDA_PALLAS_PBLOCK /
    SDA_PALLAS_TILE env vars, else (16, None=auto).

    Env-only by design: library runtime behavior must not depend on the
    mutable committed sweep artifact (benchmarks/PALLAS_KNOBS.json).
    ``bench.py`` opts in to the file record via ``export_knobs_to_env``
    before running.
    """
    import os

    pb_env = os.environ.get("SDA_PALLAS_PBLOCK")
    tile_env = os.environ.get("SDA_PALLAS_TILE")
    return (int(pb_env) if pb_env else 16,
            int(tile_env) if tile_env else None)


def tile_from_sweep() -> bool:
    """True when SDA_PALLAS_TILE came from the sweep record (set by
    export_knobs_to_env) rather than an explicit user override. Sweep-sourced tiles were tuned at flagship widths, so small
    shapes may clamp them; explicit overrides are honored as-is."""
    import os

    return os.environ.get("SDA_PALLAS_TILE_SOURCE") == "sweep"


def export_knobs_to_env() -> dict:
    """Opt in to the committed hardware-sweep record: copy its knobs into
    the SDA_* env vars (where not already set by the user) so everything
    downstream — including library code that reads env-only pallas_knobs()
    — inherits the tuned values. Called by ``bench.py`` ONLY; plain
    library/test runs never see the file. Returns the record."""
    import os

    rec = _knobs_record()
    if isinstance(rec.get("p_block"), int):
        os.environ.setdefault("SDA_PALLAS_PBLOCK", str(rec["p_block"]))
    if isinstance(rec.get("tile"), int):
        if "SDA_PALLAS_TILE" not in os.environ:
            os.environ["SDA_PALLAS_TILE"] = str(rec["tile"])
            os.environ["SDA_PALLAS_TILE_SOURCE"] = "sweep"
    if isinstance(rec.get("stream_pc"), int):
        os.environ.setdefault("SDA_BENCH_STREAM_PC", str(rec["stream_pc"]))
    if isinstance(rec.get("dim_tile"), int):
        os.environ.setdefault("SDA_PALLAS_DIMTILE", str(rec["dim_tile"]))
    if rec.get("tree_fold") is True:
        os.environ.setdefault("SDA_PALLAS_TREEFOLD", "1")
    return rec


def tree_fold_knob() -> bool:
    """Dense-sublane tree fold inside the fused kernel:
    SDA_PALLAS_TREEFOLD env ("1" enables), default off. Env-only in
    library code like the other kernel knobs; the hardware A/B record's
    tree_fold verdict arrives via export_knobs_to_env at bench entry
    points. No-op (slice fold) when the effective p_block is not a power
    of two — results are bit-identical either way."""
    import os

    return os.environ.get("SDA_PALLAS_TREEFOLD") == "1"


#: default monolithic dim-tile width: 24-grain aligned, 3 tiles at the
#: flagship d=999999 with 9 padded columns (benchmarks/ROOFLINE.md
#: "Width": whether the full-width program is superlinear in d is open)
DEFAULT_DIM_TILE = 333336


def dim_tile_knob(default: int = DEFAULT_DIM_TILE):
    """Monolithic dim-tile width: SDA_PALLAS_DIMTILE env (0 disables
    tiling -> None), else ``default``. The hardware A/B record's dim_tile
    arrives via export_knobs_to_env at bench entry points."""
    import os

    env = os.environ.get("SDA_PALLAS_DIMTILE")
    val = int(env) if env else default
    return val if val > 0 else None


def stream_pc_knob(default: int = 64) -> int:
    """Streamed participant-chunk size: SDA_BENCH_STREAM_PC env (the
    hardware A/B record's stream_pc arrives via export_knobs_to_env at
    bench entry points), else ``default``."""
    import os

    env = os.environ.get("SDA_BENCH_STREAM_PC")
    return int(env) if env else default
