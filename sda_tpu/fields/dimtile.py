"""Dim-tiled round schedule: lax.scan over fixed-width dimension tiles.

A full-width program's intermediates grow with d and may spill where a
narrower one stays fused (benchmarks/ROOFLINE.md "Width" — whether the
full-width round is superlinear in d on the chip is not measured).
Scanning fixed-width tiles bounds every tile's live set and makes round
cost affine in d by construction. Shared by the XLA round
(mesh.single_chip_round) and the model-scale sharded driver
(mesh/devscale.py: the scan, and :func:`tile_plan` for its host-driven
lane), so every tiled lane slices the dimension with ONE arithmetic.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class TilePlan(NamedTuple):
    """The fixed-width tiling of a dimension: THE schedule arithmetic,
    shared by the in-program scan below and the host-driven model-scale
    loop (mesh/devscale.py) so the two lanes cannot drift.

    ``width``   — the grain-rounded tile width actually used;
    ``n_tiles`` — number of tiles covering the (padded) dimension;
    ``pad``     — zero columns appended so ``n_tiles * width`` covers
                  ``dim`` (zero columns aggregate as zero and are
                  sliced off the output).
    """

    width: int
    n_tiles: int
    pad: int

    @property
    def padded_dim(self) -> int:
        return self.n_tiles * self.width


def tile_plan(dim: int, grain: int, dim_tile: int) -> TilePlan:
    """Fixed-width tiling of ``dim`` at the requested ``dim_tile`` width.

    The width is rounded UP to a whole multiple of ``grain`` (whole
    packing columns x whole ChaCha blocks — a tile must be a complete
    round over its own columns). A dimension narrower than one tile is
    a single tile of its own grain-rounded width: a wide tile knob must
    not inflate small shapes.
    """
    if dim_tile <= 0:
        raise ValueError(f"dim_tile must be positive, got {dim_tile}")
    if grain <= 0:
        raise ValueError(f"grain must be positive, got {grain}")
    T = -(-int(dim_tile) // grain) * grain
    if dim < T:
        width = -(-int(dim) // grain) * grain
        return TilePlan(width, 1, width - dim)
    n_tiles = -(-dim // T)
    return TilePlan(T, n_tiles, n_tiles * T - dim)


def scan_dim_tiles(one_tile, grain: int, dim_tile: int):
    """Wrap a per-tile round into a full-round function.

    ``one_tile(blk, round_key, tile_key, tile_idx, width)`` computes a
    complete round over ``blk`` ([P, width] raw inputs) and returns the
    [width] int64 aggregate; ``tile_idx`` may be traced. ``grain`` is the
    tile-width quantum (whole packing columns x whole ChaCha blocks).

    Returns ``round_fn(inputs, key)``. Inputs narrower than one tile run
    ``one_tile`` directly (no pad/scan machinery — a wide tile knob must
    not inflate small shapes); everything else runs the scan, INCLUDING
    the exactly-one-tile case, so timing points at 1, 2, ... tiles all
    measure the same schedule (a fit mixing the untiled program into its
    first point would misclassify the tiled schedule).
    """
    if dim_tile <= 0:
        raise ValueError(f"dim_tile must be positive, got {dim_tile}")
    T = -(-int(dim_tile) // grain) * grain

    def round_fn(inputs, key):
        P, d = inputs.shape
        if d < T:
            return one_tile(inputs, key, key, jnp.int32(0), d)
        plan = tile_plan(d, grain, T)
        if plan.pad:  # zero columns aggregate as zero; sliced off below
            inputs = jnp.pad(inputs, ((0, 0), (0, plan.pad)))
        xt = jnp.moveaxis(
            inputs.reshape(P, plan.n_tiles, plan.width), 1, 0)
        # [n_tiles, P, T]

        def body(_, blk_i):
            blk, i = blk_i
            # fold_in keeps tile randomness streams distinct (exactness
            # never depends on it — masks cancel and random polynomial
            # rows are annihilated by reconstruction)
            return None, one_tile(
                blk, key, jax.random.fold_in(key, i), i, plan.width)

        _, tiles = jax.lax.scan(
            body, None, (xt, jnp.arange(plan.n_tiles, dtype=jnp.int32)))
        return tiles.reshape(-1)[:d]

    return round_fn
