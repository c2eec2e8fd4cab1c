"""Fused Pallas kernel: mask + share + participant-combine, nothing per
participant in HBM.

Sharing is linear, so the clerk-combined shares of a round are
``M @ (Σ_p x_p + Σ_p mask_p ; Σ_p r_p)``. The callers fold the secrets
over the participants on the input's native ``[P, d]`` layout (one read of
the input, ``fastfield.modsum32``) and hand the kernel the folded secrets
in the column-per-batch layout ``[k, B]`` — 1/P of the input, so the
layout change in front of the kernel costs nothing. For each dimension
tile the kernel takes that block once, draws every participant's masks and
share randomness on-core (pltpu PRNG), folds the draws in VMEM and runs
the share contraction on the folds into ``[n, TB]`` accumulators. It reads
``[k, B]`` and writes accumulator-sized outputs; masks and share
randomness never touch HBM. The participants run as whole blocks of 16 and
one tail of what is left: one draw, one fold and one contraction a block,
whatever divides their number.

Algebra is the uint32 Solinas fast field (see fastfield.py — same bounds,
same helpers; fastfield's jnp ops compose inside Pallas kernels). The
share matrix M is host-side, so every multiply in the unrolled row loop is
a constant mulmod.

Randomness: `internal` mode uses the TPU per-core PRNG
(pltpu.prng_random_bits) seeded per (seed, dim tile); masks cancel within
the round, so the round stays exact. Nothing streams along the
participants there, so the grid is the dim tiles alone. `external` mode
takes pre-drawn bits as an input, streamed through VMEM a tile of
participants at a time along a second grid axis — it exists so the
arithmetic is bit-checkable under ``interpret=True`` on CPU (the TPU PRNG
primitive is hardware-only; each grid step folds the same blocks and tail
the chip folds) and is also what a protocol-grade deployment would use to
inject threefry/ChaCha streams (reference mask PRGs:
client/src/crypto/masking/*.rs).

Callers: ``mesh.simpod._pallas_stage``, the one stage around the kernel,
which ``simpod._RoundPlan`` makes the local step of every driver built
with ``use_pallas=True``. This module exports the kernel and its
column-tile rule and drives no round. The XLA step (``_scan_combine``)
stays the default of the library; the chip benchmark's packed cells all
run this kernel.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import fastfield
from .fastfield import SolinasPrime, canon32, modadd32, mulmod32_const

_U32 = jnp.uint32


def _uniform_from_bits(hi_bits, lo_bits, sp: SolinasPrime):
    """Two uint32 draws -> canonical uniform residue (fastfield.uniform32)."""
    hi = canon32(hi_bits, sp)
    lo = canon32(lo_bits, sp)
    r32 = (1 << 32) % sp.p
    return modadd32(mulmod32_const(hi, r32, sp), lo, sp)


def column_tile(B0: int) -> int:
    """Lane-dim tile for ``B0`` batch columns, whole 128-lane vregs: large
    tiles amortize the grid-step overhead, small B0 avoids padding waste.
    The caller pads the column axis to a whole number of tiles."""
    return 2048 if B0 >= 2048 else max(128, -(-B0 // 128) * 128)


def _participant_block(p_block: int, count: int):
    """(block, tail) for ``count`` participants: whole blocks of ``p_block``
    clamped to the count fold in the loop, and the ``count mod block`` left
    over fold once after it, in a draw of their own size. The
    accept-any-P contract: no draw is padded, and the block does not depend
    on the count's divisors."""
    pb = max(1, min(int(p_block), count))
    return pb, count % pb


def _participant_tile(P: int, rows_per_participant: int, tile: int) -> int:
    """External-bits mode: participants whose bits one step of the
    participant grid axis streams — the largest divisor of P whose
    ``rows_per_participant`` (2*draws) uint32 bit rows fit (double-buffered)
    ~3MB VMEM blocks. The internal-bits kernel streams nothing along the
    participants and has no such axis."""
    cap = max(1, 3_000_000 // (rows_per_participant * tile * 4))
    return next(c for c in range(min(P, cap), 0, -1) if P % c == 0)


def fused_mask_share_combine(
    x_sum,
    participants: int,
    seed,
    sp: SolinasPrime,
    m_host: np.ndarray,
    privacy_threshold: int,
    masked: bool,
    tile: int = 512,
    external_bits=None,
    interpret: bool = False,
    p_block: int = 16,
    p_tile: Optional[int] = None,
    tree_fold: bool = False,
):
    """[k, B] uint32 secrets folded over the participants (Σ_p x_p mod p,
    column-per-batch layout; canonicalized at first touch) and the number
    of ``participants`` P to draw for -> ([n, B] combined shares of all P,
    [k, B] mask totals).

    external_bits: optional [P, 2*(k+t) or 2*t, B] uint32 pre-drawn bits
    (2 words per drawn residue; mask rows first when masked) — used for
    interpret-mode tests and injectable PRG streams.

    The participants fold in whole blocks of ``p_block`` (clamped to their
    number) inside one loop — one PRNG draw and one share contraction a
    block — and those left over, ``P mod p_block``, in one tail step after
    it that draws for exactly that many (`_participant_block`): the block
    is the same whatever P's divisors are, and no participant is drawn for
    twice or in vain. With the on-core PRNG nothing streams along the
    participants, so the grid is the dim tiles alone, each seeded once
    with (seed, dim tile). External bits do stream: there a second,
    innermost grid axis takes ``p_tile`` participants' bits a step (a
    divisor of P; the largest the VMEM budget holds when None) onto the
    same output block, and each step folds its ``p_tile`` in blocks and a
    tail as above. The mod-p algebra is exact, so neither size ever
    changes results from the same bits; the on-core stream's share rows
    and mask totals depend on both the seeding and the draw shapes, and
    are pinned by nothing but their cancelling in the aggregate.

    ``tree_fold`` replaces the per-slice participant fold (adds on
    [rows, TB] slices, rows = k or t of 8 sublanes per vreg) with a
    halving tree over the flat [pb*rows, TB] block — every add at full
    sublane density, log2(pb) rounds. Bit-identical output (mod-p sums
    are order-free; canon cadence keeps raw partials < 2^32). Applied
    only to a block (or tail) of a power of two >= 2 participants;
    otherwise the slice fold runs as before.
    """
    P = int(participants)
    k, B = x_sum.shape
    n, m2 = m_host.shape
    t = privacy_threshold
    if m2 != 1 + k + t:
        raise ValueError(f"share matrix width {m2} != 1+k+t={1 + k + t}")
    if P < 1:
        raise ValueError(f"participants={P} must be at least 1")
    if B % tile:
        raise ValueError(f"B={B} must be divisible by tile={tile}")
    draws = (k + t) if masked else t
    internal = external_bits is None
    if internal:
        per_step = P
    else:
        if external_bits.shape != (P, 2 * draws, B):
            raise ValueError(
                f"external_bits {external_bits.shape} != "
                f"[P, 2*draws, B] = {(P, 2 * draws, B)}"
            )
        # external bits for all P in one block OOM VMEM beyond a few
        # hundred participants: they stream in tiles of p_tile along a
        # second (reduction) grid axis
        per_step = int(
            _participant_tile(P, 2 * draws, tile) if p_tile is None else p_tile)
        if P % per_step:
            raise ValueError(f"p_tile={per_step} must divide P={P}")
    pb, tail = _participant_block(p_block, per_step)

    def kernel(*refs):
        if internal:
            seed_ref, x_ref, mh_ref, ml_ref, shares_ref, masktot_ref = refs
            # one distinct stream per dim tile
            pltpu.prng_seed(seed_ref[0], pl.program_id(0))
        else:
            seed_ref, x_ref, mh_ref, ml_ref, bits_ref, shares_ref, masktot_ref = refs

        # raw uint32 partial sums stay exact for `fan` canonical residues
        fan = max(1, 0xFFFFFFFF // (sp.p - 1))
        # tree mode: raw-add levels between canons (2^L canonical terms
        # stay < 2^32)
        max_lvl = max(1, int(math.floor(math.log2(fan))))

        def fold_slices(get, count):
            """Σ of ``get(i)`` (canonical [r, TB]) for i < count: raw adds,
            canonicalizing every ``fan`` terms."""
            acc, partial, cnt = None, None, 0
            for i in range(count):
                sl = get(i)
                partial = sl if partial is None else partial + sl
                cnt += 1
                if cnt == fan or i == count - 1:
                    pc = canon32(partial, sp)
                    acc = pc if acc is None else modadd32(acc, pc, sp)
                    partial, cnt = None, 0
            return acc

        def tree_fold_block(arr, group_rows):
            """Σ of the stacked [group_rows, TB] slices in ``arr`` by
            halving the FULL block — dense sublanes, log2(m) rounds."""
            m = arr.shape[0] // group_rows
            lvl = 0
            while m > 1:
                h = m // 2
                arr = arr[: h * group_rows] + arr[h * group_rows:]
                m = h
                lvl += 1
                if lvl == max_lvl or m == 1:
                    arr = canon32(arr, sp)
                    lvl = 0
            return arr

        def draw_sum(rows, row0, p0, nb):
            """Σ over the ``nb`` participants from ``p0`` of [rows, TB]
            uniform residues."""
            # the slice fold applies when nb is not a power of two
            use_tree = tree_fold and nb >= 2 and (nb & (nb - 1)) == 0
            if internal:
                bits = pltpu.bitcast(
                    pltpu.prng_random_bits((2 * nb * rows, tile)), _U32
                )
                hi = bits[: nb * rows, :]
                lo = bits[nb * rows :, :]
                res = _uniform_from_bits(hi, lo, sp)          # [nb*rows, TB]
                if use_tree:
                    return tree_fold_block(res, rows)
                return fold_slices(
                    lambda i: res[i * rows: (i + 1) * rows], nb)
            blk = bits_ref[pl.ds(p0, nb)]                     # [nb, 2*draws, TB]
            hi = blk[:, 2 * row0 : 2 * row0 + rows, :]
            lo = blk[:, 2 * row0 + rows : 2 * (row0 + rows), :]
            res = _uniform_from_bits(hi, lo, sp)              # [nb, rows, TB]
            if use_tree:
                return tree_fold_block(res.reshape(nb * rows, tile), rows)
            return fold_slices(lambda i: res[i], nb)

        # matrix limb columns: first k drive the (masked) secrets, last t
        # the share randomness
        mh_k, mh_t = mh_ref[...][:, :k], mh_ref[...][:, k:]
        ml_k, ml_t = ml_ref[...][:, :k], ml_ref[...][:, k:]

        # share-combine is LINEAR: the clerk-combined output
        # Σ_p M @ values_p equals M @ (Σ_p values_p), so participants fold
        # with cheap adds FIRST and the matmul runs once per fold —
        # per-participant share rows are never materialized (in the
        # distributed protocol they live on the participants' own devices;
        # a chip computing the aggregate needs only their sum). Bit-exact
        # vs the per-participant XLA path given the same bits: mod-p
        # arithmetic is exact, so fold order is free.

        def start():
            # the outputs start from the share of the folded secrets.
            # canon at first touch: the contraction's limb bounds need
            # terms < p, and the docstring contract is otherwise unenforced
            shares_ref[...] = fastfield.modmatmul32_limbs(
                mh_k, ml_k, canon32(x_ref[...], sp), sp)      # [n, TB]
            masktot_ref[...] = jnp.zeros_like(masktot_ref)

        if internal:
            start()
        else:
            # the participant axis (grid dim 1) revisits the same output
            # block: the first visit starts it (the secrets' block index
            # ignores that axis: one fetch per dim tile), every visit
            # accumulates its tile's draws onto it
            pl.when(pl.program_id(1) == 0)(start)

        def fold_in(p0, nb):
            """The draws of the ``nb`` participants from ``p0`` (of this
            step's), shared and accumulated onto the outputs."""
            if masked:
                masksum = draw_sum(k, 0, p0, nb)              # [k, TB]
                masktot_ref[...] = modadd32(masktot_ref[...], masksum, sp)
                contrib = modadd32(
                    fastfield.modmatmul32_limbs(mh_k, ml_k, masksum, sp),
                    fastfield.modmatmul32_limbs(
                        mh_t, ml_t, draw_sum(t, k, p0, nb), sp),
                    sp,
                )                                             # [n, TB]
            else:
                contrib = fastfield.modmatmul32_limbs(
                    mh_t, ml_t, draw_sum(t, 0, p0, nb), sp)
            shares_ref[...] = modadd32(shares_ref[...], contrib, sp)

        def body(b_ix, carry):
            fold_in(b_ix * np.int32(pb), pb)
            return carry  # int32 zero: Mosaic cannot legalize an i64 carry

        # int32 bounds AND carry: under x64, Python-int bounds make the loop
        # index i64, which Mosaic cannot legalize
        jax.lax.fori_loop(
            jnp.int32(0), jnp.int32(per_step // pb), body, jnp.int32(0)
        )
        if tail:  # static: traced once, after the loop, at its own size
            fold_in(per_step - tail, tail)

    # host-side limb split of the active share-matrix columns (minus the
    # fixed zero column 0); tiny [n, m2-1] blocks, same in every grid step
    m_active = np.asarray(m_host)[:, 1:] % sp.p
    mh_np = (m_active >> 15).astype(np.uint32)
    ml_np = (m_active & 0x7FFF).astype(np.uint32)

    # grid dim 0: dim tiles. External bits add grid dim 1 (innermost): the
    # participant tiles, accumulated into the same output block; the index
    # maps of everything else ignore it
    def block(shape, index):
        return pl.BlockSpec(
            shape, lambda i, *j: index(i), memory_space=pltpu.VMEM)

    grid = (B // tile,)
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),                     # seed
        block((k, tile), lambda i: (0, i)),
        block(mh_np.shape, lambda i: (0, 0)),
        block(ml_np.shape, lambda i: (0, 0)),
    ]
    args = [jnp.asarray([seed], jnp.int32), x_sum,
            jnp.asarray(mh_np), jnp.asarray(ml_np)]
    if not internal:
        grid += (P // per_step,)
        in_specs.append(
            pl.BlockSpec((per_step, 2 * draws, tile), lambda i, j: (j, 0, i),
                         memory_space=pltpu.VMEM)
        )
        args.append(external_bits)
    out_specs = [
        block((n, tile), lambda i: (0, i)),
        block((k, tile), lambda i: (0, i)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((n, B), _U32),
        jax.ShapeDtypeStruct((k, B), _U32),
    ]
    call = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )
    # trace the kernel with x64 OFF: under the framework's global x64 the
    # BlockSpec index maps and loop indices become i64, which Mosaic cannot
    # legalize (func.return (i64) lowering error on real TPU); every value
    # in the kernel is explicitly uint32/int32 so semantics are unchanged.
    with jax.enable_x64(False):
        return call(*args)
