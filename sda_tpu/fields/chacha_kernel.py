"""The ChaCha masks of a block of participants, summed, from one Pallas
TPU kernel: ``mask_fold``.

A pod's mask stage needs of a block of participant rows only the sum of
their masks (Σ (x + m) = Σ x + Σ m: ``mesh.simpod._chacha_mask_fold``). In
XLA the block function runs as some thirty fusions a block of rows, each
a pass over the sixteen word planes in HBM, and the compiler stacks and
copies the words besides. Here one grid step holds the sixteen state
words of 3072 cipher blocks as ``[24, 128]`` uint32 vectors, three vector
registers each, and nothing but the sum leaves the core:

- the twenty rounds run as a ``fori_loop`` over the ten double rounds
  (``chacha_jax.double_round``), so the kernel body traces to a few
  hundred equations whatever the number of rows;
- words ``2j`` and ``2j + 1`` are draw ``j``'s low and high halves
  (CHACHA_PRG_V1), reduced modulo p from the two halves in 32-bit lanes
  (``pallas_round._uniform_from_bits``, which is ``fastfield.reduce64``):
  Mosaic has no 64-bit integers;
- the reduced draws of every row are added to a canonical running sum in
  the output block, one conditional subtraction a row; the sixteen words
  wait in a VMEM scratch for the reduction, which a loop over the eight
  draws traces once and Mosaic unrolls.

The result is, bit for bit, ``f.sum(f.from_u64(chacha_jax.
stream_u64_words_at(seeds, counter0, nblocks=nblocks)), axis=0)``: the same
stream, every draw, no rejection step (a pod's masks cancel inside the
round). The grid runs over the vectors, one a step; the block count is
padded up to whole vectors and the pad sliced off.

Callers: ``mesh.simpod``'s mask stages, where the step is built for a
TPU and the field is a uint32 Solinas field (``_chacha_cipher``); the XLA
block function stays everywhere else and is the kernel's oracle
(tests/test_chacha_kernel.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import chacha_jax
from .chacha import _CONSTANTS
from .fastfield import SolinasPrime, modadd32
from .pallas_round import _uniform_from_bits

_U32 = jnp.uint32
#: a word's vector: 24 sublanes x 128 lanes, three vector registers, so
#: that three blocks' worth of every step of a round are in flight at once
#: (with one register a word each step waits on the one before; with four
#: the sixteen words spill): on a v5e at 1200 rows x 125,001 blocks,
#: 1.80e9 blocks/s at 8 sublanes, 2.47e9 at 16, 2.53e9 at 24 and 2.35e9
#: at 32 (PERF.md)
_SUB, _LANES = 24, 128
#: cipher blocks one word's vector holds
_VECTOR = _SUB * _LANES


def mask_fold(seed_words, counter0, *, nblocks: int, sp: SolinasPrime,
              interpret: bool = False):
    """[rows, 8] uint32 seed words, block counter ``counter0`` (may be
    traced) -> [8, nblocks] uint32: the sum over the rows of each one's
    CHACHA_PRG_V1 draws of blocks ``[counter0, counter0 + nblocks)``
    modulo ``sp.p``, canonical, word-major (``out[j, b]`` sums draw
    ``8 * (counter0 + b) + j``)."""
    rows = int(seed_words.shape[0])
    steps = -(-int(nblocks) // _VECTOR)

    def kernel(counter_ref, seeds_ref, out_ref, words_ref):
        # the block counter of every lane of the step's vector
        counter = jax.lax.bitcast_convert_type(
            counter_ref[0] + pl.program_id(0) * _VECTOR
            + jax.lax.broadcasted_iota(jnp.int32, (_SUB, _LANES), 0) * _LANES
            + jax.lax.broadcasted_iota(jnp.int32, (_SUB, _LANES), 1), _U32)
        out_ref[...] = jnp.zeros(out_ref.shape, _U32)

        def splat(word):  # a scalar in every lane, its bits read as uint32
            return jax.lax.bitcast_convert_type(
                jax.lax.broadcast(word, (_SUB, _LANES)), _U32)

        def draw(j, carry):
            # words 2j, 2j + 1: draw j's low and high halves
            value = _uniform_from_bits(words_ref[2 * j + 1], words_ref[2 * j], sp)
            out_ref[j] = modadd32(out_ref[j], value, sp)
            return carry

        def row(r, carry):
            init = ([splat(np.int32(c)) for c in _CONSTANTS.view(np.int32)]
                    + [splat(seeds_ref[r * 8 + w]) for w in range(8)]
                    + [counter] + [splat(np.int32(0))] * 3)
            state = jax.lax.fori_loop(
                0, 10, lambda _, s: tuple(chacha_jax.double_round(list(s))),
                tuple(init))
            for i, (word, start) in enumerate(zip(state, init)):
                words_ref[i] = jax.lax.add(word, start)
            # one draw's reduction traced, and lowered eight times: as a
            # loop, each draw's reduction would wait for the one before
            return jax.lax.fori_loop(0, 8, draw, carry, unroll=True)

        jax.lax.fori_loop(0, rows, row, jnp.int32(0))

    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(steps,),
            in_specs=[],
            out_specs=pl.BlockSpec((8, _SUB, _LANES), lambda i, *_: (0, i, 0)),
            scratch_shapes=[pltpu.VMEM((16, _SUB, _LANES), _U32)],
        ),
        out_shape=jax.ShapeDtypeStruct((8, steps * _SUB, _LANES), _U32),
        interpret=interpret,
        name="sda_chacha_mask_fold",
    )
    counter = jax.lax.bitcast_convert_type(
        jnp.asarray(counter0, _U32).reshape(1), jnp.int32)
    seeds = jax.lax.bitcast_convert_type(
        jnp.asarray(seed_words, _U32).reshape(-1), jnp.int32)
    # traced with x64 off, as the share kernel is (pallas_round.py): under
    # the global x64 the grid's indices and the loops' would be i64, which
    # Mosaic cannot legalize
    with jax.enable_x64(False):
        out = call(counter, seeds)
    return out.reshape(8, -1)[:, :nblocks]
