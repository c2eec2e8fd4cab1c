"""Device-side ChaCha20 mask expansion (CHACHA_PRG_V1, bit-exact).

SURVEY.md hard part (e): the ChaCha-seed masking path must stay
wire-compatible while the recipient's mask re-expansion — the reference's
recipient hot loop, O(participants x dimension) PRG work
(client/src/receive.rs:102-118) — moves onto the TPU. ChaCha20 is pure
uint32 add/xor/rotate, ideal VPU work: all blocks advance through the 20
rounds in parallel lanes.

Bit-exactness with the host spec (fields.chacha) includes its *rejection
sampling*: a u64 draw above the acceptance zone shifts every later output.
Rejection is data-dependent and therefore unjittable — but its probability
is < modulus/2^64 (< 2^-35 per draw). So the device path expands without
rejection, simultaneously checks whether any of the first `dimension`
draws would have been rejected, and in that (practically never hit) case
the caller replays on the host oracle. Outputs are identical to
``chacha.expand_mask`` in every case.
"""

from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import chacha, layout
from .chacha import _CONSTANTS

_U32 = jnp.uint32


# The rounds are written in lax ops: the same equations as the operators
# (``x << n | x >> 32 - n``, ``+``, ``^``), without the array API's own
# dispatch on every one of the twenty rounds' 1,600 -- a round's trace,
# which every warm start pays again, is mostly theirs


def _rotl(x, n: int):
    return lax.bitwise_or(lax.shift_left(x, _U32(n)),
                          lax.shift_right_logical(x, _U32(32 - n)))


def _quarter(s, a, b, c, d):
    s[a] = lax.add(s[a], s[b])
    s[d] = _rotl(lax.bitwise_xor(s[d], s[a]), 16)
    s[c] = lax.add(s[c], s[d])
    s[b] = _rotl(lax.bitwise_xor(s[b], s[c]), 12)
    s[a] = lax.add(s[a], s[b])
    s[d] = _rotl(lax.bitwise_xor(s[d], s[a]), 8)
    s[c] = lax.add(s[c], s[d])
    s[b] = _rotl(lax.bitwise_xor(s[b], s[c]), 7)


def double_round(state):
    """Two of the block function's twenty rounds, a column round and a
    diagonal round, on the sixteen state words (a list, updated in place
    and returned). The XLA block function runs it ten times in Python;
    the on-core cipher (``chacha_kernel``) runs it in a ten-step loop."""
    _quarter(state, 0, 4, 8, 12)
    _quarter(state, 1, 5, 9, 13)
    _quarter(state, 2, 6, 10, 14)
    _quarter(state, 3, 7, 11, 15)
    _quarter(state, 0, 5, 10, 15)
    _quarter(state, 1, 6, 11, 12)
    _quarter(state, 2, 7, 8, 13)
    _quarter(state, 3, 4, 9, 14)
    return state


def _block_word_arrays(seed_words, counter0, nblocks: int):
    """The block function as it computes: sixteen ``[nblocks]`` uint32
    arrays, word ``i`` of every block in array ``i`` (block index minor)."""
    counters = jnp.asarray(counter0, _U32) + jnp.arange(nblocks, dtype=_U32)
    zeros = jnp.zeros((nblocks,), _U32)
    init = (
        [jnp.full((nblocks,), _U32(c)) for c in _CONSTANTS]
        + [jnp.broadcast_to(seed_words[i], (nblocks,)).astype(_U32) for i in range(8)]
        + [counters, zeros, zeros, zeros]
    )
    state = list(init)
    for _ in range(10):
        double_round(state)
    return [s + i for s, i in zip(state, init)]


@functools.partial(jax.jit, static_argnames=("nblocks",))
def chacha_block_words(seed_words, counter0, *, nblocks: int):
    """[nblocks, 16] uint32 keystream; mirrors chacha.chacha_block_words.

    seed_words: [8] uint32 key (zero-padded); counter0: scalar int32/uint32.
    """
    return jnp.stack(_block_word_arrays(seed_words, counter0, nblocks), axis=1)


def _paired_u64(words, *, even_is_low: bool):
    """Sixteen per-word arrays -> the blocks' 64-bit draws, word-major
    ``[8, nblocks]``: draw ``j`` of a block pairs words ``2j`` and ``2j+1``.
    The halves are picked from the Python list (no device op); which of the
    two is the low half is the stream's (V1: even, rand 0.3: odd). One
    stack of all sixteen, halves as its two contiguous slabs: stacked
    apart, XLA's CPU fusion clones the whole cipher into each stack."""
    halves = jnp.stack(words[0::2] + words[1::2]).astype(jnp.uint64)
    even, odd = halves[:8], halves[8:]
    low, high = (even, odd) if even_is_low else (odd, even)
    return (high << jnp.uint64(32)) | low


def element_order(x):
    """Word-major ``[..., 8, nblocks]`` -> element order ``[..., 8 * nblocks]``
    (element ``e = 8 * block + pair``), the order of the host stream.

    The interleave of eight rows along the minor axis, through the matrix
    unit: ``fields.layout.interleave``, which the packed scheme's column
    layout shares.
    """
    return layout.interleave(x)


@functools.partial(jax.jit, static_argnames=("dimension", "modulus", "prg"))
def _expand_no_reject(seed_words, *, dimension: int, modulus: int, prg: str):
    """(mask [dimension] int64, any_rejected bool) — fast path.

    ``prg`` selects the stream: CHACHA_PRG_V1 (word[2i] = low half, zone
    floor(2^64/m)*m inclusive-below) or CHACHA_PRG_RAND03 (rand 0.3's
    next_u64: word[2i] = HIGH half, zone u64::MAX - u64::MAX % m
    exclusive — see fields.chacha.expand_mask_rand03).
    """
    # match the host oracle's first-iteration overdraw: ceil(d/8)+1 blocks
    nblocks = max(1, -(-dimension // 8) + 1)
    if prg not in (chacha.CHACHA_PRG_V1, chacha.CHACHA_PRG_RAND03):
        raise ValueError(f"unknown ChaCha PRG {prg!r}")
    words = _block_word_arrays(seed_words, 0, nblocks)
    v = element_order(_paired_u64(words, even_is_low=prg == chacha.CHACHA_PRG_V1))
    first = v[:dimension]
    if prg == chacha.CHACHA_PRG_RAND03:
        u64_max = (1 << 64) - 1
        zone_excl = jnp.uint64(u64_max - u64_max % modulus)
        any_rejected = jnp.any(first >= zone_excl)
    else:
        zone = jnp.uint64(((1 << 64) // modulus) * modulus - 1)
        any_rejected = jnp.any(first > zone)
    mask = jnp.mod(first, jnp.uint64(modulus)).astype(jnp.int64)
    return mask, any_rejected


def stream_u64_words_at(seed_words, counter0, *, nblocks: int):
    """[S, 8] uint32 seeds -> [S, 8, nblocks] uint64: the CHACHA_PRG_V1
    draws of blocks [counter0, counter0 + nblocks) in the layout the block
    function produces them, word-major with the block index minor:
    ``out[s, j, b]`` is draw ``8 * (counter0 + b) + j`` of seed ``s``'s
    stream. No layout change: whatever is elementwise in the draws (the
    reduction mod m, and the pod's fold of the residues over the seeds'
    rows: ``simpod._chacha_mask_fold``) runs on this form, and
    ``element_order`` puts the result -- one narrow plane instead of the
    draws' two, and one row instead of the seeds' S -- in the stream's
    order. ``counter0`` may be traced."""
    return jax.vmap(
        lambda sw: _paired_u64(
            _block_word_arrays(sw, counter0, nblocks), even_is_low=True)
    )(seed_words)


def stream_u64_at(seed_words, counter0, *, dimension: int):
    """[S, 8] uint32 seeds -> [S, dimension] uint64 stream draws starting at
    u64-draw offset ``counter0 * 8`` (``dimension % 8 == 0``).

    The windowed form of the CHACHA_PRG_V1 stream for dim-sharded pod mode:
    each ChaCha block yields 8 u64 draws, so a device holding the dim window
    [8*c0, 8*c0 + dimension) expands blocks [c0, c0 + dimension/8).
    ``counter0`` may be traced (it is ``axis_index('d') * blocks_per_shard``
    under shard_map). Pod mode reduces draws mod m WITHOUT the host spec's
    rejection step — masks cancel within the round, so the aggregate is
    exact regardless; only the federated wire path needs rejection parity.

    The element-order view of ``stream_u64_words_at``: the round takes the
    word-major form, folds its residues over the participants and orders
    the fold (simpod._chacha_mask_sum).
    """
    if dimension % 8:
        raise ValueError("dimension must be a multiple of 8 (one ChaCha block)")
    return element_order(
        stream_u64_words_at(seed_words, counter0, nblocks=dimension // 8))


def _modsum_i64(x, modulus: int, axis: int = 0):
    """Overflow-safe modular sum of int64 residues in [0, modulus).

    A flat ``sum() % m`` wraps int64 once n*(m-1) >= 2^63 (e.g. ~16k seeds
    at a 2^49 modulus); fold in chunks small enough that every partial sum
    provably fits, canonicalizing between levels — same shape of fix as
    fastfield.modsum32.
    """
    fan = max(2, ((1 << 63) - 1) // max(1, modulus - 1))
    x = jnp.moveaxis(jnp.asarray(x, jnp.int64), axis, 0)
    if x.shape[0] == 0:  # empty sum is the zero mask, like jnp.sum(axis=0)
        return jnp.zeros(x.shape[1:], jnp.int64)
    while x.shape[0] > 1:
        n = x.shape[0]
        chunk = min(fan, n)
        pad = (-n) % chunk
        if pad:
            x = jnp.concatenate(
                [x, jnp.zeros((pad,) + x.shape[1:], jnp.int64)], axis=0
            )
        x = x.reshape((x.shape[0] // chunk, chunk) + x.shape[1:])
        x = jnp.mod(jnp.sum(x, axis=1, dtype=jnp.int64), modulus)
    return x[0]


@functools.partial(jax.jit, static_argnames=("dimension", "modulus", "prg"))
def _combine_no_reject(seed_matrix, *, dimension: int, modulus: int, prg: str):
    """[S, 8] seeds -> (sum of masks mod m [dimension] int64, [S] rejected)."""
    masks, rejected = jax.vmap(
        lambda sw: _expand_no_reject(
            sw, dimension=dimension, modulus=modulus, prg=prg
        )
    )(seed_matrix)
    total = _modsum_i64(masks, modulus, axis=0)
    return total, rejected


def combine_masks(
    seeds, dimension: int, modulus: int, *, prg: str
) -> np.ndarray:
    """Sum of all seeds' expanded masks mod m — the recipient hot loop
    (receive.rs:102-118), every seed's 20-round expansion in parallel lanes.
    Bit-identical to summing the host expansion (``prg``-selected) per seed.
    ``prg`` is required: a defaulted stream choice could silently expand the
    wrong stream for a wire seed."""
    if modulus <= 0 or modulus >= (1 << 62):
        raise ValueError("modulus out of range")
    if prg not in chacha._EXPANDERS:
        raise ValueError(f"unknown ChaCha PRG {prg!r}")
    seed_matrix = np.zeros((len(seeds), 8), dtype=np.uint32)
    for i, seed in enumerate(seeds):
        if len(seed) > 8:
            raise ValueError("seed longer than 256 bits")
        for j, w in enumerate(seed):
            seed_matrix[i, j] = np.uint32(int(w) & 0xFFFFFFFF)
    total, rejected = _combine_no_reject(
        jnp.asarray(seed_matrix), dimension=dimension, modulus=modulus, prg=prg
    )
    rejected = np.asarray(rejected)
    if rejected.any():  # replay the affected seeds exactly on the host
        total = np.asarray(total, dtype=np.int64)
        for i in np.nonzero(rejected)[0]:
            seed = [int(w) for w in seeds[i]]
            wrong, _ = _expand_no_reject(
                jnp.asarray(seed_matrix[i]), dimension=dimension,
                modulus=modulus, prg=prg,
            )
            right = chacha.expand_mask_for(prg, seed, dimension, modulus)
            total = (total - np.asarray(wrong) + right) % modulus
        return total
    return np.asarray(total)


def expand_mask(
    seed: Sequence[int], dimension: int, modulus: int, *, prg: str
) -> np.ndarray:
    """Drop-in device-accelerated chacha.expand_mask / expand_mask_rand03
    (bit-identical to the ``prg``-selected host expansion; ``prg`` required
    for the same reason as combine_masks)."""
    if modulus <= 0 or modulus >= (1 << 62):
        raise ValueError("modulus out of range")
    if prg not in chacha._EXPANDERS:
        raise ValueError(f"unknown ChaCha PRG {prg!r}")
    if len(seed) > 8:
        raise ValueError("seed longer than 256 bits")
    seed_words = np.zeros(8, dtype=np.uint32)
    for i, w in enumerate(seed):
        seed_words[i] = np.uint32(w & 0xFFFFFFFF)
    mask, any_rejected = _expand_no_reject(
        jnp.asarray(seed_words), dimension=dimension, modulus=modulus, prg=prg
    )
    if bool(any_rejected):  # p < dimension * modulus / 2^64 — practically never
        return chacha.expand_mask_for(prg, seed, dimension, modulus)
    return np.asarray(mask)
