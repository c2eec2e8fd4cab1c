"""The plain reference of a secure sum under additive n-of-n sharing and
ChaCha seed masks (upstream ``integration-tests/tests/full_loop.rs``
L11-L32 with L42-L52). NumPy only; nothing of the program is imported.

Three parts:

- :func:`on_device` / :func:`on_host` -- what a round must reveal: the sum
  of the inputs modulo the modulus, bit for bit (as ``modsum.py``).
- :func:`chacha20_block` and :func:`mask_stream` -- ChaCha20 (RFC 7539
  block function, 20 rounds) written out, and the rule by which a pod
  turns a participant's seed into its mask: the key is the seed's 32-bit
  words zero-padded to 8, the nonce is zero, the block counter is the
  draw offset divided by 8 (a block of 16 words gives 8 draws), draw ``i``
  of a block is ``word[2i]`` (low half) | ``word[2i+1]`` (high half) as
  one unsigned 64-bit number, and the mask is that number modulo the
  modulus.
- :func:`plain_round` -- the round written out: mask, n - 1 uniform share
  rows and the last by subtraction, each clerk's sum over the
  participants, the reveal (the sum of the clerk rows), the unmask.

Where this departs from upstream, on purpose:

- **No rejection step.** Upstream's masker draws ``u64`` values through
  ``rand``'s uniform range sampler, which rejects a draw above the
  largest multiple of the modulus (probability < modulus / 2^64 a draw)
  and so shifts every later draw. A pod generates and cancels its masks
  inside one round and they never travel, so the pod reduces without
  rejecting, and so does this file. The wire path, where a recipient must
  re-expand the same masks a participant drew, keeps rejection parity;
  that is held by the program's own ``tests/test_chacha_jax.py``, not
  here.
- **The share rows' randomness is the caller's.** Upstream draws each
  share from the operating system's generator; a reference cannot repeat
  those draws, and the revealed sum does not depend on them.
  :func:`plain_round` takes a NumPy ``Generator``.
"""

from __future__ import annotations

import numpy as np

#: "expand 32-byte k", the four constant words of the ChaCha state
CONSTANTS = (0x61707865, 0x3320646E, 0x79622D32, 0x6B206574)

_MASK32 = np.uint64(0xFFFFFFFF)


# -- what the round must reveal ------------------------------------------------

def on_device(inputs, modulus: int):
    """``[P, d]`` non-negative integers on the device -> ``[d]`` int64."""
    import jax.numpy as jnp

    return jnp.sum(inputs.astype(jnp.int64), axis=0) % modulus


def on_host(inputs, modulus: int):
    """``[P, d]`` integers in host memory -> ``[d]`` int64 (NumPy)."""
    return np.asarray(inputs, dtype=np.int64).sum(axis=0) % modulus


# -- ChaCha20 ------------------------------------------------------------------

def _rotl(x, n: int):
    # uint64 lanes holding 32-bit values: shift, fold the carry back in
    return ((x << np.uint64(n)) | (x >> np.uint64(32 - n))) & _MASK32


def _quarter(s, a: int, b: int, c: int, d: int) -> None:
    s[a] = (s[a] + s[b]) & _MASK32
    s[d] = _rotl(s[d] ^ s[a], 16)
    s[c] = (s[c] + s[d]) & _MASK32
    s[b] = _rotl(s[b] ^ s[c], 12)
    s[a] = (s[a] + s[b]) & _MASK32
    s[d] = _rotl(s[d] ^ s[a], 8)
    s[c] = (s[c] + s[d]) & _MASK32
    s[b] = _rotl(s[b] ^ s[c], 7)


def chacha20_block(key_words, counters) -> np.ndarray:
    """``[len(counters), 16]`` uint32 keystream words: one ChaCha20 block
    per 32-bit block counter, under the 8-word key and a zero nonce."""
    key_words = [int(w) & 0xFFFFFFFF for w in key_words]
    if len(key_words) != 8:
        raise ValueError("a ChaCha20 key is 8 words of 32 bits")
    counters = np.asarray(counters, dtype=np.uint64) & _MASK32
    lanes = counters.shape[0]
    init = ([np.full(lanes, c, np.uint64) for c in CONSTANTS]
            + [np.full(lanes, w, np.uint64) for w in key_words]
            + [counters] + [np.zeros(lanes, np.uint64) for _ in range(3)])
    state = [column.copy() for column in init]
    for _ in range(10):  # 10 double rounds: 4 column + 4 diagonal quarters
        _quarter(state, 0, 4, 8, 12)
        _quarter(state, 1, 5, 9, 13)
        _quarter(state, 2, 6, 10, 14)
        _quarter(state, 3, 7, 11, 15)
        _quarter(state, 0, 5, 10, 15)
        _quarter(state, 1, 6, 11, 12)
        _quarter(state, 2, 7, 8, 13)
        _quarter(state, 3, 4, 9, 14)
    words = [(s + i) & _MASK32 for s, i in zip(state, init)]
    return np.stack(words, axis=1).astype(np.uint32)


def mask_stream(seed_words, first_draw: int, count: int, modulus: int) -> np.ndarray:
    """Draws ``first_draw .. first_draw + count`` of one participant's
    mask, ``[count]`` int64 in ``[0, modulus)``. ``seed_words``: the
    seed's 32-bit words, at most 8, zero-padded to the key."""
    seed_words = list(seed_words)
    if len(seed_words) > 8:
        raise ValueError("a seed is at most 256 bits")
    if first_draw < 0 or count < 0:
        raise ValueError("draw window out of range")
    key = seed_words + [0] * (8 - len(seed_words))
    first_block = first_draw // 8
    blocks = -(-(first_draw + count) // 8) - first_block
    words = chacha20_block(key, first_block + np.arange(max(blocks, 0)))
    words = words.reshape(-1).astype(np.uint64)
    draws = (words[1::2] << np.uint64(32)) | words[0::2]
    skip = first_draw - 8 * first_block
    window = draws[skip:skip + count]
    return (window % np.uint64(modulus)).astype(np.int64)


# -- the round -----------------------------------------------------------------

def plain_round(inputs, seeds, share_count: int, modulus: int,
                rng: np.random.Generator) -> dict:
    """One additive-sharing round with ChaCha seed masks, step by step.

    ``inputs``: ``[P, d]`` integers; ``seeds``: ``[P, <=8]`` seed words,
    one seed per participant. Returns the revealed ``aggregate`` ``[d]``
    with what a test wants to look at on the way: ``masks`` ``[P, d]``,
    ``clerk_rows`` ``[n, d]`` (each clerk's sum of the shares it was
    sent) and ``mask_total`` ``[d]``."""
    inputs = np.asarray(inputs, dtype=np.int64) % modulus
    participants, dim = inputs.shape
    masks = np.stack([mask_stream(seed, 0, dim, modulus) for seed in seeds])
    masked = (inputs + masks) % modulus
    clerk_rows = np.zeros((share_count, dim), np.int64)
    for row in masked:  # one participant at a time, as a participant would
        free = rng.integers(0, modulus, size=(share_count - 1, dim), dtype=np.int64)
        last = (row - free.sum(axis=0)) % modulus
        shares = np.concatenate([free, last[None, :]], axis=0)
        clerk_rows = (clerk_rows + shares) % modulus
    revealed = clerk_rows.sum(axis=0) % modulus          # n-of-n: every row
    mask_total = masks.sum(axis=0) % modulus
    return {"aggregate": (revealed - mask_total) % modulus, "masks": masks,
            "clerk_rows": clerk_rows, "mask_total": mask_total}
