"""The plain reference of a secure sum under packed Shamir sharing and
ChaCha seed masks: the two schemes upstream's ``Aggregation`` declares
independently (``protocol/src/resources.rs#L44-L67``), the ChaCha masker
of ``integration-tests/tests/full_loop.rs#L42-L52`` over the packed scheme
of ``#L54-L67``. NumPy and Python integers only; nothing of the program,
and nothing of the other references, is imported.

Three parts:

- :func:`on_device` / :func:`on_host` -- what a round must reveal: the sum
  of the inputs modulo the modulus, bit for bit.
- :func:`chacha20_block` and :func:`mask_stream` -- ChaCha20 (RFC 7539
  block function, 20 rounds) written out, and the rule by which a pod
  turns a participant's seed into its mask: the key is the seed's 32-bit
  words zero-padded to 8, the nonce is zero, the block counter is the
  draw offset divided by 8 (a block of 16 words gives 8 draws), draw ``i``
  of a block is ``word[2i]`` (low half) | ``word[2i+1]`` (high half) as
  one unsigned 64-bit number, and the mask is that number modulo the
  modulus.
- :func:`plain_round` -- the round written out: every row masked with its
  stream, every masked row shared by the packed scheme (one polynomial of
  degree at most ``k + t`` a batch of ``k`` secrets: 0 at ``w2^0``, the
  secrets at ``w2^1..k``, uniform values at ``w2^(k+1..k+t)``; clerk ``i``
  holds its value at ``w3^i``), each clerk's sum over the participants,
  the reveal from all ``n`` clerk rows by the Lagrange basis, the masks'
  sum subtracted.

Where this departs from upstream, on purpose:

- **No rejection step.** Upstream's masker draws ``u64`` values through
  ``rand``'s uniform range sampler, which rejects a draw above the largest
  multiple of the modulus and so shifts every later draw. A pod generates
  and cancels its masks inside one round and they never travel, so the pod
  reduces without rejecting, and so does this file.
- **The share polynomials' randomness is the caller's.** Upstream draws
  it from the operating system's generator; a reference cannot repeat
  those draws, and the revealed sum does not depend on them.
  :func:`plain_round` takes a NumPy ``Generator``.
- **The pairing itself.** Upstream's golden test runs each scheme alone;
  the ``Aggregation`` resource lets a recipient declare both.
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


# -- the packed scheme ---------------------------------------------------------

def root_of_unity(order: int, prime_factor: int, modulus: int) -> int:
    """The first ``g^((modulus - 1) / order)``, ``g = 2, 3, ...``, whose
    order is exactly ``order`` (a power of ``prime_factor``)."""
    if (modulus - 1) % order:
        raise ValueError(f"{modulus} - 1 is not a multiple of {order}")
    for g in range(2, modulus):
        w = pow(g, (modulus - 1) // order, modulus)
        if pow(w, order // prime_factor, modulus) != 1:
            return w
    raise ValueError(f"no element of order {order} modulo {modulus}")


def lagrange_matrix(points, targets, modulus: int) -> np.ndarray:
    """``matrix[i, j]`` = the ``j``-th Lagrange basis polynomial of
    ``points`` at ``targets[i]``: values at ``points`` -> values at
    ``targets`` of the one polynomial of degree < ``len(points)``."""
    matrix = []
    for x in targets:
        row = []
        for j, xj in enumerate(points):
            numerator = denominator = 1
            for m, xm in enumerate(points):
                if m != j:
                    numerator = numerator * (x - xm) % modulus
                    denominator = denominator * (xj - xm) % modulus
            row.append(numerator * pow(denominator, -1, modulus) % modulus)
        matrix.append(row)
    return np.asarray(matrix, dtype=np.int64)


def scheme_points(scheme: dict, modulus: int):
    """``(value points, share points)`` of the configuration's ``scheme``
    block: the powers ``0..k+t`` of ``w2`` (order ``k + t + 1``, a power of
    2) and the powers ``1..n`` of ``w3`` (order a power of 3 above ``n``).
    The roots are the block's ``omega_secrets`` / ``omega_shares`` where
    it states them, else the first of the right order."""
    k, n, t = scheme["secret_count"], scheme["share_count"], scheme["privacy_threshold"]
    m2, m3 = k + t + 1, 3
    if m2 & (m2 - 1):
        raise ValueError(f"k + t + 1 = {m2} is not a power of 2")
    while m3 <= n:
        m3 *= 3
    w2 = scheme.get("omega_secrets") or root_of_unity(m2, 2, modulus)
    w3 = scheme.get("omega_shares") or root_of_unity(m3, 3, modulus)
    if pow(w2, m2, modulus) != 1 or pow(w2, m2 // 2, modulus) == 1:
        raise ValueError(f"{w2} has not order {m2} modulo {modulus}")
    if pow(w3, m3, modulus) != 1 or pow(w3, m3 // 3, modulus) == 1:
        raise ValueError(f"{w3} has not order {m3} modulo {modulus}")
    return ([pow(w2, j, modulus) for j in range(m2)],
            [pow(w3, i, modulus) for i in range(1, n + 1)])


def _matmul_mod(matrix: np.ndarray, values: np.ndarray, modulus: int) -> np.ndarray:
    # exact in int64: a row's products, each under modulus^2, must not wrap
    if matrix.shape[1] * (modulus - 1) ** 2 >= 1 << 63:
        raise ValueError(f"modulus {modulus} too large for int64 products")
    return (matrix @ values) % modulus


# -- the round -----------------------------------------------------------------

def plain_round(inputs, seeds, scheme: dict, modulus: int,
                rng: np.random.Generator) -> dict:
    """One packed-Shamir round with ChaCha seed masks, step by step.

    ``inputs``: ``[P, d]`` integers; ``seeds``: ``[P, <=8]`` seed words,
    one seed per participant; ``scheme``: the configuration's block
    (``secret_count``, ``share_count``, ``privacy_threshold``, and the
    roots where it states them). Returns the revealed ``aggregate``
    ``[d]`` with what a test wants to look at on the way: ``masks``
    ``[P, d]``, ``clerk_rows`` ``[n, ceil(d / k)]`` (each clerk's sum of
    the shares it was sent) and ``mask_total`` ``[d]``."""
    k, n, t = scheme["secret_count"], scheme["share_count"], scheme["privacy_threshold"]
    if scheme.get("prime_modulus", modulus) != modulus:
        raise ValueError("the scheme shares over another prime than the masks' modulus")
    value_points, share_points = scheme_points(scheme, modulus)
    share_matrix = lagrange_matrix(value_points, share_points, modulus)          # [n, k+t+1]
    reveal_matrix = lagrange_matrix(share_points, value_points[1:k + 1], modulus)  # [k, n]

    inputs = np.asarray(inputs, dtype=np.int64) % modulus
    participants, dim = inputs.shape
    batches = -(-dim // k)
    masks = np.stack([mask_stream(seed, 0, dim, modulus) for seed in seeds])
    masked = (inputs + masks) % modulus
    clerk_rows = np.zeros((n, batches), np.int64)
    for row in masked:  # one participant at a time, as a participant would
        secrets = np.zeros(batches * k, np.int64)    # the last batch's pad
        secrets[:dim] = row
        values = np.concatenate([
            np.zeros((1, batches), np.int64),         # the polynomial's zero at w2^0
            secrets.reshape(batches, k).T,            # secret j of a batch at w2^j
            rng.integers(0, modulus, size=(t, batches), dtype=np.int64)])
        clerk_rows = (clerk_rows + _matmul_mod(share_matrix, values, modulus)) % modulus
    revealed = _matmul_mod(reveal_matrix, clerk_rows, modulus)   # [k, batches]
    revealed = revealed.T.reshape(-1)[:dim]
    mask_total = masks.sum(axis=0) % modulus
    return {"aggregate": (revealed - mask_total) % modulus, "masks": masks,
            "clerk_rows": clerk_rows, "mask_total": mask_total}
