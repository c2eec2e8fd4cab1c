"""The plain reference of a secure sum over a cohort that is never held
whole: the inputs arrive in blocks of rows, as upstream's server iterates
a snapshot's participations into per-clerk jobs
(``server/src/stores.rs#L86-L101``) and a clerk sums shares one
participation at a time (``client/src/clerk.rs#L63-L107``). NumPy and
Python integers only; nothing of the program is imported.

Two parts:

- :func:`on_host_blocks` -- what a round must reveal: the column sums of
  the blocks, modulo the modulus, bit for bit. One block is in memory at
  a time, so it fits at any cohort.
- :func:`plain_streamed_round` -- the round written out at toy size in
  Python integers, block by block: mask, packed-Shamir share by the
  Vandermonde definition, each clerk's sum accumulated over the blocks,
  Lagrange reconstruction from ``k + t`` of the ``n`` clerk rows, unmask.
  It must equal :func:`on_host_blocks` for any blocking and any
  randomness.

The packed-Shamir scheme is upstream's (``full_loop.rs#L54-L67``; the
``threshold-secret-sharing`` crate's ``PackedSecretSharing``): one
polynomial of degree at most ``k + t`` carries ``k`` secrets. With ``w2``
a root of unity of order ``m2 = k + t + 1`` (a power of 2) and ``w3`` one
of order ``m3 > n`` (a power of 3), the polynomial takes 0 at ``w2^0``,
secret ``j`` at ``w2^j`` (``j = 1..k``) and a uniform value at each of
``w2^(k+1..k+t)``; clerk ``i`` (``i = 1..n``) holds its value at
``w3^i``. The map from those ``m2`` values to the ``n`` shares is
``V(share points) . V(value points)^-1`` with ``V`` the Vandermonde
matrix of the points; it is built here from the Lagrange basis, which is
the same matrix without a modular matrix inverse. Reconstruction adds the
known point ``(w3^0, 0)`` to ``k + t`` shares and evaluates at the secret
points.

Where this departs from upstream, on purpose:

- **Masks stay in the round.** Upstream's participant seals its mask to
  the recipient, who subtracts the masks' sum after the reveal. A streamed
  pod round draws and cancels its masks inside one round; here the mask
  total is accumulated over the blocks and subtracted at the end.
- **The randomness is the caller's.** Upstream draws masks and polynomial
  values from the operating system's generator; a reference cannot repeat
  those draws, and the revealed sum does not depend on them.
  :func:`plain_streamed_round` takes a NumPy ``Generator``.
- **Inputs are taken to their least non-negative residues** (Python's
  ``%``), whatever their sign or size, before they are masked.
- **Blocks of rows, not single participations.** ``rows`` sets only the
  order of the additions, which are exact; ``rows = 1`` is upstream's.
"""

from __future__ import annotations

import numpy as np

_INT64_ROOM = 1 << 62


# -- what the round must reveal ------------------------------------------------

def on_host_blocks(get_block, participants: int, dimension: int, modulus: int,
                   rows: int) -> np.ndarray:
    """``get_block(p0, p1, d0, d1)`` -> ``[p1 - p0, d1 - d0]`` integers;
    the ``[dimension]`` int64 sum of all ``participants`` rows modulo
    ``modulus``, taken ``rows`` rows at a time. A block whose column sums
    could leave int64 is reduced before it is summed."""
    if rows < 1:
        raise ValueError("a block holds at least one row")
    total = np.zeros(dimension, dtype=np.int64)
    for p0 in range(0, participants, rows):
        p1 = min(p0 + rows, participants)
        block = np.asarray(get_block(p0, p1, 0, dimension), dtype=np.int64)
        if block.shape != (p1 - p0, dimension):
            raise ValueError(f"block [{p0}, {p1}) has shape {block.shape}")
        largest = max(abs(int(block.min())), abs(int(block.max())))
        if largest * (p1 - p0) >= _INT64_ROOM:
            block = block % modulus
        total = (total + block.sum(axis=0) % modulus) % modulus
    return total


# -- the packed-Shamir scheme, in Python integers ---------------------------------

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


def lagrange_matrix(points, targets, modulus: int) -> list:
    """``matrix[i][j]`` = the ``j``-th Lagrange basis polynomial of
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
    return matrix


def scheme_points(scheme: dict):
    """``(value points, share points)`` of the configuration's ``scheme``
    block: the powers of ``w2`` and the powers ``1..n`` of ``w3``. The
    roots are the block's ``omega_secrets`` / ``omega_shares`` where it
    states them, else the first of the right order."""
    k, n = scheme["secret_count"], scheme["share_count"]
    t, p = scheme["privacy_threshold"], scheme["prime_modulus"]
    m2, m3 = k + t + 1, 3
    if m2 & (m2 - 1):
        raise ValueError(f"k + t + 1 = {m2} is not a power of 2")
    while m3 <= n:
        m3 *= 3
    w2 = scheme.get("omega_secrets") or root_of_unity(m2, 2, p)
    w3 = scheme.get("omega_shares") or root_of_unity(m3, 3, p)
    if pow(w2, m2, p) != 1 or pow(w2, m2 // 2, p) == 1:
        raise ValueError(f"{w2} has not order {m2} modulo {p}")
    if pow(w3, m3, p) != 1 or pow(w3, m3 // 3, p) == 1:
        raise ValueError(f"{w3} has not order {m3} modulo {p}")
    return ([pow(w2, j, p) for j in range(m2)],
            [pow(w3, i, p) for i in range(1, n + 1)])


# -- the round -----------------------------------------------------------------

def plain_streamed_round(inputs, scheme: dict, rows: int,
                         rng: np.random.Generator, clerks=None) -> dict:
    """One fully masked packed-Shamir round over ``inputs`` ``[P, d]``,
    ``rows`` participants at a time, in Python integers.

    ``clerks``: the ``k + t`` clerk indices (0-based) whose rows reveal;
    the first ``k + t`` by default. Returns the revealed ``aggregate``
    ``[d]`` int64 with what a test wants to look at on the way:
    ``clerk_rows`` (``n`` lists of one sum per batch), ``mask_total``
    ``[d]`` and ``blocks`` (how many were folded)."""
    k, n = scheme["secret_count"], scheme["share_count"]
    t, p = scheme["privacy_threshold"], scheme["prime_modulus"]
    value_points, share_points = scheme_points(scheme)
    share_matrix = lagrange_matrix(value_points, share_points, p)
    clerks = list(range(k + t)) if clerks is None else list(clerks)
    if len(set(clerks)) != k + t or not all(0 <= c < n for c in clerks):
        raise ValueError(f"reconstruction takes {k + t} distinct clerks of {n}")
    reveal_matrix = lagrange_matrix(
        [1] + [share_points[c] for c in clerks], value_points[1:k + 1], p)

    inputs = [[int(v) % p for v in row] for row in np.asarray(inputs).tolist()]
    participants, dim = len(inputs), len(inputs[0])
    batches = -(-dim // k)

    def uniform(count):
        return [int(v) for v in rng.integers(0, p, size=count, dtype=np.int64)]

    clerk_rows = [[0] * batches for _ in range(n)]
    mask_total = [0] * dim
    blocks = 0
    for p0 in range(0, participants, rows):  # one block in memory at a time
        for row in inputs[p0:p0 + rows]:
            mask = uniform(dim)
            masked = [(x + m) % p for x, m in zip(row, mask)]
            mask_total = [(a + m) % p for a, m in zip(mask_total, mask)]
            masked += [0] * (batches * k - dim)      # the last batch's pad
            for b in range(batches):
                values = [0] + masked[b * k:(b + 1) * k] + uniform(t)
                for clerk, weights in enumerate(share_matrix):
                    share = sum(w * v for w, v in zip(weights, values)) % p
                    clerk_rows[clerk][b] = (clerk_rows[clerk][b] + share) % p
        blocks += 1

    revealed = []
    for b in range(batches):
        known = [0] + [clerk_rows[c][b] for c in clerks]
        revealed += [sum(w * v for w, v in zip(weights, known)) % p
                     for weights in reveal_matrix]
    aggregate = [(v - m) % p for v, m in zip(revealed[:dim], mask_total)]
    return {"aggregate": np.asarray(aggregate, dtype=np.int64),
            "clerk_rows": clerk_rows,
            "mask_total": np.asarray(mask_total, dtype=np.int64),
            "blocks": blocks}
