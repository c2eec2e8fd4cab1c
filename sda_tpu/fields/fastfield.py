"""uint32 Solinas-prime field kernels — the TPU fast path.

TPU has no native 64-bit integers: every s64 op XLA emulates costs several
s32 VPU ops, and s64 arrays burn double HBM bandwidth. The generic kernels
in ``modular.py`` pay both. This module removes them for primes of Solinas
form

    p = 2^b - delta,   20 <= b <= 29,   delta < 2^14,

where reduction is shift/add (``2^b ≡ delta (mod p)``) and every
intermediate provably fits uint32:

- values are canonical residues < p < 2^29 held in uint32 (HALF the bytes);
- ``v mod p`` for any v < 2^32 is ``q = v >> b; v - q*p`` (+ one
  conditional subtract), ~3 VPU ops — no 64-bit magic-multiply sequence;
- products a*b split into 15-bit limbs: 4 uint32 multiplies whose scale
  streams (2^30, 2^15, 1) recombine through the Solinas congruence with
  every partial sum < 2^32 (bounds in ``modmatmul32``);
- a 64-bit value is reduced from its two uint32 halves, ``hi * (2^32 mod
  p) + lo`` (``reduce64``): random draws, ChaCha stream draws and int64
  inputs never meet a 64-bit ``jnp.mod``, which the chip would emulate as
  a multi-word division.

``generate_packed_params`` prefers such primes, so packed-Shamir rounds hit
this path; arbitrary primes (e.g. the reference's p=433 conformance vector)
keep the generic ``modular.py`` kernels — results are bit-identical either
way (tests/test_fastfield.py checks against the NumPy oracle).

Reference semantics being accelerated: the share/clerk/reconstruct loops of
client/src/crypto/sharing/*.rs (see modular.py / SURVEY.md §2.2).
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_U32 = jnp.uint32
_LOW = 15  # low-limb width: limbs < 2^15 keep 15x15-bit products < 2^30


class SolinasPrime:
    """Parameter pack for p = 2^b - delta; ``try_from`` gates eligibility."""

    __slots__ = ("p", "b", "delta")

    def __init__(self, p: int, b: int, delta: int):
        self.p = p
        self.b = b
        self.delta = delta

    @staticmethod
    def try_from(p: int) -> Optional["SolinasPrime"]:
        b = p.bit_length()
        delta = (1 << b) - p
        if not (20 <= b <= 29):
            return None
        if delta >= (1 << 14):
            return None
        # canon32 does ONE conditional subtract after _reduce; its input
        # r < 2^b + (2^(32-b))*delta must stay < 2p
        if delta * (1 + (1 << (32 - b))) >= p:
            return None
        return SolinasPrime(p, b, delta)

    def __repr__(self):
        return f"SolinasPrime(2^{self.b} - {self.delta})"


def supported(p: int) -> bool:
    return SolinasPrime.try_from(p) is not None


# ---------------------------------------------------------------------------
# Scalar helpers (all uint32 lanes; sp.* are Python ints => XLA constants)

def _reduce(v, sp: SolinasPrime):
    """v < 2^32  ->  r ≡ v (mod p), r < p + 8*delta (< 2p)."""
    q = v >> np.uint32(sp.b)
    return v - q * np.uint32(sp.p)


def canon32(v, sp: SolinasPrime):
    """v < 2^32 -> canonical residue in [0, p)."""
    r = _reduce(jnp.asarray(v, _U32), sp)
    return jnp.where(r >= np.uint32(sp.p), r - np.uint32(sp.p), r)


def to_residues32(inputs, sp: SolinasPrime):
    """Any-integer inputs -> canonical uint32 residues mod p.

    Every dtype skips the 64-bit pass entirely: what it returns is
    ``jnp.mod(inputs.astype(int64), p)`` bit for bit, computed in uint32
    lanes. Narrower integers widen to the 32-bit branches; anything else is
    read as int64 (a uint64 of 2^63 or more wraps to a negative, as that
    cast does) and reduced from its two halves.
    """
    inputs = jnp.asarray(inputs)
    if inputs.dtype.itemsize < 4 and not jnp.issubdtype(inputs.dtype, jnp.floating):
        signed = jnp.issubdtype(inputs.dtype, jnp.signedinteger)
        inputs = inputs.astype(jnp.int32 if signed else jnp.uint32)
    if inputs.dtype == jnp.uint32:
        return canon32(inputs, sp)
    if inputs.dtype == jnp.int32:
        bits = inputs.astype(jnp.uint32)  # two's complement: negatives ≡ v + 2^32
        r = canon32(bits, sp)
        r32 = jnp.uint32((1 << 32) % sp.p)
        return jnp.where(inputs < 0, modsub32(r, r32, sp), r)
    inputs = inputs.astype(jnp.int64)
    hi = (inputs >> 32).astype(jnp.uint32)
    r = reduce64(hi, inputs.astype(jnp.uint32), sp)
    # two's complement again: a negative's halves read v + 2^64
    r64 = jnp.uint32((1 << 64) % sp.p)
    return jnp.where(hi >= np.uint32(1 << 31), modsub32(r, r64, sp), r)


def modadd32(a, b, sp: SolinasPrime):
    """Canonical a, b -> canonical a+b (sum < 2p < 2^30)."""
    s = a + b
    return jnp.where(s >= np.uint32(sp.p), s - np.uint32(sp.p), s)


def modsub32(a, b, sp: SolinasPrime):
    """Canonical a, b -> canonical a-b (uint32 wraparound + correction)."""
    d = a - b
    # underflow iff b > a: wrapped value >= 2^32 - p > p, add p back
    return jnp.where(a >= b, d, d + np.uint32(sp.p))


def _compose(t1, t0, sp: SolinasPrime):
    """t1*2^15 + t0 mod p -> canonical, for t1 < 2^31, t0 < 2^31."""
    t1 = canon32(t1, sp)                                     # < p < 2^b
    t1h = t1 >> np.uint32(sp.b - _LOW)                       # < 2^15
    t1l = t1 & np.uint32((1 << (sp.b - _LOW)) - 1)           # < 2^(b-15)
    # t1*2^15 = t1h*2^b + t1l*2^15 ≡ t1h*delta + t1l*2^15
    v = t0 + t1h * np.uint32(sp.delta) + (t1l << np.uint32(_LOW))
    # bound: 2^31 + 2^29 + 2^29 < 2^32
    return canon32(v, sp)


def mulmod32_const(x, c: int, sp: SolinasPrime):
    """Canonical x (< p) times Python-int constant c (< p), canonical out."""
    c = c % sp.p
    c15 = (c << _LOW) % sp.p
    xh = x >> np.uint32(_LOW)                                # < 2^(b-15) <= 2^14
    xl = x & np.uint32((1 << _LOW) - 1)                      # < 2^15
    # x*c = xh*(c*2^15) + xl*c; split both constants into 15-bit limbs
    t1 = xh * np.uint32(c15 >> _LOW) + xl * np.uint32(c >> _LOW)   # < 2^30
    t0 = xh * np.uint32(c15 & 0x7FFF) + xl * np.uint32(c & 0x7FFF)  # < 2^31
    return _compose(t1, t0, sp)


def modsum32(x, sp: SolinasPrime, axis: int = 0):
    """Canonical residues summed along ``axis`` -> canonical (clerk kernel).

    ONE reduce whose combiner is the modular add (a + b < 2p < 2^30, so
    the unsigned minimum of s and s - p is the canonical sum): exact in
    any order, and a single op that the TPU compiler fuses with an
    elementwise producer — the fold of a round's whole input reads it
    once and writes only the sum. Keep it one op: a tree of raw uint32
    adds with a canonicalizing fold every few terms needs its axis padded
    to whole groups, and the compiler fuses no producer through that pad
    (nor into a whole-groups/remainder pair of reduces, which gives the
    producer two consumers): it writes the canonical [S, d] input to HBM
    and reads it back.
    """
    x = jnp.asarray(x, _U32)
    p = np.uint32(sp.p)

    def add(a, b):
        s = a + b
        return jnp.minimum(s, s - p)

    return jax.lax.reduce(x, np.uint32(0), add, (axis % x.ndim,))


def reduce64(hi, lo, sp: SolinasPrime):
    """(hi*2^32 + lo) mod p, canonical, for any uint32 halves hi, lo.

    The 64-bit value never exists: each half is canonicalized (``canon32``
    takes any v < 2^32), the high one multiplied by the constant
    ``2^32 mod p`` and the two added, all in uint32 lanes. Exact for every
    prime ``SolinasPrime.try_from`` admits.
    """
    hi = canon32(hi, sp)
    lo = canon32(lo, sp)
    r32 = (1 << 32) % sp.p
    return modadd32(mulmod32_const(hi, r32, sp), lo, sp)


def random_bits64(key, shape):
    """64 threefry bits an element: ONE ``uint64`` draw of ``shape`` -- the
    draw under ``uniform32`` and ``modular.uniform_mod``.

    JAX's (partitionable) threefry runs a threefry-2x32 block on a counter
    per drawn element: a ``uint64`` element keeps both output words of its
    block, a ``uint32`` one XORs them into one word -- so the same 64 bits
    asked for as ``shape + (2,)`` ``uint32`` words cost two blocks an
    element, half of each thrown away. On the chip no 64-bit array is
    made: the combine inside the draw and ``uniform32``'s split cancel in
    the TPU compiler's 64-bit rewriting, and the words fuse into whatever
    reduces them.
    """
    if not jax.config.jax_enable_x64:
        # the draw would be narrowed to uint32: 32 bits an element
        raise RuntimeError("a 64-bit draw needs jax_enable_x64 "
                           "(importing sda_tpu sets it)")
    return jax.random.bits(key, shape=tuple(shape), dtype=jnp.uint64)


def uniform32(key, shape, sp: SolinasPrime):
    """Uniform canonical residues, each from 64 random bits of its own.

    An element is the full output block of one threefry counter
    (``random_bits64``): its high and low words go to ``reduce64``,
    ``(hi*2^32 + lo) mod p`` by exact constant-multiply reduction --
    <= p/2^64 from uniform, and element for element what the generic
    ``modular.uniform_mod`` gives for the same key, shape and prime.
    """
    bits = random_bits64(key, shape)
    return reduce64((bits >> np.uint64(32)).astype(_U32), bits.astype(_U32), sp)


# ---------------------------------------------------------------------------
# The contraction kernel: out = (M @ v) mod p, M a small host-side matrix

def modmatmul32(m_host: np.ndarray, v, sp: SolinasPrime):
    """[n, k] host matrix (ints mod p) times canonical [..., k, B] uint32.

    Builds the matrix limbs host-side (trace-time constants) and contracts
    via :func:`modmatmul32_limbs`.
    """
    m_host = np.asarray(m_host) % sp.p
    n, k = m_host.shape
    v = jnp.asarray(v, _U32)
    if v.shape[-2] != k:
        raise ValueError(f"contraction mismatch: M has k={k}, v has {v.shape[-2]}")

    low_mask = (1 << _LOW) - 1
    mh = jnp.asarray((m_host >> _LOW).astype(np.uint32))     # [n, k] < 2^14
    ml = jnp.asarray((m_host & low_mask).astype(np.uint32))  # [n, k] < 2^15
    return modmatmul32_limbs(mh, ml, v, sp)


def modmatmul32_limbs(mh, ml, v, sp: SolinasPrime):
    """Core contraction on pre-split matrix limbs (device arrays).

    ``mh``/``ml``: [n, k] uint32 high/low 15-bit limbs of a matrix of
    canonical residues; ``v``: canonical [..., k, B] uint32. Split out from
    :func:`modmatmul32` so Pallas kernels can take the limbs as inputs
    (kernels may not capture traced constants).

    Limb streams with per-stream overflow-safe fan-in (bounds for b <= 29,
    low limbs < 2^15, high limbs < 2^(b-15) <= 2^14):

      hh = mh*vh < 2^28   (scale 2^30)    hl/lh = *h**l < 2^29 (scale 2^15)
      ll = ml*vl < 2^30   (scale 1)

    Each stream folds (canonical reduce) whenever another chunk of terms
    would overflow uint32; the scale-2^30 stream re-enters through
    ``mulmod32_const(.., 2^30 mod p)``.
    """
    n, k = mh.shape
    low_mask = (1 << _LOW) - 1
    vh = v >> np.uint32(_LOW)                                # [..., k, B] < 2^14
    vl = v & np.uint32(low_mask)                             # [..., k, B] < 2^15

    hi_max = (1 << (sp.b - _LOW)) - 1
    bounds = {
        "hh": hi_max * hi_max,
        "hl": hi_max * low_mask,
        "ll": low_mask * low_mask,
    }
    fans = {s: max(1, 0xFFFFFFFF // bound) for s, bound in bounds.items()}
    # one chunking of the contraction axis serves all streams
    chunk = max(1, min(fans.values()))

    def stream(a_limbs, b_limbs):
        # a: [n, k]; b: [..., k, B] -> sum over k of a*b, folded per chunk.
        # Accumulated with explicit adds, not jnp.sum: Mosaic cannot lower
        # unsigned reductions, and k is tiny so the unrolled adds fuse the
        # same either way.
        acc = None
        for start in range(0, k, chunk):
            part = None
            for j in range(start, min(start + chunk, k)):
                term = a_limbs[:, j][:, None] * b_limbs[..., j, :][..., None, :]
                part = term if part is None else part + term  # [..., n, B]
            part = canon32(part, sp)
            acc = part if acc is None else modadd32(acc, part, sp)
        return acc                                           # canonical < p

    s_hh = stream(mh, vh)
    s_hl = stream(mh, vl)
    s_lh = stream(ml, vh)
    s_ll = stream(ml, vl)

    c30 = (1 << 30) % sp.p
    t0 = modadd32(s_ll, mulmod32_const(s_hh, c30, sp), sp)   # < p
    t1 = modadd32(s_hl, s_lh, sp)                            # < p
    return _compose(t1, t0, sp)                              # t1*2^15 + t0


# ---------------------------------------------------------------------------
# NumPy mirror (oracle for bit-exactness tests)

def np_modmatmul32(m_host: np.ndarray, v: np.ndarray, sp: SolinasPrime) -> np.ndarray:
    m = np.asarray(m_host, dtype=object) % sp.p
    vv = np.asarray(v, dtype=object)
    return (m @ vv % sp.p).astype(np.uint32)
