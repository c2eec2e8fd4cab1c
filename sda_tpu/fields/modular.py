"""Device-side modular arithmetic kernels (jnp, jit-friendly).

All arrays carry int64 values in canonical form [0, m). On TPU int64 is
emulated in int32 pairs and — crucially — XLA cannot lower an s64
``dot_general`` at all (the X64 rewrite is unimplemented for dot), so the
hot matmul (``modmatmul``) is formulated dot-free: a broadcast multiply +
reduction over the (always tiny: committee-sized) contraction axis, with
the modular reduction applied every ``group`` terms so emulated-s64
intermediates never overflow. XLA fuses the broadcast product into the
reduction, so the big operand streams from HBM once.

Overflow discipline (p < 2^31 enforced by schemes): products < p^2 < 2^62;
``group = (2^63 - 1) // p^2 >= 2`` terms are accumulated between
reductions, so partial sums stay < 2^63.

The reference computes the same algebra as scalar Rust loops over Vec<i64>
(client/src/crypto/sharing/*.rs); the canonical-form convention here differs
only by a final `positive()` lift (receive.rs:14-21) — values are congruent.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import fastfield


def canon(x, m):
    """Canonical representative in [0, m) of any int64 residues."""
    return jnp.mod(x, m)


def modadd(a, b, m):
    return jnp.mod(a + b, m)


def modsub(a, b, m):
    return jnp.mod(a - b, m)


def modsum(x, m, axis=0):
    """Sum of canonical residues along ``axis`` mod m — THE clerk kernel
    (reference hot loop: sharing/combiner.rs:15-30).

    Exact for any m < 2^62 and any term count: when a flat int64 sum could
    wrap (n_terms * (m-1) >= 2^63, e.g. 8 shares of a 2^61 modulus), the
    reduction folds in chunks small enough that every partial sum provably
    fits, canonicalizing between levels. For m < 2^31 the fan exceeds any
    realistic axis and this is a single plain sum.
    """
    x = jnp.asarray(x, jnp.int64)
    n = x.shape[axis]
    fan = max(2, ((1 << 63) - 1) // max(1, int(m) - 1))
    if n <= fan:
        return jnp.mod(jnp.sum(x, axis=axis, dtype=jnp.int64), m)
    x = jnp.moveaxis(x, axis, 0)
    while x.shape[0] > 1:
        k = x.shape[0]
        chunk = min(fan, k)
        pad = (-k) % chunk
        if pad:
            x = jnp.concatenate(
                [x, jnp.zeros((pad,) + x.shape[1:], jnp.int64)], axis=0
            )
        x = x.reshape((x.shape[0] // chunk, chunk) + x.shape[1:])
        x = jnp.mod(jnp.sum(x, axis=1, dtype=jnp.int64), m)
    return x[0]


#: Largest supported modulus (exclusive): residues must fit 31 bits so
#: products fit s64 and at least two terms accumulate between reductions.
MAX_MODULUS = 1 << 31


def modmatmul(a, b, p: int):
    """(a @ b) mod p for canonical int64 operands; p < 2^31.

    ``a`` is typically a small host-built scheme matrix ([n, m2] share or
    [k, r] reconstruct matrix), ``b`` the batch-column data [..., m2, B]
    with B huge — the batched formulation of packed-Shamir
    share/reconstruct. Contraction runs as broadcast multiply + chunked
    modular sum (no dot: TPU cannot lower s64 dot_general); exact for any
    contraction size since partial sums are reduced every ``group`` terms.
    """
    if p >= MAX_MODULUS:
        raise ValueError(f"modulus {p} >= 2^31 unsupported by modmatmul")
    a = jnp.asarray(a)
    b = jnp.asarray(b)
    a_vec, b_vec = a.ndim == 1, b.ndim == 1  # matmul vector promotion rules
    if a_vec:
        a = a[None, :]
    if b_vec:
        b = b[:, None]
    k = b.shape[-2]  # contraction axis
    group = max(1, ((1 << 63) - 1) // (p * p))
    # a: [..., n, k] -> [..., n, k, 1]; b: [..., k, B] -> [..., 1, k, B]
    a = a[..., :, :, None]
    b = b[..., None, :, :]
    if k <= group:
        out = jnp.mod(jnp.sum(a * b, axis=-2), p)
    else:
        acc = None
        for start in range(0, k, group):
            part = jnp.sum(
                a[..., start : start + group, :] * b[..., start : start + group, :],
                axis=-2,
            )
            acc = part if acc is None else acc + jnp.mod(part, p)
            acc = jnp.mod(acc, p)
        out = acc
    if a_vec:
        out = out[..., 0, :]
    if b_vec:
        out = out[..., 0]
    return out


def uniform_mod(key, shape, m: int):
    """Uniform draws in [0, m) from threefry bits; m < 2^62.

    64 random bits an element -- the output block of one threefry counter,
    the same draw ``fastfield.uniform32`` reduces (``random_bits64``), so
    the two agree element for element at a Solinas prime -- reduced mod m:
    statistical distance from uniform is <= m / 2^64 (< 2^-33 for 31-bit
    moduli) — the TPU-native replacement for the reference's
    OsRng.gen_range (additive.rs:42-44, full.rs:25-27).
    """
    if not 0 < m < (1 << 62):
        raise ValueError(f"modulus {m} out of range for uniform_mod")
    v = fastfield.random_bits64(key, shape)
    return jnp.mod(v, jnp.uint64(m)).astype(jnp.int64)


# ---------------------------------------------------------------------------
# NumPy mirrors (host oracle building blocks — bit-exact same algorithms)

def np_modmatmul(a: np.ndarray, b: np.ndarray, p: int) -> np.ndarray:
    if p >= MAX_MODULUS:
        raise ValueError(f"modulus {p} >= 2^31 unsupported by modmatmul")
    a = np.asarray(a, dtype=np.int64)
    b = np.asarray(b, dtype=np.int64)
    k = b.shape[-2] if b.ndim >= 2 else b.shape[0]  # contraction axis
    group = max(1, ((1 << 63) - 1) // (p * p))
    if k * p * p < (1 << 63):
        return np.matmul(a, b) % p
    b_vec = b.ndim == 1
    if b_vec:
        b = b[:, None]
    acc = None
    for start in range(0, k, group):
        part = np.matmul(a[..., start : start + group], b[..., start : start + group, :])
        acc = part % p if acc is None else (acc + part % p) % p
    return acc[..., 0] if b_vec else acc


def np_modsum(x: np.ndarray, m: int, axis=0) -> np.ndarray:
    x = np.asarray(x, dtype=np.int64)
    n = x.shape[axis]
    fan = max(2, ((1 << 63) - 1) // max(1, int(m) - 1))
    if n <= fan:
        return np.sum(x, axis=axis) % m
    x = np.moveaxis(x, axis, 0)
    acc = np.zeros(x.shape[1:], dtype=np.int64)
    for start in range(0, n, fan):
        part = np.sum(x[start : start + fan], axis=0) % m
        acc = (acc + part) % m  # both canonical: sum < 2m < 2^63
    return acc
