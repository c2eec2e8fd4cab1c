"""Device kernels for secret sharing: additive and packed Shamir.

The reference's batching layer (client/src/crypto/sharing/batched.rs:18-99)
chunks a d-vector into ceil(d/k) batches of k secrets, shares each batch,
and transposes shares per clerk. Here that whole layer is a change of
layout (``fields.layout``: the de-interleave of k rows and its inverse):
the batch axis becomes the matmul's column axis, so sharing a participant's
vector is ONE [n, m2] @ [m2, B] modular matmul and reconstruction is ONE
[k, r+1] @ [r+1, B] matmul — MXU-shaped, vmap-able over participants.

Functions are jit-compiled with scheme parameters static; canonical residues
[0, m) throughout (congruent to the reference's signed representatives, cf.
receive.rs:14-21 `positive()`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import fastfield, layout
from ..obs import devprof
from .modular import modmatmul, modsub, modsum, uniform_mod


def batch_columns(secrets, input_size: int):
    """[d] -> [input_size, B] column-per-batch layout (zero-padded).

    Batch b holds secrets[b*k:(b+1)*k] (batched.rs:18-53 semantics): the
    de-interleave of ``k`` rows, through the matrix unit (``fields.layout``).
    """
    return layout.deinterleave(secrets, input_size)


def unbatch_columns(batched, dimension: int):
    """[k, B] -> [d], inverse of batch_columns (truncates padding)."""
    return layout.interleave(batched)[..., :dimension]


# ---------------------------------------------------------------------------
# Additive sharing (reference: client/src/crypto/sharing/additive.rs)

@functools.partial(jax.jit, static_argnames=("modulus",))
def additive_share_from_randomness(secrets, draws, *, modulus: int):
    """[..., d] secrets + [..., n-1, d] draws -> [..., n, d] shares.

    Last share is secret minus the sum of the draws (additive.rs:32-52);
    split out so the CPU oracle can be fed identical randomness.
    """
    last = modsub(secrets, modsum(draws, modulus, axis=-2), modulus)
    return jnp.concatenate([draws, last[..., None, :]], axis=-2)


# devprof compiled-shape registry on the jit entry points: calls from
# inside an outer trace (the pod/streamed programs) pass through uncounted
# under a named scope; top-level calls (the federated client path) count
additive_share_from_randomness = devprof.instrument(
    "fields.additive_share", additive_share_from_randomness)


def additive_share(key, secrets, *, share_count: int, modulus: int):
    """[..., d] secrets -> [..., n, d] shares with fresh threefry draws."""
    d = secrets.shape[-1]
    draws = uniform_mod(key, secrets.shape[:-1] + (share_count - 1, d), modulus)
    return additive_share_from_randomness(secrets, draws, modulus=modulus)


@functools.partial(jax.jit, static_argnames=("modulus",))
def combine(shares, *, modulus: int):
    """Elementwise modular sum across the leading axis — the clerk hot kernel
    (combiner.rs:15-30) and the additive reconstructor (additive.rs:55-73)."""
    return modsum(shares, modulus, axis=0)


combine = devprof.instrument("fields.combine", combine)


# ---------------------------------------------------------------------------
# Packed Shamir (reference: packed_shamir.rs via the tss crate; matrices
# built host-side in sda_tpu.fields.numtheory)

@functools.partial(jax.jit, static_argnames=("prime", "secret_count"), donate_argnums=())
def packed_share_from_randomness(secrets, randomness, share_matrix, *, prime: int,
                                 secret_count: int):
    """Share [..., d] secrets given explicit [..., t, B] randomness.

    values column = [0; k secrets; t randomness]; shares = M @ values.
    Split out so the CPU oracle can be fed identical randomness for
    bit-exactness tests.
    """
    sk = batch_columns(secrets, secret_count)                    # [..., k, B]
    zeros = jnp.zeros(sk.shape[:-2] + (1,) + sk.shape[-1:], sk.dtype)
    values = jnp.concatenate([zeros, sk, randomness], axis=-2)   # [..., m2, B]
    return modmatmul(share_matrix, values, prime)                # [..., n, B]


packed_share_from_randomness = devprof.instrument(
    "fields.packed_share", packed_share_from_randomness)


def packed_share(key, secrets, share_matrix, *, prime: int, secret_count: int,
                 privacy_threshold: int):
    """Share with fresh threefry randomness; returns [..., n, B] clerk rows."""
    d = secrets.shape[-1]
    B = -(-d // secret_count)
    randomness = uniform_mod(
        key, secrets.shape[:-1] + (privacy_threshold, B), prime
    )
    return packed_share_from_randomness(
        secrets, randomness, share_matrix, prime=prime, secret_count=secret_count
    )


# ---------------------------------------------------------------------------
# uint32 Solinas fast variants (fields.fastfield) — same algebra, same
# results, ~half the HBM bytes and no emulated-s64 ops. Matrices stay
# host-side numpy so limb decomposition happens at trace time.

def packed_share32(key, secrets32, share_matrix_host, sp: "fastfield.SolinasPrime",
                   *, secret_count: int, privacy_threshold: int):
    """Canonical uint32 [..., d] secrets -> [..., n, B] canonical shares."""
    d = secrets32.shape[-1]
    B = -(-d // secret_count)
    randomness = fastfield.uniform32(
        key, secrets32.shape[:-1] + (privacy_threshold, B), sp
    )
    sk = batch_columns(secrets32, secret_count)                  # [..., k, B]
    zeros = jnp.zeros(sk.shape[:-2] + (1,) + sk.shape[-1:], sk.dtype)
    values = jnp.concatenate([zeros, sk, randomness], axis=-2)   # [..., m2, B]
    return fastfield.modmatmul32(share_matrix_host, values, sp)  # [..., n, B]


def packed_reconstruct32(shares32, recon_matrix_host, sp: "fastfield.SolinasPrime",
                         *, dimension: int):
    """[r, B] canonical uint32 clerk rows -> [d] canonical secrets.

    Two device stages, a scope each (docs/observability.md): the Lagrange
    product on the column layout, and the layout change back to [d]."""
    with jax.named_scope("sda.reconstruct.lagrange"):
        zeros = jnp.zeros((1,) + shares32.shape[1:], shares32.dtype)
        values = jnp.concatenate([zeros, shares32], axis=0)      # [r+1, B]
        secrets = fastfield.modmatmul32(recon_matrix_host, values, sp)
    with jax.named_scope("sda.reconstruct.unbatch"):
        return unbatch_columns(secrets, dimension)


@functools.partial(jax.jit, static_argnames=("prime", "dimension"))
def packed_reconstruct(shares, recon_matrix, *, prime: int, dimension: int):
    """[r, B] surviving clerk share rows -> [d] secrets.

    recon_matrix is built for the surviving index set
    (numtheory.packed_reconstruct_matrix); the implicit point-1 zero row is
    prepended here. The same two scopes as :func:`packed_reconstruct32`.
    """
    with jax.named_scope("sda.reconstruct.lagrange"):
        zeros = jnp.zeros((1,) + shares.shape[1:], shares.dtype)
        values = jnp.concatenate([zeros, shares], axis=0)        # [r+1, B]
        secrets = modmatmul(recon_matrix, values, prime)         # [k, B]
    with jax.named_scope("sda.reconstruct.unbatch"):
        return unbatch_columns(secrets, dimension)


packed_reconstruct = devprof.instrument(
    "fields.packed_reconstruct", packed_reconstruct)
