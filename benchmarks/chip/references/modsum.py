"""The plain reference of a secure sum: the sum of the inputs, modulo
the aggregation's modulus. No masks, no shares, no kernels -- what the
round must reveal, bit for bit, whatever happened in between."""

from __future__ import annotations


def on_device(inputs, modulus: int):
    """``[P, d]`` non-negative integers on the device -> ``[d]`` int64."""
    import jax.numpy as jnp

    return jnp.sum(inputs.astype(jnp.int64), axis=0) % modulus


def on_host(inputs, modulus: int):
    """``[P, d]`` integers in host memory -> ``[d]`` int64 (NumPy)."""
    import numpy as np

    return np.asarray(inputs, dtype=np.int64).sum(axis=0) % modulus
