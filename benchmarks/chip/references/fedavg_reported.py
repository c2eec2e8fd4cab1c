"""The plain reference of a FedAvg round over a fixed-point secure sum in
which only some rows of the cohort's buffer reported: what
``pod_fedavg_round(..., reported=...)`` must return for the float32 weights
in the buffer, whatever masks, shares and kernels stood in between, and
whatever the rows that did not report hold. Nothing of the program is
imported: no ``FixedPointCodec``, no pod.

The round, as the configuration's guarantees state it:

1. ``delta = client - global`` in float32, row by row;
2. the fixed-point encoding, in float32: NaN -> 0, clip to ``+- clip``,
   times ``2^fractional_bits``, round half to even; then integers in int64
   (``clip * 2^fractional_bits`` is a whole number here, so the rounded
   product needs no second clamp);
3. **the rows that did not report count as zero**, whatever step 2 made of
   them, and the rest are summed modulo the modulus, ``rows`` rows at a
   time (:func:`integer_sum`): every block's sum is far inside int64;
4. the centered lift of the sum (above ``modulus // 2`` it stands for a
   negative), divided by the scale and **the number of rows that
   reported** in float64, added to the global vector in float64
   (:func:`new_global`); with no reporter the global vector holds.

Steps 1-3 are written once for NumPy and ``jax.numpy`` (``xp``): at the
chip's size the cohort is on the device and stays there, so the driver
jits :func:`integer_sum` with ``reported`` as an argument (one compile for
every set; the row blocks are static slices, read where they lie); step 4
runs on the host in NumPy either way. ``dtype`` is the precision of steps
1-2: float32 is the configuration's; ``bfloat16`` (``jax.numpy`` only) is
the nearest below it, for the reading that sets the comparison's limit
(PERF.md).
"""

from __future__ import annotations

import numpy as np

#: rows a block of the integer sum holds by default
ROWS = 100


def quantize(deltas, clip: float, fractional_bits: int, xp=np):
    """Float deltas -> int64 fixed-point values in ``[-clip, clip] * 2^f``,
    rounded in the precision ``deltas`` have."""
    dtype = deltas.dtype
    deltas = xp.where(xp.isnan(deltas), xp.zeros((), dtype), deltas)
    deltas = xp.clip(deltas, xp.asarray(-clip, dtype), xp.asarray(clip, dtype))
    scaled = deltas * xp.asarray(2.0 ** fractional_bits, dtype)
    return xp.rint(scaled).astype(xp.int64)


def integer_sum(global_vec, client_vecs, reported, modulus: int, clip: float,
                fractional_bits: int, rows: int = ROWS, xp=np, dtype=None):
    """``[d]`` and ``[P, d]`` floats and ``[P]`` booleans -> ``[d]`` int64
    in ``[0, modulus)``: the sum, modulo ``modulus``, of the quantized
    deltas of the rows that reported."""
    dtype = dtype or xp.float32
    global_row = global_vec.astype(dtype)[None, :]
    total = xp.zeros(global_vec.shape, xp.int64)
    for start in range(0, client_vecs.shape[0], rows):
        deltas = client_vecs[start:start + rows].astype(dtype) - global_row
        values = quantize(deltas, clip, fractional_bits, xp)
        values = xp.where(reported[start:start + rows, None], values, 0)
        total = (total + values.sum(axis=0)) % modulus  # least non-negative
    return total


def new_global(global_vec, total, reporters: int, modulus: int,
               fractional_bits: int):
    """The global vector (float32) and the integer sum, both on the host
    -> (the new global vector in float64, the mean delta in float64) over
    ``reporters`` rows; none reported: the mean is zero."""
    total = np.asarray(total, dtype=np.int64)
    lifted = total - np.where(total > modulus // 2, modulus, 0)
    mean = lifted.astype(np.float64) / 2.0 ** fractional_bits / max(reporters, 1)
    return np.asarray(global_vec, dtype=np.float64) + mean, mean


def tolerance(global_vec, mean):
    """How far an element of the program's float32 result may stand from
    :func:`new_global`'s rounded to float32: ``2^-23 (|global| + 2 |mean|)``.

    Four roundings of 2^-24 relative each separate the two: the program's
    mean rounds twice (its integer lift to float32 above 2^24, and the
    division by the reporters: ``FixedPointCodec.decode_mean_device``, for
    every count), together ``2^-23 |mean|``; its add rounds once and the
    reference's float64 result rounds once to float32, each ``2^-24
    |global + mean|``, together at most ``2^-23 (|global| + |mean|)``. The
    bound is taken at the operands' magnitude, not the result's, because
    ``global + mean`` cancels. A row summed that did not report, or a mean
    over the buffer's rows in place of the reporters', moves an element by
    about ``1 / reporters`` of a delta, some 10^-3 here: ten thousand
    limits. An encode in bfloat16 passes it by orders of magnitude too; an
    integer sum off by a few units does not, and is held exactly elsewhere
    (tier-1, and the driver's set-up).
    """
    global_vec = np.asarray(global_vec, dtype=np.float64)
    return 2.0 ** -23 * (np.abs(global_vec) + 2.0 * np.abs(mean))


def outside(result, expected, limit, xp=np):
    """-> (elements of ``result`` further than ``limit`` from ``expected``,
    elements that differ at all, the largest distance in units of the
    limit), compared in float64. A NaN counts as outside."""
    distance = xp.abs(result.astype(xp.float64) - expected.astype(xp.float64))
    share = distance / xp.maximum(limit, 1e-300)
    return ((~(distance <= limit)).sum(), (distance != 0).sum(), share.max())
