"""Fixed-point encoding of float model vectors into Z_m.

The reference aggregates i64 vectors and leaves the float<->integer story
to the application ("combining locally trained machine learning models",
reference README.md:3-15; `Secret = i64`, client/src/crypto/mod.rs:33-36).
This module owns that story for the TPU build: a deterministic fixed-point
codec whose central guarantee is *exactness of the aggregate* — the secure
modular sum of encodings decodes to the exact sum of the quantized client
values, provided the configured summand capacity is respected.

Centered representation: a quantized value q in [-Q, Q] is uploaded as
q mod m. Sums stay decodable while |sum q_i| < m/2, so the codec derives
its clip range from (modulus, fractional_bits, max_summands) and refuses
configurations that could wrap. This mirrors the headroom discipline the
reference leaves implicit (values "assumed small enough", sharing/
additive.rs:37-39) but makes it a checked, documented contract.
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

__all__ = [
    "FieldSizingError",
    "FixedPointCodec",
    "field_capacity",
    "field_headroom_check",
    "ravel_pytree",
]


class FieldSizingError(ValueError):
    """A configuration whose worst-case aggregate could wrap the field.

    Raised by :func:`field_headroom_check` — the one headroom rule shared
    by :class:`FixedPointCodec` and every analytics encoder
    (``sda_tpu/analytics``), so the two contracts cannot drift. A
    subclass of ``ValueError`` so existing callers keep catching it.
    """


def field_capacity(modulus: int, max_summands: int) -> int:
    """Largest per-coordinate magnitude the centered band can carry.

    A sum of ``max_summands`` contributions each bounded by the returned
    value stays strictly inside the decodable band ``|sum| <= m//2 - 1``
    (centered lift, matching ``RecipientOutput.positive()``'s canonical
    band shifted to (-m/2, m/2]).
    """
    if modulus < 3:
        raise FieldSizingError(f"modulus {modulus} must be >= 3")
    if max_summands < 1:
        raise FieldSizingError(f"max_summands {max_summands} must be >= 1")
    return (modulus // 2 - 1) // int(max_summands)


def field_headroom_check(max_abs: int, max_summands: int, modulus: int,
                         *, context: str = "") -> int:
    """THE modulus-headroom rule: refuse configurations that could wrap.

    Checks that the worst-case aggregate magnitude ``max_abs *
    max_summands`` fits the centered decodable band of ``modulus`` and
    returns the remaining margin (``m//2 - 1 - max_abs*max_summands``,
    always >= 0 on success). Raises :class:`FieldSizingError` naming the
    whole configuration otherwise — a misconfigured encoder is a typed
    error at construction, never a silent wrap at decode.

    ``context`` names the caller (e.g. ``"FixedPointCodec"`` or
    ``"CountMinEncoder(width=64, depth=4)"``) so the error says WHICH
    contract failed.
    """
    max_abs = int(max_abs)
    if max_abs < 1:
        raise FieldSizingError(
            f"{context or 'field sizing'}: max per-coordinate contribution "
            f"{max_abs} must be >= 1")
    cap = field_capacity(modulus, max_summands)
    margin = modulus // 2 - 1 - max_abs * int(max_summands)
    if margin < 0:
        raise FieldSizingError(
            f"{context or 'field sizing'}: per-coordinate contribution up "
            f"to {max_abs} x {max_summands} summands needs a decodable "
            f"band of {max_abs * int(max_summands)}, but modulus {modulus} "
            f"only carries |sum| <= {modulus // 2 - 1} "
            f"(per-coordinate capacity {cap}): increase the modulus or "
            f"lower max_summands")
    return margin


def ravel_pytree(tree):
    """Flatten a pytree of float arrays to one float64 numpy vector.

    Returns (vector, unravel) where unravel maps a same-length float vector
    back to the original structure/shapes/dtypes. This is the TPU analog of
    the reference's "the model IS the vector" convention (README.md:3-15):
    one participation carries one flattened model (or model delta).
    """
    import jax
    from jax import numpy as jnp

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    shapes = [np.shape(l) for l in leaves]
    dtypes = [np.asarray(l).dtype for l in leaves]
    sizes = [int(np.prod(s, dtype=np.int64)) if s else 1 for s in shapes]
    vec = np.concatenate(
        [np.asarray(l, dtype=np.float64).reshape(-1) for l in leaves]
    ) if leaves else np.zeros((0,), np.float64)

    def unravel(flat):
        flat = np.asarray(flat, dtype=np.float64)
        if flat.shape != vec.shape:
            raise ValueError(f"expected shape {vec.shape}, got {flat.shape}")
        out, off = [], 0
        for shape, dtype, size in zip(shapes, dtypes, sizes):
            chunk = flat[off:off + size].reshape(shape).astype(dtype)
            out.append(jnp.asarray(chunk))
            off += size
        return jax.tree_util.tree_unflatten(treedef, out)

    return vec, unravel


def _settle_quotient(proposed, c):
    """uint32 scalars ``c`` in ``[2^23, 2^24)`` and ``proposed`` within 127
    of ``2^47 / c`` -> ``M = round(2^47 / c)`` as int32, exactly.

    The quotient is never half-way between two integers (an odd number
    would have to divide a power of two), so ``M`` is the one integer with
    ``|2^47 - M c| < c / 2``. While ``proposed`` is within 127 of it the
    remainder ``2^47 - proposed * c`` is below 2^31, and ``-proposed * c``
    in wrapping uint32 arithmetic is that remainder exactly. One step by
    the remainder's own float quotient (at most 127, so any division good
    to a part in a thousand is within one of it) leaves it within about
    ``c / 2``, and two steps of one by comparison leave it where it
    belongs."""
    import jax
    from jax import numpy as jnp

    c_signed = c.astype(jnp.int32)
    rest = jax.lax.bitcast_convert_type(-(proposed * c), jnp.int32)
    step = jnp.round(rest.astype(jnp.float32)
                     / c.astype(jnp.float32)).astype(jnp.int32)
    m, rest = proposed.astype(jnp.int32) + step, rest - step * c_signed
    for _ in range(2):
        step = ((2 * rest > c_signed).astype(jnp.int32)
                - (2 * rest < -c_signed).astype(jnp.int32))
        m, rest = m + step, rest - step * c_signed
    return m


def _reciprocal_device(count):
    """A traced integer scalar ``1 <= count < 2^24`` -> ``1 / count``
    correctly rounded to float32, so that every backend gives the bits the
    host gives for ``np.float32(1.0 / count)``. The chip's float32
    division does not (PERF.md, PR 42), so its quotient only proposes and
    32-bit integers decide (``_settle_quotient``; a 64-bit division of a
    scalar is emulated on the TPU in some two thousand scalar ops).

    With ``2^n <= count < 2^(n+1)`` and ``c = count * 2^(23-n)`` in
    ``[2^23, 2^24)``, the reciprocal is ``M * 2^-(n+24)`` for ``M =
    round(2^47 / c)`` in ``[2^23, 2^24]``; the float quotient ``2^47 / c``
    is an integer as it stands. The float's bits are the exponent field of
    ``2^-(n+1)`` plus ``M - 2^23``; ``M == 2^24`` (a power of two's
    reciprocal) carries into the exponent, as it must."""
    import jax
    from jax import numpy as jnp

    count = count.astype(jnp.uint32)
    n = jnp.uint32(31) - jax.lax.clz(count)
    c = count << (jnp.uint32(23) - n)
    proposed = (jnp.float32(2.0 ** 47) / c.astype(jnp.float32)).astype(jnp.uint32)
    m = _settle_quotient(proposed, c)
    bits = ((jnp.int32(126) - n.astype(jnp.int32)) << 23) + (m - (1 << 23))
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


class FixedPointCodec:
    """Deterministic fixed-point codec float -> Z_m with summand capacity.

    Parameters
    ----------
    modulus:
        The aggregation modulus m (additive scheme modulus or the Shamir
        prime; resources.rs:44-67 carries it in-band in the Aggregation).
    fractional_bits:
        Scale = 2**fractional_bits. Quantization step is 2**-fractional_bits.
    max_summands:
        Largest number of vectors that will ever be summed under one
        aggregation (participants; clerk partial sums never exceed this).
        The decodable band is |sum| < m/2, so per-value magnitude is capped
        at clip = floor((m//2 - 1) / max_summands) / scale.
    clip:
        Optional tighter magnitude bound (floats are clamped to [-clip, clip]
        before quantization). Must not exceed the capacity-derived bound.
    norm_clip:
        Optional L2 bound enforced BY CONSTRUCTION: any vector whose
        Euclidean norm exceeds it is projected onto the norm_clip ball
        before quantization. This is the input-side poisoning defense —
        a boosted or sign-flipped update cannot contribute more L2 mass
        than an honest one, because the bound lives in the codec every
        client routes through, not in a flag a malicious client could
        skip. Host-lane only: the float64 norm reduction is not
        bit-reproducible across numpy and XLA, so ``encode_device``
        rejects the combination with a typed error.

    Adversarial floats (NaN/±Inf) clamp deterministically on BOTH lanes:
    NaN -> 0, ±Inf -> ±clip — never an undefined int cast (``np.clip``
    passes NaN through, so the scrub happens explicitly first).
    """

    __slots__ = ("modulus", "fractional_bits", "scale", "max_summands",
                 "clip", "norm_clip", "_q_max")

    def __init__(self, modulus: int, fractional_bits: int, max_summands: int,
                 clip: Optional[float] = None,
                 norm_clip: Optional[float] = None):
        modulus = int(modulus)
        if modulus < 3:
            raise ValueError("modulus must be >= 3")
        if max_summands < 1:
            raise ValueError("max_summands must be >= 1")
        self.modulus = modulus
        self.fractional_bits = int(fractional_bits)
        self.scale = float(1 << self.fractional_bits)
        self.max_summands = int(max_summands)
        q_cap = field_capacity(modulus, self.max_summands)
        if q_cap < 1:
            raise FieldSizingError(
                f"modulus {modulus} has no headroom for {max_summands} "
                f"summands: increase the modulus or lower max_summands"
            )
        cap = q_cap / self.scale
        if clip is None:
            clip = cap
        elif clip > cap:
            raise ValueError(
                f"clip {clip} exceeds the exactness capacity {cap:.6g} "
                f"(modulus {modulus}, {max_summands} summands, "
                f"{self.fractional_bits} fractional bits)"
            )
        elif clip <= 0:
            raise ValueError("clip must be positive")
        self.clip = float(clip)
        if norm_clip is not None:
            norm_clip = float(norm_clip)
            if not norm_clip > 0:
                raise ValueError("norm_clip must be positive")
        self.norm_clip = norm_clip
        self._q_max = int(round(self.clip * self.scale))
        # seal the invariant through the SHARED headroom rule (the same
        # one every analytics encoder calls), so the codec's capacity
        # derivation above and the field contract cannot drift apart
        field_headroom_check(max(1, self._q_max), self.max_summands,
                             modulus, context="FixedPointCodec")

    @property
    def q_max(self) -> int:
        """The integer quantization cap: |quantize(x)| <= q_max, so the
        worst-case sum magnitude is q_max * max_summands (< m/2 by the
        constructor's capacity rule)."""
        return self._q_max

    # -- host (numpy) path -------------------------------------------------

    def quantize(self, x) -> np.ndarray:
        """Float array -> signed quantized int64 in [-q_max, q_max].

        Quantization happens in float32 — the same arithmetic the device
        path uses — so host and device encodings are bit-identical (both
        numpy and XLA round half to even). Adversarial floats clamp
        deterministically: NaN -> 0 (np.clip would pass it through into
        an undefined int64 cast), ±Inf -> ±clip. With ``norm_clip``, the
        per-coordinate clamp happens FIRST (bounding every coordinate,
        Inf included), then the L2 projection — computed in float64 so
        the scale factor is deterministic — shrinks the whole vector
        onto the norm ball.
        """
        x32 = np.asarray(x, dtype=np.float32)
        x32 = np.where(np.isnan(x32), np.float32(0.0), x32)
        x32 = np.clip(x32, np.float32(-self.clip), np.float32(self.clip))
        if self.norm_clip is not None:
            x64 = x32.astype(np.float64)
            norm = float(np.sqrt(np.sum(x64 * x64)))
            if norm > self.norm_clip:
                x32 = (x64 * (self.norm_clip / norm)).astype(np.float32)
        q = np.rint(x32 * np.float32(self.scale)).astype(np.int64)
        return np.clip(q, -self._q_max, self._q_max)

    def encode(self, x) -> np.ndarray:
        """Float array -> representatives in [0, modulus) ready to share."""
        return np.mod(self.quantize(x), self.modulus).astype(np.int64)

    def _check_summands(self, summands: int, size: int) -> None:
        """The decoders' typed errors, host and device alike."""
        if summands < 1:
            # a zero/negative summand count is always a caller bug (an
            # empty frozen set, a None participation count propagated
            # into the mean): fail typed here rather than as a
            # ZeroDivisionError inside decode_mean or a silently wrong
            # "sum of zero things" — and name the aggregation context so
            # the error is actionable from a decoder stack trace
            raise ValueError(
                f"decode needs at least one summand, got {summands} "
                f"(aggregation: dim {size}, modulus {self.modulus}, "
                f"capacity {self.max_summands} summands; empty frozen "
                "set? use the revealed participation count)"
            )
        if summands > self.max_summands:
            raise ValueError(
                f"{summands} summands exceeds configured capacity "
                f"{self.max_summands} (aggregation: dim {size}, "
                f"modulus {self.modulus}); the sum may have wrapped"
            )

    def decode_sum(self, values, summands: int = 1) -> np.ndarray:
        """Aggregate in [0, m) -> exact float sum of the quantized inputs.

        ``summands`` is checked against the configured capacity; the lift is
        centered, matching RecipientOutput.positive()'s canonical band
        (receive.rs:14-21) shifted to (-m/2, m/2].
        """
        v = np.asarray(values, dtype=np.int64)
        self._check_summands(summands, v.size)
        v = np.mod(v, self.modulus)
        half = self.modulus // 2
        centered = v - np.where(v > half, self.modulus, 0)
        return centered.astype(np.float64) / self.scale

    def decode_mean(self, values, summands: int) -> np.ndarray:
        return self.decode_sum(values, summands) / float(summands)

    # -- device (jnp) path -------------------------------------------------

    def encode_device(self, x):
        """jnp float array -> int32 residues in [0, m), jit-friendly.

        Matches the host ``encode`` bit-for-bit: both paths clip, scale,
        and round in float32 (half-to-even). Requires clip * scale within
        float32's exact-integer range (2^24) so the rounded product is
        representable — the constructor's capacity rule keeps realistic
        FedAvg configs far below that. Output dtype is int32 (modulus <
        2^31 per fields/numtheory.py's device-limb constraint) so it feeds
        the pod/streamed paths directly.
        """
        from jax import numpy as jnp

        if self.norm_clip is not None:
            raise ValueError(
                f"norm_clip {self.norm_clip} is a host-lane contract: the "
                "L2 reduction is not bit-reproducible between numpy and "
                "XLA; use the host encode() for norm-clipped configs"
            )
        if self._q_max > (1 << 24):
            raise ValueError(
                f"q_max {self._q_max} exceeds float32's exact-integer range; "
                "use the host encode() for this configuration"
            )
        xf = jnp.asarray(x, jnp.float32)
        xf = jnp.where(jnp.isnan(xf), jnp.float32(0.0), xf)
        xc = jnp.clip(xf, jnp.float32(-self.clip), jnp.float32(self.clip))
        q = jnp.round(xc * jnp.float32(self.scale)).astype(jnp.int32)
        q = jnp.clip(q, -self._q_max, self._q_max)
        return jnp.where(q < 0, q + self.modulus, q).astype(jnp.int32)

    def _lift_device(self, values, summands: int):
        """jnp canonical residues in [0, m) -> the centered lift
        ``v - m * [v > m // 2]`` in integers; ``decode_sum``'s errors."""
        from jax import numpy as jnp

        v = jnp.asarray(values)
        self._check_summands(summands, v.size)
        v = v.astype(jnp.int32 if self.modulus < (1 << 31) else jnp.int64)
        return v - jnp.where(v > self.modulus // 2, self.modulus, 0)

    def decode_sum_device(self, values, summands: int = 1):
        """jnp aggregate -> float32 sum of the quantized inputs,
        jit-friendly; the typed errors of ``decode_sum``.

        ``values`` are canonical residues in [0, m) as a round reveals
        them, of any integer dtype; unlike the host method nothing is
        reduced here (a 64-bit remainder is emulated on the TPU). The lift
        is taken in integers, converted to float32 (one rounding, above
        2^24) and divided by the scale, a power of two.
        """
        from jax import numpy as jnp

        lifted = self._lift_device(values, summands)
        return lifted.astype(jnp.float32) / jnp.float32(self.scale)

    def decode_mean_device(self, values, summands, capacity=None):
        """jnp aggregate -> float32 mean of the quantized inputs: against
        ``decode_mean``, which divides in float64, that value rounded to
        float32 to within ``2^-23 * |mean|``.

        ``summands`` is a Python int, folded into the program, or a traced
        integer scalar -- a round's count of the rows that reported, which
        the compiled program reads -- with ``capacity``, the static number
        it cannot pass (the buffer's rows), checked in its place. A traced
        count of 0 (nobody reported: the aggregate is 0) gives a mean of
        exactly 0.

        Not ``decode_sum_device(...) / summands``: compiled, a division by
        a constant is a multiplication by its rounded reciprocal, a third
        rounding after the lift's conversion and the product's, and by a
        traced divisor it is the chip's float32 division, which is not
        correctly rounded (PERF.md, PR 42). The lift is split in integers
        instead, ``lift = whole * summands + rest`` (``lax.div``: toward
        zero, exact on every backend, by a traced divisor too), and

            mean = float32(whole) + float32(rest) * r,  r = float32(1 / summands)

        The bound, for every count within the capacity: ``|whole| <= q_max
        <= 2^24`` and ``|rest| < summands <= 2^24`` convert exactly; ``r``
        is correctly rounded (the constant by the host, the traced one by
        ``_reciprocal_device`` in integers: the same number bit for bit),
        so ``rest * r`` is within ``2^-23 |rest / summands|`` of ``rest /
        summands``, which is below 1 and has ``whole``'s sign; the sum
        rounds once, ``2^-24 |mean|``. ``whole == 0``: the sum is exact and
        the error ``2^-23 |mean|``. ``|mean|`` in ``[2^j, 2^(j+1))``, ``j
        >= 0``: at most ``2^(j-24) + 2^-23 (|mean| - 2^j) <= 2^-23 |mean|``.
        The division by the scale, a power of two, is exact.
        """
        import jax
        from jax import numpy as jnp

        if isinstance(summands, (int, np.integer)):
            lifted = self._lift_device(values, int(summands))
            count = jnp.asarray(summands, lifted.dtype)
            reciprocal = jnp.float32(1.0 / summands)
        else:
            if capacity is None:
                raise ValueError(
                    "a traced count of summands needs its static capacity "
                    "(the rows of the buffer the round summed over)")
            lifted = self._lift_device(values, int(capacity))
            count = jnp.maximum(jnp.asarray(summands, lifted.dtype), 1)
            reciprocal = _reciprocal_device(count)
        whole = jax.lax.div(lifted, count)  # toward zero: rest keeps the sign
        rest = lifted - whole * count
        mean = (whole.astype(jnp.float32)
                + rest.astype(jnp.float32) * reciprocal)
        return mean / jnp.float32(self.scale)

    # -- misc ----------------------------------------------------------------

    def __repr__(self):
        norm = ("" if self.norm_clip is None
                else f", norm_clip={self.norm_clip:.6g}")
        return (f"FixedPointCodec(modulus={self.modulus}, "
                f"fractional_bits={self.fractional_bits}, "
                f"max_summands={self.max_summands}, clip={self.clip:.6g}"
                f"{norm})")
