"""The interleave of ``k`` rows along the minor axis, and its inverse.

``out[k * b + j] = x[j, b]`` is the packed scheme's column layout going
back to a vector (``sharing.unbatch_columns``, ``k`` secrets a batch) and
the ChaCha draws' word-major layout going to the stream's order
(``chacha_jax.element_order``, ``k`` = 8 draws a block); the inverse is
``sharing.batch_columns``.

Written as ``moveaxis`` + ``reshape`` the TPU makes it a copy into an array
whose minor dimension ``k`` is padded to 128 lanes, a flatten and a row
loop, and moves it up through whatever elementwise arithmetic made its
input (a quarter of the round it was measured in: PERF.md, PR 30; a third
of the packed round, once a Lagrange term: PR 38, PR 43). So the
permutation goes through the matrix unit, which a round of integer
arithmetic leaves idle: each tile of ``k`` x 128 words (row ``j``, column
``r``) times the one-hot ``[(j, r), k r + j]`` is the tile's ``128 k`` words
interleaved, and the transposed product takes them apart. One matmul per
byte of the words: a byte is exact in bfloat16, every output is one product
by 1 plus zeros, and the float32 accumulator holds it exactly -- on any
backend, for any integer dtype (the top byte of a negative value comes
back through the shift).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_LANES = 128

# The most rows that go through the matrix unit. Its form costs 2 * 128 k
# flops a byte an element (k * 5.2 ps for a uint32 at the v5e's 197 TFLOP/s)
# and a one-hot of 32 KiB * k^2; the copy form moves 2 * 128 / k padded
# words an element through HBM (1.25 ns / k at 819 GB/s): they meet at
# k^2 = 240, where the one-hot is 8 MiB. Above it the minor dimension's
# pad shrinks (none from 128 rows on) while the matmul keeps growing with
# k, so wider interleaves stay moveaxis + reshape. The packed schemes of
# this repo's configurations and tests have k <= 3, the ChaCha draws 8.
_MATRIX_UNIT_MAX_ROWS = 16


def _permute_tiles(x, k: int, spec: str):
    """``x``'s tiles through the one-hot ``[k, 128, 128 k]`` by ``spec``, a
    matmul a byte of its dtype, the bytes put together again."""
    target = k * jnp.arange(_LANES)[None, :] + jnp.arange(k)[:, None]
    onehot = jax.nn.one_hot(target, k * _LANES, dtype=jnp.bfloat16)
    out = None
    for byte in range(x.dtype.itemsize):
        shift = jnp.asarray(8 * byte, x.dtype)
        plane = ((x >> shift) & jnp.asarray(0xFF, x.dtype)).astype(jnp.bfloat16)
        moved = jnp.einsum(spec, plane, onehot,
                           preferred_element_type=jnp.float32)
        moved = moved.astype(x.dtype) << shift
        out = moved if out is None else out | moved
    return out


def _pad_minor(x, size: int):
    """Zero-pad the minor axis up to ``size``."""
    if size == x.shape[-1]:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, size - x.shape[-1])])


def interleave(x):
    """``[..., k, B]`` -> ``[..., k * B]``, ``out[k * b + j] = x[j, b]``."""
    lead, (k, columns) = x.shape[:-2], x.shape[-2:]
    if k == 1:
        return x.reshape(lead + (columns,))
    if k > _MATRIX_UNIT_MAX_ROWS:
        return jnp.moveaxis(x, -2, -1).reshape(lead + (k * columns,))
    tiles = -(-columns // _LANES)  # whole lane tiles; the tail is cut below
    x = _pad_minor(x, tiles * _LANES).reshape(lead + (k, tiles, _LANES))
    out = _permute_tiles(x, k, "...jqr,jrl->...ql")
    return out.reshape(lead + (tiles * k * _LANES,))[..., :k * columns]


def deinterleave(x, k: int):
    """``[..., d]`` -> ``[..., k, ceil(d / k)]``, ``out[j, b] = x[k * b + j]``,
    zeros past ``d``: the inverse of :func:`interleave`."""
    lead, d = x.shape[:-1], x.shape[-1]
    columns = -(-d // k)
    if k == 1:
        return x.reshape(lead + (1, d))
    if k > _MATRIX_UNIT_MAX_ROWS:
        x = _pad_minor(x, k * columns).reshape(lead + (columns, k))
        return jnp.moveaxis(x, -1, -2)
    tiles = -(-columns // _LANES)
    x = _pad_minor(x, tiles * k * _LANES).reshape(lead + (tiles, k * _LANES))
    out = _permute_tiles(x, k, "...ql,jrl->...jqr")
    return out.reshape(lead + (k, tiles * _LANES))[..., :columns]
