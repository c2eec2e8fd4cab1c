"""The packed scheme's column layout, ``batch_columns`` / ``unbatch_columns``
(``fields/sharing.py``), as the de-interleave and interleave of ``k`` rows
through the matrix unit (``fields/layout.py``): bit for bit the NumPy
oracle's ``moveaxis`` + ``reshape`` (``fields/oracle.py``), for every width
on and off the 128-lane tile, every integer dtype over its whole range,
``k`` below and above the matrix unit's bound, any leading axes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from sda_tpu.fields import chacha_jax, layout, oracle, sharing
from sda_tpu.mesh.simpod import make_mesh

DIMS = [1, 2, 3, 10, 383, 384, 385, 1000, 333333 * 3]
ROWS = [1, 3, 5, 8, layout._MATRIX_UNIT_MAX_ROWS + 1]
DTYPES = [np.uint32, np.int32, np.int64]
LEADS = [(), (2,)]


def _values(dtype, shape, seed: int) -> np.ndarray:
    """The dtype's whole range, negative values where it has them, both
    ends in place."""
    info = np.iinfo(dtype)
    x = np.random.default_rng(seed).integers(
        info.min, info.max, size=shape, dtype=dtype, endpoint=True)
    flat = x.reshape(-1)
    flat[0], flat[-1] = info.max, info.min
    return x


def cases(test):
    """Every width x every ``k`` x every dtype x both leading shapes."""
    for name, values, ids in (
            ("lead", LEADS, ["vector", "stacked"]),
            ("dtype", DTYPES, lambda t: t.__name__),
            ("k", ROWS, None), ("d", DIMS, None)):
        test = pytest.mark.parametrize(name, values, ids=ids)(test)
    return test


@cases
def test_batch_columns_is_the_oracles(d, k, dtype, lead):
    x = _values(dtype, lead + (d,), d + k)
    got = np.asarray(sharing.batch_columns(jnp.asarray(x), k))
    assert got.dtype == dtype and got.shape == lead + (k, -(-d // k))
    np.testing.assert_array_equal(got, oracle.batch_columns(x, k).astype(dtype))


@cases
def test_unbatch_columns_is_the_oracles(d, k, dtype, lead):
    x = _values(dtype, lead + (k, -(-d // k)), d + k)
    got = np.asarray(sharing.unbatch_columns(jnp.asarray(x), d))
    assert got.dtype == dtype and got.shape == lead + (d,)
    np.testing.assert_array_equal(got, oracle.unbatch_columns(x, d))


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda t: t.__name__)
@pytest.mark.parametrize("k", ROWS)
@pytest.mark.parametrize("d", [1, 385, 1000])
def test_unbatch_columns_undoes_batch_columns(d, k, dtype):
    x = _values(dtype, (2, d), 7 * d + k)
    columns = sharing.batch_columns(jnp.asarray(x), k)
    np.testing.assert_array_equal(
        np.asarray(sharing.unbatch_columns(columns, d)), x)


@pytest.mark.parametrize("dtype", [np.uint32, np.uint64],
                         ids=lambda t: t.__name__)
@pytest.mark.parametrize("nblocks", [1, 128, 130])
def test_element_order_is_unbatch_columns_of_eight_rows(nblocks, dtype):
    """The ChaCha draws' word-major layout is the column layout at ``k`` = 8."""
    words = jnp.asarray(_values(dtype, (2, 8, nblocks), nblocks))
    np.testing.assert_array_equal(
        np.asarray(chacha_jax.element_order(words)),
        np.asarray(sharing.unbatch_columns(words, 8 * nblocks)))


@pytest.mark.parametrize("how", ["jit", "shard_map"])
def test_the_pair_under_a_trace(how):
    """Traced into a caller's program, and inside ``shard_map`` on a 1 x 2
    mesh, where every shard changes the layout of its own ``d_loc``."""
    k, d_loc = 3, 1000

    def there_and_back(x):
        columns = sharing.batch_columns(x, k)
        return columns, sharing.unbatch_columns(columns, x.shape[-1])

    if how == "jit":
        x = _values(np.uint32, (d_loc,), 43)
        columns, back = jax.jit(there_and_back)(jnp.asarray(x))
        shards = [x]
    else:
        x = _values(np.uint32, (2 * d_loc,), 43)
        columns, back = jax.jit(jax.shard_map(
            there_and_back, mesh=make_mesh(1, 2), in_specs=P("d"),
            out_specs=(P(None, "d"), P("d"))))(jnp.asarray(x))
        shards = np.split(x, 2)
    np.testing.assert_array_equal(np.asarray(back), x)
    np.testing.assert_array_equal(
        np.asarray(columns),
        np.concatenate([oracle.batch_columns(s, k) for s in shards], axis=-1))
