"""Bit-exactness of the uint32 Solinas fast path vs exact integer math.

Every kernel in sda_tpu.fields.fastfield must agree with Python big-int
arithmetic on worst-case operands; the fast path may only change speed,
never results (SURVEY.md §2.2 oracle discipline).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from sda_tpu.fields import fastfield as ff
from sda_tpu.fields import numtheory
from sda_tpu.fields.ops import FieldOps

P29 = 536870233   # 2^29 - 679, ≡ 1 mod 72
P28 = 268435009   # 2^28 - 447, ≡ 1 mod 72


@pytest.mark.parametrize("p,expected", [
    (P29, True),
    (P28, True),
    (433, False),                  # too small
    ((1 << 30) + 3, False),        # b = 31 > 29
    ((1 << 29) - (1 << 15), False) # delta too large
])
def test_try_from_gating(p, expected):
    assert (ff.SolinasPrime.try_from(p) is not None) == expected
    assert ff.supported(p) == expected


@pytest.fixture(params=[P29, P28])
def sp(request):
    return ff.SolinasPrime.try_from(request.param)


def test_canon32_full_range(sp):
    rng = np.random.default_rng(0)
    p = sp.p
    v = np.concatenate([
        rng.integers(0, 1 << 32, size=20000, dtype=np.uint64).astype(np.uint32),
        np.array([0, 1, p - 1, p, p + 1, 2**32 - 1, 2**31, 2**30], dtype=np.uint32),
    ])
    got = np.asarray(ff.canon32(jnp.asarray(v), sp))
    np.testing.assert_array_equal(got.astype(object), v.astype(object) % p)


def test_addsub_mulconst(sp):
    rng = np.random.default_rng(1)
    p = sp.p
    a = rng.integers(0, p, size=20000).astype(np.uint32)
    b = rng.integers(0, p, size=20000).astype(np.uint32)
    np.testing.assert_array_equal(
        np.asarray(ff.modadd32(jnp.asarray(a), jnp.asarray(b), sp)).astype(object),
        (a.astype(object) + b) % p,
    )
    np.testing.assert_array_equal(
        np.asarray(ff.modsub32(jnp.asarray(a), jnp.asarray(b), sp)).astype(object),
        (a.astype(object) - b) % p,
    )
    for c in (0, 1, p - 1, 12345, (1 << 30) % p, (1 << 32) % p):
        got = np.asarray(ff.mulmod32_const(jnp.asarray(a), c, sp))
        np.testing.assert_array_equal(got.astype(object), a.astype(object) * c % p)


def test_modsum32(sp):
    rng = np.random.default_rng(2)
    p = sp.p
    # worst case: all terms p-1, count straddling the fold fan-in
    for n in (1, 2, 7, 8, 9, 100, 1000):
        x = np.full((n, 33), p - 1, dtype=np.uint32)
        got = np.asarray(ff.modsum32(jnp.asarray(x), sp, axis=0))
        np.testing.assert_array_equal(got.astype(object), (n * (p - 1)) % p)
    x = rng.integers(0, p, size=(321, 50)).astype(np.uint32)
    got = np.asarray(ff.modsum32(jnp.asarray(x), sp, axis=0))
    np.testing.assert_array_equal(got.astype(object), x.astype(object).sum(0) % p)


def test_modmatmul32_worst_case(sp):
    rng = np.random.default_rng(3)
    p = sp.p
    for (n, k, B) in [(8, 8, 257), (3, 9, 130), (16, 16, 64), (1, 1, 8)]:
        M = rng.integers(0, p, size=(n, k))
        M[:, : min(2, k)] = p - 1
        V = rng.integers(0, p, size=(k, B)).astype(np.uint32)
        V[:, : min(5, B)] = p - 1
        got = np.asarray(ff.modmatmul32(M, jnp.asarray(V), sp))
        exp = (M.astype(object) @ V.astype(object)) % p
        np.testing.assert_array_equal(got.astype(object), exp)


def test_np_oracle_matches_bigint(sp):
    """np_modmatmul32 (the module's own NumPy oracle) must agree with the
    exact bigint product — it is what audits device results elsewhere."""
    rng = np.random.default_rng(9)
    p = sp.p
    M = rng.integers(0, p, size=(8, 7))
    V = rng.integers(0, p, size=(7, 65)).astype(np.uint32)
    got = ff.np_modmatmul32(M, V, sp)
    exp = (M.astype(object) @ V.astype(object)) % p
    np.testing.assert_array_equal(got.astype(object), exp)
    # and the device kernel agrees with the oracle
    dev = np.asarray(ff.modmatmul32(M, jnp.asarray(V), sp))
    np.testing.assert_array_equal(dev, got)


def test_modmatmul32_batched(sp):
    rng = np.random.default_rng(4)
    p = sp.p
    M = rng.integers(0, p, size=(8, 8))
    V = rng.integers(0, p, size=(5, 8, 33)).astype(np.uint32)
    got = np.asarray(ff.modmatmul32(M, jnp.asarray(V), sp))
    exp = np.stack([
        (M.astype(object) @ V[i].astype(object)) % p for i in range(V.shape[0])
    ])
    np.testing.assert_array_equal(got.astype(object), exp)


def test_uniform32_range_and_mean(sp):
    u = np.asarray(ff.uniform32(jax.random.PRNGKey(7), (100000,), sp))
    assert u.dtype == np.uint32
    assert int(u.max()) < sp.p
    assert abs(u.mean() / sp.p - 0.5) < 0.01


# -- 64-bit values from their uint32 halves --------------------------------------

P20 = 1048573     # 2^20 - 3: the narrowest width try_from admits


@pytest.fixture(params=[P29, P28, P20])
def sp64(request):
    sp = ff.SolinasPrime.try_from(request.param)
    assert sp is not None
    return sp


def test_reduce64_edge_halves(sp64):
    p = sp64.p
    edge = [0, 1, p - 1, p, 2**32 - 1]
    hi, lo = np.meshgrid(np.asarray(edge, np.uint32), np.asarray(edge, np.uint32),
                         indexing="ij")
    got = np.asarray(ff.reduce64(jnp.asarray(hi), jnp.asarray(lo), sp64))
    assert got.dtype == np.uint32
    want = [[((h << 32) | l) % p for l in edge] for h in edge]
    assert want[-1][-1] == (2**64 - 1) % p
    assert got.tolist() == want


def test_reduce64_random_pairs(sp64):
    rng = np.random.default_rng(64)
    hi = rng.integers(0, 1 << 32, size=100_000, dtype=np.uint64).astype(np.uint32)
    lo = rng.integers(0, 1 << 32, size=100_000, dtype=np.uint64).astype(np.uint32)
    got = np.asarray(ff.reduce64(jnp.asarray(hi), jnp.asarray(lo), sp64))
    want = ((hi.astype(object) << 32) | lo.astype(object)) % sp64.p
    np.testing.assert_array_equal(got.astype(object), want)


def _words(key, shape):
    """High and low uint32 words of the one uint64 draw of ``shape``."""
    bits = np.asarray(jax.random.bits(key, shape=shape, dtype=jnp.uint64))
    return (bits >> np.uint64(32)).astype(np.uint32), bits.astype(np.uint32)


def test_uniform32_is_reduce64_of_its_two_words(sp64):
    # the words are the high and the low half of ONE uint64 draw of `shape`:
    # a threefry block an element, both of its output words kept
    key = jax.random.PRNGKey(32)
    hi, lo = _words(key, (3, 1000))
    want = ff.reduce64(jnp.asarray(hi), jnp.asarray(lo), sp64)
    np.testing.assert_array_equal(
        np.asarray(ff.uniform32(key, (3, 1000), sp64)), np.asarray(want))
    np.testing.assert_array_equal(
        np.asarray(ff.random_bits64(key, (3, 1000))),
        (hi.astype(np.uint64) << np.uint64(32)) | lo.astype(np.uint64))


@pytest.mark.parametrize("shape", [(5,), (3, 1000), (2, 2, 257)])
def test_uniform32_equals_uniform_mod_element_for_element(sp64, shape):
    from sda_tpu.fields import modular

    key = jax.random.PRNGKey(sp64.b)
    fast = np.asarray(ff.uniform32(key, shape, sp64))
    generic = np.asarray(modular.uniform_mod(key, shape, sp64.p))
    assert fast.dtype == np.uint32 and generic.dtype == np.int64
    np.testing.assert_array_equal(fast.astype(np.int64), generic)


@pytest.mark.parametrize("foreign", ["hi", "lo"])
def test_uniform32_takes_both_halves_of_its_draw(sp64, foreign):
    key, other = jax.random.PRNGKey(1), jax.random.PRNGKey(2)
    hi, lo = _words(key, (4096,))
    ohi, olo = _words(other, (4096,))
    assert (hi != lo).mean() > 0.99  # two words, not one word twice
    mixed = (ohi, lo) if foreign == "hi" else (hi, olo)
    got = np.asarray(ff.reduce64(jnp.asarray(mixed[0]), jnp.asarray(mixed[1]), sp64))
    own = np.asarray(ff.uniform32(key, (4096,), sp64))
    assert (got != own).mean() > 0.99


def test_uniform32_every_residue_bit_is_balanced(sp64):
    u = np.asarray(ff.uniform32(jax.random.PRNGKey(11), (100_000,), sp64))
    assert int(u.max()) < sp64.p and int(u.min()) >= 0
    assert abs(u.mean() / sp64.p - 0.5) < 0.01
    # bits under the top one of p = 2^b - delta are fair coins to delta/2^b;
    # 1e5 draws put a fair coin's mean within 0.008 of a half at 5 sigma
    for bit in range(sp64.b - 1):
        assert abs(((u >> bit) & 1).mean() - 0.5) < 0.008, bit
    top = ((u >> (sp64.b - 1)) & 1).mean()
    assert abs(top - (sp64.p - (1 << (sp64.b - 1))) / sp64.p) < 0.008


def test_uniform32_rows_differ_across_keys_and_leading_indices(sp64):
    # every participant's every free row is a draw of its own: no two rows
    # of one [S, n - 1, d] draw agree, nor do two keys' draws
    one = np.asarray(ff.uniform32(jax.random.PRNGKey(3), (2, 4, 512), sp64))
    two = np.asarray(ff.uniform32(jax.random.PRNGKey(4), (2, 4, 512), sp64))
    rows = np.concatenate([one.reshape(-1, 512), two.reshape(-1, 512)])
    for i in range(len(rows)):
        for j in range(i):
            assert (rows[i] == rows[j]).mean() < 0.02, (i, j)


def test_a_64_bit_draw_is_refused_where_it_would_be_narrowed(sp64):
    from sda_tpu.fields import modular

    with jax.enable_x64(False):
        with pytest.raises(RuntimeError, match="jax_enable_x64"):
            ff.uniform32(jax.random.PRNGKey(0), (8,), sp64)
        with pytest.raises(RuntimeError, match="jax_enable_x64"):
            modular.uniform_mod(jax.random.PRNGKey(0), (8,), sp64.p)


def _int64_samples(p):
    rng = np.random.default_rng(p)
    edge = [0, 1, -1, p, -p, p - 1, 1 - p, 2**32, -2**32, 2**63 - 1, -2**63]
    return np.concatenate([
        np.asarray(edge, np.int64),
        rng.integers(-2**63, 2**63 - 1, size=50_000, dtype=np.int64, endpoint=True),
        rng.integers(-2**33, 2**33, size=10_000, dtype=np.int64),
    ])


def test_to_residues32_int64_is_the_python_modulo(sp64):
    x = _int64_samples(sp64.p)
    got = np.asarray(ff.to_residues32(jnp.asarray(x), sp64))
    assert got.dtype == np.uint32
    np.testing.assert_array_equal(got.astype(object), x.astype(object) % sp64.p)
    # and what the 64-bit pass returned, bit for bit
    np.testing.assert_array_equal(
        got, np.asarray(jnp.mod(jnp.asarray(x), sp64.p)).astype(np.uint32))


@pytest.mark.parametrize("dtype", [np.int8, np.int16, np.uint8, np.uint16,
                                   np.uint64, np.bool_])
def test_to_residues32_other_dtypes_read_as_int64(sp64, dtype):
    # astype wraps: every bit pattern of the narrow types, and uint64s on
    # both sides of 2^63 (those above read as negatives, as the cast does)
    x = jnp.asarray(_int64_samples(sp64.p).astype(dtype))
    got = ff.to_residues32(x, sp64)
    assert got.dtype == jnp.uint32
    want = jnp.mod(x.astype(jnp.int64), sp64.p).astype(jnp.uint32)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _traces_rem(fn, *args) -> bool:
    return " rem " in str(jax.make_jaxpr(fn)(*args))


def test_to_residues32_traces_no_remainder_for_any_dtype(sp64):
    for dtype in (jnp.int8, jnp.uint16, jnp.int32, jnp.uint32, jnp.int64, jnp.uint64):
        assert not _traces_rem(lambda x: ff.to_residues32(x, sp64),
                               jnp.zeros((4,), dtype))


def test_from_u64_over_a_solinas_modulus_is_the_64_bit_modulo(sp64):
    field = FieldOps.create(sp64.p)
    assert field.sp is not None
    v = jnp.asarray(_int64_samples(sp64.p).view(np.uint64))
    got = field.from_u64(v)
    assert got.dtype == jnp.uint32
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(jnp.mod(v, jnp.uint64(sp64.p))).astype(np.uint32))
    assert not _traces_rem(field.from_u64, v)


def test_from_u64_keeps_the_generic_modulo_for_a_modulus_off_the_fast_path():
    field = FieldOps.create(433)
    assert field.sp is None
    v = jnp.asarray(_int64_samples(433).view(np.uint64))
    got = field.from_u64(v)
    assert got.dtype == jnp.int64
    np.testing.assert_array_equal(
        np.asarray(got).astype(object), np.asarray(v).astype(object) % 433)
    assert _traces_rem(field.from_u64, v)


def test_generated_packed_params_prefer_solinas():
    """The default prime generator should land on fast-path primes when a
    Solinas candidate exists in range (so flagship rounds use uint32)."""
    t, p, w2, w3 = numtheory.generate_packed_params(3, 8, 28)
    assert ff.supported(p), f"generated prime {p} misses the fast path"
    numtheory.validate_packed_scheme(3, 8, t, p, w2, w3)
    # out-of-range request still produces a valid (generic-path) scheme
    t2, p2, _, _ = numtheory.generate_packed_params(3, 8, 30)
    assert p2 >= (1 << 30) and numtheory.is_prime(p2)
