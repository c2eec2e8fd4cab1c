"""Device-side ChaCha20 expansion vs the host oracle — bit-exact.

CHACHA_PRG_V1 is a versioned wire spec (fields/chacha.py): the jnp
implementation must reproduce it word-for-word, including the overdraw
layout and the mod reduction, for any seed and modulus — and the combined
(recipient hot loop) path must match per-seed host expansion summed.
"""

import numpy as np
import pytest

from sda_tpu.fields import chacha, chacha_jax


@pytest.mark.parametrize("seed", [
    [0], [1, 2, 3, 4], [0xFFFFFFFF] * 8, [0xDEADBEEF, 0x12345678],
])
@pytest.mark.parametrize("nblocks", [1, 3, 7])
def test_block_words_match_host(seed, nblocks):
    seed_words = np.zeros(8, dtype=np.uint32)
    for i, w in enumerate(seed):
        seed_words[i] = np.uint32(w)
    got = np.asarray(chacha_jax.chacha_block_words(seed_words, 0, nblocks=nblocks))
    exp = chacha.chacha_block_words(seed, 0, nblocks)
    np.testing.assert_array_equal(got, exp)


@pytest.mark.parametrize("modulus", [433, 536870233, (1 << 61) + 1 - 2])
@pytest.mark.parametrize("dimension", [1, 7, 8, 9, 100, 1000])
def test_expand_mask_matches_host(modulus, dimension):
    seed = chacha.random_seed(128)
    got = chacha_jax.expand_mask(seed, dimension, modulus, prg=chacha.CHACHA_PRG_V1)
    exp = chacha.expand_mask(seed, dimension, modulus)
    np.testing.assert_array_equal(got, exp)


def test_combine_masks_matches_host_sum():
    modulus, dimension = 536870233, 257
    seeds = [chacha.random_seed(128) for _ in range(5)]
    got = chacha_jax.combine_masks(seeds, dimension, modulus, prg=chacha.CHACHA_PRG_V1)
    exp = np.zeros(dimension, dtype=np.int64)
    for s in seeds:
        exp = (exp + chacha.expand_mask(s, dimension, modulus)) % modulus
    np.testing.assert_array_equal(got, exp)


def test_combine_masks_large_modulus_no_i64_overflow():
    """A flat int64 sum of S masks wraps once S*(modulus-1) >= 2^63; the
    chunked modular fold must stay exact (advisor round-1 finding)."""
    modulus = (1 << 61) - 1  # 4+ masks of this size overflow a flat i64 sum
    dimension = 33
    seeds = [chacha.random_seed(128) for _ in range(9)]
    got = chacha_jax.combine_masks(seeds, dimension, modulus, prg=chacha.CHACHA_PRG_V1)
    exp = np.zeros(dimension, dtype=object)
    for s in seeds:
        exp = (exp + chacha.expand_mask(s, dimension, modulus)) % modulus
    np.testing.assert_array_equal(got, exp.astype(np.int64))


def test_combine_masks_rejects_out_of_range_modulus():
    with pytest.raises(ValueError):
        chacha_jax.combine_masks([[1]], 4, 1 << 62, prg=chacha.CHACHA_PRG_V1)


def test_native_oracle_agreement():
    """When the C++ kernel is available, all three implementations agree."""
    from sda_tpu import native

    if not native.available():
        pytest.skip("native library unavailable")
    modulus, dimension = 433, 123
    seed = [7, 11, 13, 17]
    a = chacha.expand_mask(seed, dimension, modulus)
    b = chacha_jax.expand_mask(seed, dimension, modulus, prg=chacha.CHACHA_PRG_V1)
    c = native.chacha_expand_mask(seed, dimension, modulus, prg=chacha.CHACHA_PRG_V1)
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(a, c)


# -- the word-major stream and the pod's mask stage (PR 30) ----------------------
#
# The pod's mask stage keeps the draws in the layout the block function
# produces (word-major [S, 8, nblocks]) until they are residues, and puts
# only those in element order. Every mask is still the draw it was:
# mask[s, 8b + j] = ((w[2j+1] << 32) | w[2j]) mod p of block b.

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec  # noqa: E402

from sda_tpu.fields import numtheory  # noqa: E402
from sda_tpu.fields.ops import FieldOps  # noqa: E402
from sda_tpu.mesh import simpod  # noqa: E402
from sda_tpu.protocol import ChaChaMasking, PackedShamirSharing  # noqa: E402

from util import external_bits  # noqa: E402

_SEEDS = {
    "1word": [[0x9E3779B9], [7]],
    "4words": [[1, 2, 3, 4], [0xDEADBEEF, 0x12345678, 0, 0xFFFFFFFF]],
    "8words": [[0xFFFFFFFF] * 8, list(range(11, 19))],
}


def _seed_matrix(seeds) -> np.ndarray:
    matrix = np.zeros((len(seeds), 8), dtype=np.uint32)
    for row, seed in enumerate(seeds):
        matrix[row, :len(seed)] = seed
    return matrix


def _host_stream(seed, block0: int, dimension: int) -> np.ndarray:
    """The host oracle's draws [8 * block0, 8 * block0 + dimension)."""
    words = chacha.chacha_block_words(seed, block0, dimension // 8)
    words = words.reshape(-1).astype(np.uint64)
    return (words[1::2] << np.uint64(32)) | words[0::2]


@pytest.mark.parametrize("dimension", [8, 96, 1000])
@pytest.mark.parametrize("window", ["counter0", "traced-window"])
@pytest.mark.parametrize("words", list(_SEEDS))
def test_word_major_stream_in_element_order_is_the_stream(words, window, dimension):
    seeds = _SEEDS[words]
    matrix = jnp.asarray(_seed_matrix(seeds))
    nblocks = dimension // 8
    block0 = 0 if window == "counter0" else 3 * nblocks + 5

    def both(counter0):
        return (chacha_jax.stream_u64_words_at(matrix, counter0, nblocks=nblocks),
                chacha_jax.stream_u64_at(matrix, counter0, dimension=dimension))

    # traced, as axis_index('d') * blocks_per_shard is under shard_map
    wordmajor, stream = jax.jit(both)(block0) if block0 else both(0)
    assert wordmajor.shape == (len(seeds), 8, nblocks) and wordmajor.dtype == jnp.uint64
    ordered = np.asarray(chacha_jax.element_order(wordmajor))
    np.testing.assert_array_equal(ordered, np.asarray(stream))
    host = np.stack([_host_stream(seed, block0, dimension) for seed in seeds])
    np.testing.assert_array_equal(ordered, host)
    # draw j of block b sits at [j, b]: no transpose hides in the pairing
    np.testing.assert_array_equal(
        np.asarray(wordmajor)[:, 3, nblocks - 1], host[:, 8 * (nblocks - 1) + 3])


@pytest.mark.parametrize("shape", [(8, 1), (8, 37), (3, 8, 128), (2, 5, 8, 130)],
                         ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("dtype,top", [(np.uint32, 1 << 32), (np.uint64, 1 << 64),
                                       (np.int64, 1 << 62)],
                         ids=["uint32", "uint64", "int64"])
def test_element_order_is_the_interleave_bit_for_bit(dtype, top, shape):
    """The one-hot matmul moves every byte of every word where
    ``swapaxes(-1, -2).reshape`` puts it: full-range words, block counts on
    and off the 128-lane tile, any leading dimensions."""
    rng = np.random.default_rng(len(shape) * 1000 + shape[-1])
    words = rng.integers(0, top, size=shape, dtype=np.uint64).astype(dtype)
    words[..., 0, 0], words[..., -1, -1] = 0, top - 1  # both ends of the range
    got = np.asarray(chacha_jax.element_order(jnp.asarray(words)))
    assert got.dtype == dtype
    np.testing.assert_array_equal(
        got, np.swapaxes(words, -1, -2).reshape(shape[:-2] + (-1,)))


@pytest.mark.parametrize("blocks", [125, 128, 1001])
@pytest.mark.parametrize("rows", [1, 8, 13])
def test_the_fold_of_the_rows_in_element_order_is_the_fold_of_the_ordered_rows(
        rows, blocks):
    """The modular fold over the rows is elementwise and ``element_order``
    a permutation: fold first on the word-major residues and order the one
    ``[8, blocks]`` result (the pod's mask stage, PR 40), or order every
    row and fold -- the same vector bit for bit. Residues up to p - 1, a
    column of them in every row; block counts under, on and past whole
    lane tiles."""
    p = 536870233
    field = FieldOps.create(p)
    rng = np.random.default_rng(rows * 10_000 + blocks)
    residues = rng.integers(0, p, size=(rows, 8, blocks), dtype=np.uint32)
    residues[:, 3, blocks // 2] = p - 1
    residues[0, 0, 0] = residues[-1, -1, -1] = p - 1
    r = jnp.asarray(residues)
    fold_first = np.asarray(chacha_jax.element_order(field.sum(r, axis=0)))
    order_first = np.asarray(field.sum(chacha_jax.element_order(r), axis=0))
    assert fold_first.shape == (8 * blocks,) and fold_first.dtype == np.uint32
    np.testing.assert_array_equal(fold_first, order_first)
    want = np.swapaxes(residues, -1, -2).reshape(rows, -1).astype(np.uint64).sum(axis=0)
    np.testing.assert_array_equal(fold_first, want % np.uint64(p))


@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs 2 virtual devices")
@pytest.mark.parametrize("rows", [5, 8, 13, 16])
@pytest.mark.parametrize("path", ["xla-stage", "kernel-path-sum"])
def test_the_masks_sum_is_the_sum_of_the_host_oracles_streams_window_by_window(
        path, rows):
    """Both mask passes fold word-major and order the fold: the block
    function's fold of the rows it is given, ordered once, and the masks'
    sum the steps take, a scan 8 rows at a time (13 rows expand 16: the
    ids after the last row cancel like any mask). Each 'd' shard expands
    its own window at a traced block counter; held to
    ``fields/chacha.py``'s block function, reduced on the host."""
    p, dim, first_id = 536870233, 96, 7
    d_loc = dim // 2
    field, masking = FieldOps.create(p), ChaChaMasking(p, dim, 128)
    round_key = jax.random.PRNGKey(11)

    def local(x):
        block0 = jax.lax.axis_index("d") * (d_loc // 8)
        if path == "xla-stage":
            return simpod._element_order(simpod._chacha_mask_fold(
                masking, field, round_key, first_id, x.shape[0], d_loc, block0))
        return simpod._chacha_mask_sum(masking, field, round_key, first_id, rows,
                                       d_loc, block0)

    sharded = simpod._shard_map(
        local, mesh=simpod.make_mesh(1, 2), in_specs=PartitionSpec(None, "d"),
        out_specs=PartitionSpec("d"))
    mask_sum = np.asarray(jax.jit(sharded)(jnp.zeros((rows, dim), field.dtype)))

    expanded = rows if path == "xla-stage" else simpod._scan_rows(rows, simpod._SCAN_CHUNK)[1]
    seeds = np.asarray(simpod._chacha_seed_words(
        round_key, first_id + jnp.arange(expanded), 128))
    for window in range(2):
        want = sum(_host_stream([int(w) for w in seed], window * (d_loc // 8), d_loc)
                   % np.uint64(p) for seed in seeds) % np.uint64(p)
        np.testing.assert_array_equal(
            mask_sum[window * d_loc:(window + 1) * d_loc].astype(np.uint64), want)


@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs 2 virtual devices")
@pytest.mark.parametrize("step", ["xla", "pallas-interpret"])
def test_mask_stage_masks_are_the_stream_mod_p_row_for_row_on_a_sharded_dim(step):
    """Two 'd' shards, each expanding its own window of every row's stream
    at a traced block counter, as ``SimulatedPod._local_round`` calls its
    local step. Zero inputs, so what comes out is the masks."""
    t, p, w2, w3 = numtheory.generate_packed_params(3, 8, 28)
    scheme = PackedShamirSharing(3, 8, t, p, w2, w3)  # the kernel's: a Solinas prime
    rows, dim, first_id = 5, 96, 7
    d_loc = dim // 2
    field = FieldOps.create(p)
    masking = ChaChaMasking(p, dim, 128)
    round_key, dev_key = jax.random.PRNGKey(11), jax.random.PRNGKey(2)
    matrices = simpod._build_matrices(scheme)

    def local(x):
        block0 = jax.lax.axis_index("d") * (d_loc // 8)
        if step == "xla":
            # the step returns folds alone: one row a call, like the kernel's
            sums = [simpod._scan_combine(
                scheme, field, matrices[0], masking, x[row:row + 1], dev_key,
                round_key=round_key, pid_base=first_id + row,
                d_block0=block0)[1]
                for row in range(rows)]
            _, mask_sum = simpod._scan_combine(
                scheme, field, matrices[0], masking, x, dev_key,
                round_key=round_key, pid_base=first_id, d_block0=block0)
            return jnp.stack(sums), mask_sum
        sums = [simpod._pallas_stage(
            scheme, field, matrices[0], masking, x[row:row + 1], dev_key,
            round_key=round_key, pid_base=first_id + row, d_block0=block0,
            interpret=True, external_bits_fn=external_bits)[1]
            for row in range(rows)]
        _, mask_sum = simpod._pallas_stage(
            scheme, field, matrices[0], masking, x, dev_key,
            round_key=round_key, pid_base=first_id, d_block0=block0,
            interpret=True, external_bits_fn=external_bits)
        return jnp.stack(sums), mask_sum

    sharded = simpod._shard_map(
        local, mesh=simpod.make_mesh(1, 2), in_specs=PartitionSpec(None, "d"),
        out_specs=(PartitionSpec(None, "d"), PartitionSpec("d")))
    masks, mask_sum = jax.jit(sharded)(jnp.zeros((rows, dim), field.dtype))

    seeds = simpod._chacha_seed_words(round_key, first_id + jnp.arange(rows), 128)
    want = np.asarray(chacha_jax.stream_u64_at(seeds, 0, dimension=dim)) % np.uint64(p)
    np.testing.assert_array_equal(np.asarray(masks).astype(np.uint64), want)
    np.testing.assert_array_equal(
        np.asarray(mask_sum).astype(np.uint64), want.sum(axis=0) % np.uint64(p))


def test_mask_stage_reads_the_keystream_with_no_gather():
    """Until PR 30 the stage paired the cipher's words with two strided
    reads of the flat keystream, which the TPU runs as gathers over every
    draw (two thirds of the round, PERF.md). Pairing the per-word arrays
    needs none; the only gathers left read the [S, 8] seed words."""
    p, rows, dim = 536870233, 8, 96
    field = FieldOps.create(p)

    def stage(round_key, block0):
        return simpod._chacha_mask_sum(ChaChaMasking(p, dim, 128), field,
                                       round_key, 0, rows, dim, block0,
                                       cipher="xla")

    text = jax.jit(stage).lower(
        jax.random.PRNGKey(1), jnp.int32(0)).as_text(debug_info=True)
    assert "sda.mask/sda.mask.relayout" in text
    keystream = rows * dim * 2  # uint32 words of the block's draws
    gathers = [line for line in text.splitlines() if "stablehlo.gather" in line]
    for line in gathers:
        # "... : (tensor<8x8xui32>, tensor<...xi32>) -> ..." : the operand
        operand = line.split(" : (tensor<")[1].split(">")[0]
        sizes = [int(n) for n in operand.split("x")[:-1]]
        assert int(np.prod(sizes)) < keystream, line
