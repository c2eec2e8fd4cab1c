"""Programs of the main path compiled at their real size for a TPU v5e
that is described, not attached: what the chip's compiler makes of them,
at no chip time. Nothing runs; a compile that passes is not a chip run.

One file, and the topology is described inside a fixture: only one process
may load the TPU's library, so the worker that is given this file loads it
and every other worker collects the same tests without touching it.
"""

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest

from sda_tpu.fields.ops import FieldOps
from sda_tpu.mesh import simpod
from sda_tpu.protocol import AdditiveSharing, ChaChaMasking

from util import external_bits, lowered_ops

MODULUS = 536870233  # 2^29 - 679: the uint32 field path


def _packed_scheme():
    """Packed Shamir 3/8/4 over ``MODULUS``: the packed cells' scheme."""
    from sda_tpu.fields import numtheory
    from sda_tpu.protocol import PackedShamirSharing

    t, p, w2, w3 = numtheory.generate_packed_params(3, 8, 28)
    assert p == MODULUS
    return PackedShamirSharing(3, 8, t, p, w2, w3)


@pytest.fixture(scope="module")
def v5e_host():
    """The four described chips of a v5e:2x2 host."""
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or it logs under /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return topo.devices


@pytest.fixture(scope="module")
def one_chip(v5e_host):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(v5e_host[0])


ROWS, DIM = 8, 1_000_000  # one scan block of the cell ``additive-chacha-1m``


def _compiled(lowered):
    """A program lowered for the described chip, compiled."""
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep it out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return lowered.compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _compile_for(one_chip, stage, *args):
    """``stage`` compiled for the described chip on ``(shape, dtype)`` args."""
    return _compiled(jax.jit(stage).lower(*(
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for shape, dtype in args)))


@pytest.fixture(scope="module")
def mask_stage_compiled(one_chip):
    """The XLA step's ChaCha masks on one scan block: the masks' sum of 8
    rows x 1,000,000 (``_chacha_mask_sum`` with the XLA block function, the
    cipher of every step not built for a TPU; a step built for a TPU takes
    the on-core cipher) at a traced block counter, and the rows' fold with
    it added: -> (masked fold, masks' sum)."""
    field = FieldOps.create(MODULUS)

    def stage(x, round_key, pid_base, block0):
        mask_sum = simpod._chacha_mask_sum(
            ChaChaMasking(MODULUS, DIM, 128), field, round_key, pid_base, ROWS,
            DIM, block0, cipher="xla")
        return field.add(field.sum(x, axis=0), mask_sum), mask_sum

    return _compile_for(
        one_chip, stage, ((ROWS, DIM), jnp.uint32), ((2,), jnp.uint32),
        ((), jnp.int32), ((), jnp.int32))


@pytest.fixture(scope="module")
def share_stage_compiled(one_chip):
    """The XLA step's additive share stage, 3 shares, on one scan block:
    a block's draws folded (``_share_draws``) and the share rows made from
    the folds (``_share_combine``)."""
    field = FieldOps.create(MODULUS)
    scheme = AdditiveSharing(3, MODULUS)

    def stage(masked, key):
        return simpod._share_combine(scheme, field, None, field.sum(masked, axis=0),
                                     simpod._share_draws(scheme, field, ROWS, DIM, key))

    return _compile_for(one_chip, stage, ((ROWS, DIM), jnp.uint32), ((2,), jnp.uint32))


def _lane_padded(text: str, least: int = 1 << 20):
    """Arrays of ``least`` elements or more whose minor dimension is under
    the 128 lanes of their ``T(8,128)`` tiles: ``u32[8,125000,8]{2,1,0:...}``
    holds 8 words in every 128 it occupies."""
    found = set()
    for dims, order in re.findall(r"\w+\[([\d,]+)\]\{([\d,]+):T\(8,128\)", text):
        dims = [int(n) for n in dims.split(",")]
        minor = dims[int(order.split(",")[0])]
        if math.prod(dims) >= least and minor < 128:
            found.add((tuple(dims), order))
    return found


def test_mask_stage_changes_layout_with_no_gather_no_row_loop_and_no_padded_plane(
        mask_stage_compiled):
    """Until PR 30 this block compiled to two gathers over the keystream, a
    copy into ``u32[8,125000,16]`` (16 words in 128 lanes), a flatten and a
    row loop: two thirds of the round. The draws now stay word-major until
    they are residues, and those change layout once, through the matrix
    unit (``chacha_jax.element_order``). The one loop is the masks' sum's
    scan over blocks of 8 rows (one block here): none inside a block."""
    text = mask_stage_compiled.as_text()
    assert " gather(" not in text
    loops = [line for line in text.splitlines() if " while(" in line]
    assert len(loops) == 1 and 'op_name="jit(stage)/sda.mask/while"' in loops[0], loops
    assert not _lane_padded(text)
    assert "sda.mask.relayout" in text and "dot_general" in text


def test_mask_stage_orders_one_row_a_block(mask_stage_compiled):
    """Since PR 40 the block's masks fold over their rows word-major and
    the fold alone goes through the matrix unit: a matmul a byte on ONE
    row (8 x 977 tiles x 128 words), where eight rows went. No array of
    the layout change's tiles holds more than one row's words, and the
    only [8, d] array is the input."""
    text = mask_stage_compiled.as_text()
    row = 8 * 977 * 128
    tiled = {dims for dims in _written_shapes(text) if 977 in dims}
    assert tiled and all(math.prod(dims) <= row for dims in tiled), tiled
    wide = {dims for dims in _written_shapes(text)
            if DIM in dims and math.prod(dims) >= ROWS * DIM}
    assert wide == {(ROWS, DIM)}, wide


def test_mask_stage_reduces_its_draws_with_no_remainder_and_no_64_bit_array(
        mask_stage_compiled):
    """Until PR 32 the draws were glued to uint64 and reduced by ``jnp.mod``:
    one op, an emulated multi-word division, 55 % of the round. The glue
    (``chacha_jax._paired_u64``) and the split (``FieldOps.from_u64``)
    cancel in the compiler's 64-bit rewriting: what is left is uint32
    arithmetic on the two half planes (``fastfield.reduce64``)."""
    text = mask_stage_compiled.as_text()
    assert "sda.mask.reduce" in text
    assert "remainder" not in text and "/rem" not in text
    assert not re.search(r"\b[us]64\[", text)


def test_mask_stage_block_holds_under_a_hundred_megabytes_of_temporaries(
        mask_stage_compiled):
    # 578 MB before PR 30, 515 MB with transpose + reshape; the planes of
    # one block are 32 MB each
    assert mask_stage_compiled.memory_analysis().temp_size_in_bytes < 100e6


# -- the additive share stage: one threefry block a draw, made, reduced and
# folded in one fusion (PR 34). The parent's block drew (8, 2, 1000000, 2)
# uint32 words -- two cipher blocks an element, half of each thrown away --
# into a 128 MB array with two consumers.

def test_share_stage_block_writes_no_draw_to_memory(share_stage_compiled):
    # 64,512 B; the parent held 128,072,704 B of bits
    assert share_stage_compiled.memory_analysis().temp_size_in_bytes < 1e6


def _written_arrays(text: str):
    """(dtype, shape) of the arrays a compiled program holds in memory:
    results and parameters of every computation but the bodies of fusions,
    whose values live in registers."""
    arrays = set()
    for header, body in re.findall(r"^(\S[^\n]*)\{\n(.*?)^\}", text, re.M | re.S):
        if header.startswith("%fused_computation"):
            continue
        for dtype, dims in re.findall(r"\b([a-z]+\d+)\[([\d,]+)\]", header + body):
            arrays.add((dtype, tuple(int(n) for n in dims.split(","))))
    return arrays


def _written_shapes(text: str):
    return {dims for _, dims in _written_arrays(text)}


def test_share_stage_holds_no_array_of_the_draws_width_but_its_rows(
        share_stage_compiled):
    """The only arrays of 2^20 elements or more along the 1,000,000 axis are
    the input, the folded free rows, the share rows and the [d] vectors:
    nothing with a participant axis AND a share axis, nothing per word."""
    allowed = {(ROWS, DIM), (2, DIM), (3, DIM), (1, DIM), (DIM,)}
    wide = {dims for dims in _written_shapes(share_stage_compiled.as_text())
            if DIM in dims and math.prod(dims) >= (1 << 20)}
    assert (ROWS, DIM) in wide and (3, DIM) in wide
    assert wide <= allowed, wide - allowed


def test_share_stage_makes_no_64_bit_array(share_stage_compiled):
    # the uint64 draw's combine and uniform32's split cancel in the
    # compiler's 64-bit rewriting, as from_u64's did (PR 32)
    text = share_stage_compiled.as_text()
    assert "sda.share" in text
    assert not re.search(r"\b[us]64\[", text)


def test_share_stage_runs_one_cipher_block_a_draw_once(share_stage_compiled):
    """Cost analysis of the block: 2.778e9 flops. Two blocks a draw (the
    (..., 2) uint32 words) read 5.607e9; one block a draw with the draws'
    total taken by a second reduce, which recomputes the cipher in both
    consumers, 5.523e9."""
    assert 2.0e9 < share_stage_compiled.cost_analysis()["flops"] < 3.2e9


# -- packed Shamir under ChaCha masks on the kernel path (PR 35): the masks'
# sum is made 8 rows at a time in front of the kernel. Until then the whole
# [S, d] block had its masks expanded at once: 22 MB of temporaries a
# row, 6.16 GB at 300 rows, and 1200 rows were refused by the compiler.

PADDED_DIM = 1_000_008  # 999,999 at the grain lcm(3, 8)


@pytest.fixture(scope="module")
def packed_chacha_rounds_compiled(one_chip):
    """``SimulatedPod(packed 3/8/4, ChaChaMasking, use_pallas=True)``'s
    round at 64 and at 128 rows of the benchmark's padded width."""
    from jax.sharding import Mesh

    mesh = Mesh([[one_chip._device]], ("p", "d"))
    pod = simpod.SimulatedPod(_packed_scheme(), ChaChaMasking(MODULUS, 999_999, 128),
                              mesh=mesh, use_pallas=True)
    return {rows: _compile_for(one_chip, pod.aggregate_fn(rows, PADDED_DIM),
                               ((rows, PADDED_DIM), jnp.uint32), ((2,), jnp.uint32))
            for rows in (64, 128)}


def test_packed_chacha_round_holds_one_block_of_masks_whatever_the_rows(
        packed_chacha_rounds_compiled):
    """Twice the rows, the same temporaries to within one block's (they are
    equal: 727,917,056 B at 300, 600 and 1200 rows, PERF.md), the kernel in
    the program, and no array of the rows' extent but the input: no
    ``[S, ...]`` draws, masks or masked inputs are written."""
    small, large = (packed_chacha_rounds_compiled[rows] for rows in (64, 128))
    temporaries = [c.memory_analysis().temp_size_in_bytes for c in (small, large)]
    assert abs(temporaries[1] - temporaries[0]) < 100e6, temporaries
    assert max(temporaries) < 1e9, temporaries
    for rows, compiled in packed_chacha_rounds_compiled.items():
        text = compiled.as_text()
        assert "tpu_custom_call" in text and "sda.mask.fold" in text
        wide = {dims for dims in _written_shapes(text)
                if math.prod(dims) >= rows * PADDED_DIM}
        assert wide == {(rows, PADDED_DIM)}, wide


def test_packed_chacha_round_changes_layout_once_after_the_scan(
        packed_chacha_rounds_compiled):
    """The running sum of the masks is word-major; ``element_order`` takes
    it once a round. Every op the compiler left under ``sda.mask.relayout``
    stands outside the scan's body, the matmuls among them, and no
    computation of the loop holds a matmul."""
    for compiled in packed_chacha_rounds_compiled.values():
        text = compiled.as_text()
        relayout = re.findall(r'op_name="([^"]*sda\.mask\.relayout[^"]*)"', text)
        assert any("dot_general" in name for name in relayout)
        assert not [name for name in relayout if "/while/" in name]
        in_loop = [name for name in re.findall(r'op_name="([^"]*)"', text)
                   if "/while/" in name and "dot_general" in name]
        assert not in_loop, in_loop[:3]


COHORT = (1200, 999_999)  # the cell ``fedavg-f32-1m``: float32 client weights


def _fedavg_round_compiled(one_chip, reported: bool):
    from jax.sharding import Mesh

    from sda_tpu.models import FixedPointCodec, federated
    from sda_tpu.protocol import FullMasking

    mesh = Mesh([[one_chip._device]], ("p", "d"))
    pod = simpod.SimulatedPod(_packed_scheme(), FullMasking(MODULUS),
                              mesh=mesh, use_pallas=True)
    codec = FixedPointCodec(MODULUS, 16, max_summands=COHORT[0], clip=2.0)
    program = federated._resident_program(pod, codec, *COHORT,
                                          reported=reported)
    who = [(COHORT[:1], jnp.bool_)] if reported else []
    return _compile_for(one_chip, program, (COHORT[1:], jnp.float32),
                        (COHORT, jnp.float32), ((2,), jnp.uint32), *who)


@pytest.fixture(scope="module")
def fedavg_round_compiled(one_chip):
    """``pod_fedavg_round``'s resident program (models/federated.py) at the
    cell's size: packed 3/8/4, full masks, the fused kernel, the codec of
    ``pod-fedavg-packed8``."""
    return _fedavg_round_compiled(one_chip, reported=False)


@pytest.fixture(scope="module")
def sporadic_round_compiled(one_chip):
    """The same with the ``reported`` operand, ``bool[1200]``: the cell
    ``fedavg-sporadic-1m``'s one program (PR 44)."""
    return _fedavg_round_compiled(one_chip, reported=True)


def test_fedavg_round_writes_no_residue_of_the_cohorts_shape(
        fedavg_round_compiled):
    """The encode is traced into the program that folds it: the compiler
    fuses delta, quantization and residue pass into the fold of the rows
    (root under ``sda.fold``), so the only array of the cohort's extent is
    the float32 input -- no int32 or uint32 ``[1200, 999999]`` stands in
    HBM beside it (4.8 GB more, written and read back) -- and the round's
    temporaries are the packed round's own (720,399,360 B, PR 42)."""
    text = fedavg_round_compiled.as_text()
    assert "tpu_custom_call" in text and "sda.decode" in text
    wide = {(dtype, dims) for dtype, dims in _written_arrays(text)
            if math.prod(dims) >= math.prod(COHORT)}
    assert wide == {("f32", COHORT)}, wide
    memory = fedavg_round_compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 1e9, memory.temp_size_in_bytes
    # the fold reads the weights themselves: one pass over the 4.8 GB
    (fold,) = re.findall(r"fusion\(%client_vecs[.\d]*, %global_vec[.\d]*\)[^\n]*", text)
    assert "sda.fold" in fold


def test_sporadic_round_selects_inside_the_folds_one_read_of_the_cohort(
        sporadic_round_compiled, fedavg_round_compiled):
    """Told who reported, the round still reads the float32 cohort in ONE
    fusion, root under ``sda.fold``, and that fusion takes the ``reported``
    operand: the select fuses into the read as the encode does. No array
    of the cohort's extent but the input -- no ``[1200, 999999]`` or
    ``[1200, 1000008]`` of selected residues -- and the packed round's
    temporaries. The count's sum is the one op the operand adds to the
    device's trace; the reciprocal of the count is some forty scalar ops
    (two thousand as an emulated 64-bit division, PR 44)."""
    text = sporadic_round_compiled.as_text()
    assert "tpu_custom_call" in text and "sda.decode" in text
    wide = {(dtype, dims) for dtype, dims in _written_arrays(text)
            if math.prod(dims) >= math.prod(COHORT)}
    assert wide == {("f32", COHORT)}, wide
    assert sporadic_round_compiled.memory_analysis().temp_size_in_bytes < 100e6
    readers = re.findall(r"[^\n]*\(%client_vecs[.\d]*[,)][^\n]*", text)
    (fold,) = [line for line in readers if " fusion(" in line]
    assert "sda.fold" in fold and "%who" in fold, fold
    assert not re.search(r"\b[us]64\[\]", text)   # no emulated 64-bit scalar
    ops, plain = (_entry_ops(c.as_text()) for c in (
        sporadic_round_compiled, fedavg_round_compiled))
    assert len(ops) - len(plain) < 100, (len(ops), len(plain))
    traced = lambda found: sorted(        # noqa: E731  (what a trace shows)
        name for opcode, name in found if opcode in ("fusion", "custom-call"))
    added = set(traced(ops)) - set(traced(plain))
    assert added == {"jit(program)/sda.unmask/reduce_sum"}, added


# -- the packed round's layout changes (PR 43): ``batch_columns`` in front of
# the kernel, the kernel's mask total back to ``[d]`` and ``unbatch_columns``
# behind the Lagrange product go through the matrix unit (fields/layout.py).
# As ``moveaxis`` + ``reshape`` the compiler moved the last one up through the
# product's adds into its eight terms, each laid out on its own as
# ``u32[333333,3]{1,0:T(8,128)}`` -- 4 MB held in 170 MB, a third of the round.

@pytest.fixture(scope="module")
def packed_round_compiled(one_chip):
    """The cell ``packed-1m``'s round: 300 x 999,999 residues, packed 3/8/4
    under full masks, the fused kernel."""
    from jax.sharding import Mesh

    from sda_tpu.protocol import FullMasking

    mesh = Mesh([[one_chip._device]], ("p", "d"))
    pod = simpod.SimulatedPod(_packed_scheme(), FullMasking(MODULUS),
                              mesh=mesh, use_pallas=True)
    return _compile_for(one_chip, pod.aggregate_fn(300, 999_999),
                        ((300, 999_999), jnp.uint32), ((2,), jnp.uint32))


@pytest.fixture(params=["packed_round_compiled", "fedavg_round_compiled"],
                ids=["packed-1m", "fedavg-f32-1m"])
def packed_full_round(request):
    return request.getfixturevalue(request.param)


def _entry_ops(text: str):
    """(opcode, op_name) of the instructions of the program's entry
    computation: the ops the device runs one after another."""
    entry = text[text.index("\nENTRY "):]
    return [(opcode, name) for opcode, name in re.findall(
        r"^\s+(?:ROOT )?%\S+ = .*? ([a-z][\w-]*)\(.*?op_name=\"([^\"]*)\"",
        entry, re.M)]


def test_packed_round_holds_no_lane_padded_array(packed_full_round):
    text = packed_full_round.as_text()
    assert "[333333,3]" not in text
    assert not _lane_padded(text, least=1 << 16)


def test_packed_round_changes_layout_through_the_matrix_unit(packed_full_round):
    """Four matmuls a layout change, one a byte of the uint32 residues:
    eight under ``sda.relayout`` (``batch_columns`` and the mask total's way
    back), four under ``sda.reconstruct.unbatch``, which until PR 43 no op
    carried; and no ``copy`` or ``transpose`` under either stage."""
    ops = _entry_ops(packed_full_round.as_text())
    matmuls = [name for _, name in ops if name.endswith("/dot_general")]
    assert len([n for n in matmuls if "/sda.relayout/" in n]) == 8, matmuls
    assert len([n for n in matmuls if "/sda.reconstruct.unbatch/" in n]) == 4, matmuls
    moved = [(opcode, name) for opcode, name in ops
             if opcode in ("copy", "transpose")
             and ("sda.reconstruct" in name or "sda.relayout" in name)]
    assert not moved, moved


def test_packed_round_keeps_its_temporaries_out_of_hbm(packed_full_round):
    # 721,044,480 B at the parent (720,399,360 in the FedAvg round), the
    # terms' padded arrays; 935,424 B now
    assert packed_full_round.memory_analysis().temp_size_in_bytes < 100e6


# -- the same, in the rounds as they are lowered (no compiler, any backend): the
# relayout's matmuls take ONE row -- [8 pairs, tiles, 128 lanes] in bfloat16,
# no leading axis of a block's rows -- once a round, after the masks' scan, on
# both steps (the XLA step's once took one row a scan block).

def _relayout_matmuls(lowered):
    """(plane operand's dims, inside a scan's body?) of every
    ``dot_general`` under ``sda.mask.relayout`` in a lowered program."""
    found = []
    for op, path in lowered_ops(lowered):
        if op.operation.name == "stablehlo.dot_general" and "sda.mask.relayout" in path:
            dims = re.match(r"tensor<([\dx]+)xbf16>", str(op.operands[0].type)).group(1)
            found.append((tuple(int(n) for n in dims.split("x")), "while" in path))
    return found


@pytest.mark.parametrize("path", ["xla-step", "kernel-path"])
def test_a_chacha_round_puts_one_row_through_the_matrix_unit(path):
    dim, rows = 1024 * 3, 24            # three lane tiles a pair; three scan blocks
    masking = ChaChaMasking(MODULUS, dim, 128)
    if path == "xla-step":
        pod = simpod.SimulatedPod(AdditiveSharing(3, MODULUS), masking,
                                  mesh=simpod.make_mesh(1, 1))
    else:
        pod = simpod.SimulatedPod(
            _packed_scheme(), masking, mesh=simpod.make_mesh(1, 1), use_pallas=True,
            pallas_interpret=True, pallas_external_bits_fn=external_bits)
    lowered = pod.aggregate_fn(rows, dim).lower(
        jax.ShapeDtypeStruct((rows, dim), jnp.uint32),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    matmuls = _relayout_matmuls(lowered)
    # a matmul a byte of the uint32 residues, each on one row's planes,
    # once a round after the masks' scan on both steps
    assert [dims for dims, _ in matmuls] == [(8, dim // 1024, 128)] * 4, matmuls
    assert {in_while for _, in_while in matmuls} == {False}


# -- the fused kernel's grid and draws (PR 37): with the on-core PRNG nothing
# streams along the participants, so the grid is the dim tiles alone and the
# participants fold in blocks of 16 whatever their number's divisors; only
# external bits keep a participant axis. Interpret mode has no on-core PRNG:
# the internal-bits kernel is reached by Mosaic alone. The participant count
# is what matters, so two narrow dim tiles keep a compile to about a second.

KERNEL_TILE, KERNEL_COLUMNS = 128, 256


def _mosaic_modules(text: str):
    """The Mosaic modules of a compiled program's kernels, as text."""
    import base64

    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    context = mlir.make_ir_context()
    context.allow_unregistered_dialects = True  # the serialised dialect's name
    with context:
        return [ir.Module.parse(base64.b64decode(body)).operation.get_asm()
                for body in re.findall(r'"body":"([A-Za-z0-9+/=]+)"', text)]


def _kernel_compiled(one_chip, participants: int, masked: bool, external: bool):
    from sda_tpu.fields import fastfield, numtheory
    from sda_tpu.fields.pallas_round import fused_mask_share_combine

    scheme = _packed_scheme()
    k, t, p = scheme.secret_count, scheme.privacy_threshold, scheme.prime_modulus

    def kernel(x_sum, seed, *bits):
        return fused_mask_share_combine(
            x_sum, participants, seed, fastfield.SolinasPrime.try_from(p),
            numtheory.share_matrix_for(scheme), t, masked, tile=KERNEL_TILE,
            external_bits=bits[0] if bits else None)

    args = [((k, KERNEL_COLUMNS), jnp.uint32), ((), jnp.int32)]
    if external:
        rows = 2 * ((k + t) if masked else t)
        args.append(((participants, rows, KERNEL_COLUMNS), jnp.uint32))
    [module] = _mosaic_modules(_compile_for(one_chip, kernel, *args).as_text())
    [grid] = re.findall(r"iteration_bounds = array<i64: ([\d, ]+)>", module)
    draws = re.findall(r"tpu\.prng_random_bits.*?vector<(\d+)x(\d+)xi32>", module)
    return ([int(n) for n in grid.split(",")],
            {(int(rows), int(lanes)) for rows, lanes in draws})


@pytest.mark.parametrize("participants,masked,draw_rows", [
    # packed-1m's rows a chip: 18 blocks of 16 (2 words x 16 x 3 mask rows
    # = 96, x 4 share rows = 128) and one tail of 12 (72 and 96)
    (300, True, {72, 96, 128}),
    # packed-chacha-1m's: 75 blocks of 16 share rows, no tail
    (1200, False, {128}),
    (7, True, {42, 56}),     # fewer than a block: one draw of their size
])
def test_internal_bits_kernel_has_a_one_axis_grid_and_draws_for_blocks_of_16(
        one_chip, participants, masked, draw_rows):
    grid, draws = _kernel_compiled(one_chip, participants, masked, external=False)
    assert grid == [KERNEL_COLUMNS // KERNEL_TILE]
    assert draws == {(rows, KERNEL_TILE) for rows in draw_rows}


def test_external_bits_kernel_keeps_its_participant_axis(one_chip):
    """1200 participants' bits do not fit one VMEM block: they stream in
    tiles of 400 (the largest divisor under the budget) along grid axis 1."""
    grid, draws = _kernel_compiled(one_chip, 1200, True, external=True)
    assert grid == [KERNEL_COLUMNS // KERNEL_TILE, 3]
    assert not draws


# -- the accepted cells' rounds as they are lowered for the chip (PR 44): a
# change that means to leave a cell's program alone shows it here. The
# method of PRs 31-43, kept as a test: the lowered text, every Mosaic module
# deserialized and printed without its debug locations (they hold the
# caller's line numbers), sha256. A pin is the value at the parent commit
# of the PR that last changed that cell's program ON PURPOSE: such a PR
# replaces the pin of the cells it changes (the failure prints the value)
# and says so; an untouched pin is the proof that the others lower to the
# parent's text.

LOWERED_SHA256 = {
    "packed-1m": "ed573eb07a503a1ff82193b857b876960605f656dabf6ad9dd0dd6c09740254d",
    "packed-1m-hostfed": "5fa51410850772063bf34f48ee6a9bf57248a5bd835ea4bfab3cc0ab82478de1",
    "packed-1m-mesh4": "e322816bc5795a67b75c3e476e17b1e39af921343bc2534ec383abdba15e91e4",
    "additive-chacha-1m": "efb68d56a0095fd60f49d3bd7677d91e9bdd1ab2224b6441fae72e91f40ed098",
    "packed-1m-streamed": "3cc029c33dabc5f695b23528a9a2b1e515ff9ac7d238f2015d8110faab595bda",
    "packed-chacha-1m": "e046b5d8b816cb7ccc775ee9b95c94e9a01f8879631263d45661c7dbc5333b1d",
    "fedavg-f32-1m": "dbf5d9b29710a4446e3133f7902de96be8e36b67067b830c7b70b1792c83487d",
    "fedavg-sporadic-1m": "347d0be88de0b0b5b99f2f31c4a093b8ab20a9e5f6f4f16b6f03d539239ce36d",
}


def _without_mosaic_locations(text: str) -> str:
    """``text`` with each serialized Mosaic module replaced by the hash of
    its assembly printed without locations."""
    import base64
    import hashlib
    import json

    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir
    from jaxlib.mlir.passmanager import PassManager

    def unescape(literal):  # an MLIR string literal's escapes
        simple = {"n": "\n", "t": "\t", "\\": "\\", '"': '"'}
        return re.sub(
            r'\\([0-9A-Fa-f]{2}|[nt"\\])',
            lambda m: simple.get(m.group(1)) or chr(int(m.group(1), 16)),
            literal)

    def replace(match):
        config = json.loads(unescape(match.group(1)))
        if "custom_call_config" not in config:
            return match.group(0)
        with mlir.make_ir_context() as context:
            tpu.register_dialect(context)
            context.allow_unregistered_dialects = True
            module = ir.Module.parse(
                base64.b64decode(config["custom_call_config"]["body"]))
            PassManager.parse(
                "builtin.module(mosaic-serde{serialize=false})").run(
                    module.operation)
            assembly = module.operation.get_asm(enable_debug_info=False)
        config["custom_call_config"]["body"] = hashlib.sha256(
            assembly.encode()).hexdigest()
        return "backend_config = " + json.dumps(config, sort_keys=True)

    return re.sub(r'backend_config = "((?:[^"\\]|\\.)*)"', replace, text)


# -- the ChaCha cells' masks from ONE kernel: where the round is built for a TPU
# over a uint32 field, ``fields/chacha_kernel.py`` holds the cipher's sixteen
# words on-core and folds a block's reduced draws in VMEM. The XLA block
# function runs as some thirty fusions of ``u32[8,1,N]`` word planes a scan
# block there, and the compiler stacks and copies the words besides.

CHACHA_CELLS = {"additive-chacha-1m": 125_000, "packed-chacha-1m": 125_001}


@pytest.fixture(scope="module")
def chacha_cells_compiled(v5e_host):
    return {name: _compiled(lowered) for name in CHACHA_CELLS
            for [lowered] in [_accepted_cell_lowered(name, v5e_host)]}


def _ops_of(text: str):
    """(name, dims, op_name) of every instruction of a compiled program."""
    ops = []
    for line in text.splitlines():
        head = re.match(r"\s+(?:ROOT )?%([\w.-]+) = \w+\[([\d,]*)\]", line)
        if head:
            op_name = re.search(r'op_name="([^"]*)"', line)
            ops.append((head.group(1), tuple(int(n) for n in head.group(2).split(",") if n),
                        op_name.group(1) if op_name else ""))
    return ops


def _equations(jaxpr) -> int:
    """Equations of a traced program, those of its nested jaxprs (a
    scan's body, a kernel's) included: what every warm start traces."""
    from jax._src import core

    return sum(1 + sum(_equations(sub) for sub in core.jaxprs_in_params(eqn.params))
               for eqn in jaxpr.eqns)


@pytest.mark.parametrize("name", sorted(CHACHA_CELLS))
def test_a_chacha_cell_expands_its_masks_in_one_kernel_call(chacha_cells_compiled, name):
    """ONE ``sda_chacha_mask_fold`` Mosaic call under ``sda.mask.chacha``
    (once a round on both steps, where the XLA step's was once a scan
    block):
    no word plane of the cipher, ``u32[8,1,N]``, and nothing under the
    scope of a block's width but the kernel's output and its re-tile."""
    from sda_tpu.fields import chacha_kernel

    blocks = CHACHA_CELLS[name]
    ops = _ops_of(chacha_cells_compiled[name].as_text())
    kernels = [(n, op_name) for n, _, op_name in ops if n.startswith("sda_chacha_mask_fold")]
    assert len(kernels) == 1 and "/sda.mask.chacha/" in kernels[0][1], kernels
    assert not [n for n, dims, _ in ops if dims == (8, 1, blocks)]
    wide = {dims for _, dims, op_name in ops
            if "/sda.mask.chacha/" in op_name and math.prod(dims) >= blocks}
    rows = -(-blocks // chacha_kernel._VECTOR) * chacha_kernel._SUB  # 41 vectors
    assert wide == {(8, rows, 128), (8, rows * 128)}, wide


def test_the_xla_steps_scan_carries_the_draws_alone(chacha_cells_compiled):
    """``additive-chacha-1m``'s round: the cohort folds once in front of
    the participant scan and the masks' sum is made once a round, as on
    the kernel path. No copy of the 2.4 GB cohort into scan blocks
    (``u32[75,8,1000000]`` in the block-by-block scan, 7.06 ms a round on
    a v5e, and 2.4 GB of temporaries); the one kernel call and every op of the layout change
    stand outside the ``while``, the matmuls on ONE row's planes; what the
    loop runs is the share stage's draws and its counter."""
    compiled = chacha_cells_compiled["additive-chacha-1m"]
    ops = _ops_of(compiled.as_text())
    assert not [n for n, dims, _ in ops if dims == (75, 8, 1_000_000)]
    [kernel] = [op_name for n, _, op_name in ops if n.startswith("sda_chacha_mask_fold")]
    assert "/while/" not in kernel, kernel
    relayout = [(n, dims, op_name) for n, dims, op_name in ops
                if "/sda.mask.relayout/" in op_name]
    assert relayout and not [op for op in relayout if "/while/" in op[2]], relayout[:3]
    planes = {dims for n, dims, op_name in relayout if "dot_general" in op_name}
    assert planes and all(math.prod(dims) <= 8 * 977 * 1024 for dims in planes), planes
    in_loop = {op_name.split("/while/body/")[1].split("/")[1]
               for _, _, op_name in ops if "/while/body/closed_call/" in op_name}
    assert in_loop == {"sda.share"}, in_loop
    assert compiled.memory_analysis().temp_size_in_bytes < 100e6


#: traced equations of ``additive-chacha-1m``'s round at its shape, built
#: for a v5e (the on-core cipher), nested jaxprs included. The block-by-
#: block scan traced 540: every warm start traces and lowers the round
#: again, so a change of the XLA step may not make it larger.
ADDITIVE_CHACHA_EQUATIONS = 531


def test_the_xla_steps_trace_is_no_larger_than_before(one_chip):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh([[one_chip._device]], ("p", "d"))
    pod = simpod.SimulatedPod(AdditiveSharing(3, MODULUS),
                              ChaChaMasking(MODULUS, 999_999, 128), mesh=mesh)
    assert pod._cipher == "kernel" and not pod.pallas_active
    rows, dim = pod.padded_shape(600, 999_999)
    traced = jax.make_jaxpr(pod.aggregate_fn(rows, dim))(
        jax.ShapeDtypeStruct((rows, dim), jnp.uint32,
                             sharding=NamedSharding(mesh, PartitionSpec("p", "d"))),
        jax.ShapeDtypeStruct((2,), jnp.uint32,
                             sharding=NamedSharding(mesh, PartitionSpec())))
    assert _equations(traced.jaxpr) == ADDITIVE_CHACHA_EQUATIONS <= 540


def test_the_cipher_kernel_lowers_to_a_loop_whatever_its_rows(one_chip):
    """The body traces to a few hundred equations and lowers to one Mosaic
    module with two loops (the rows, the ten double rounds; the eight
    draws' reduction is one traced body, lowered eight times), the same at
    8 rows and at 1200: a body that unrolls the
    twenty rounds or the draws in Python is many times that, traced and
    lowered on every warm start, and fails here and not in ``setup_s``."""
    from sda_tpu.fields import chacha_kernel

    field = FieldOps.create(MODULUS)
    sizes = set()
    for rows, blocks in ((ROWS, 125_000), (1200, 125_001)):
        def kernel(seeds, block0):
            return chacha_kernel.mask_fold(seeds, block0, nblocks=blocks, sp=field.sp)

        args = (((rows, 8), jnp.uint32), ((), jnp.int32))
        traced = _equations(jax.make_jaxpr(kernel)(
            *(jnp.zeros(shape, dtype) for shape, dtype in args)).jaxpr)
        [module] = _mosaic_modules(_compile_for(one_chip, kernel, *args).as_text())
        sizes.add((traced, len(module.splitlines()), module.count("scf.for")))
    [(traced, lines, loops)] = sizes
    assert traced < 500 and lines < 2500 and loops == 2, sizes


def _accepted_cell_lowered(name, devices):
    """The lowered round(s) of the accepted cell ``name`` of BENCHMARK.json
    on the described ``devices`` it asks for."""
    import sys
    from pathlib import Path

    home = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
    sys.path.insert(0, str(home))
    try:
        import harness

        cell = harness.load_cell(harness.ROOT, name)
        return _cell_lowered(cell, devices[:cell.chips])
    finally:
        sys.path.remove(str(home))


def _cell_lowered(cell, devices):
    """The lowered round(s) of one cell of BENCHMARK.json on described
    ``devices``, built by the cell's own driver from its own files."""
    from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

    import harness

    config, traffic = cell.config, cell.traffic
    participants, dim = traffic["participants"], traffic["dim"]
    driver = harness.load_module(cell.home, "drivers", config["driver"])
    if config["driver"] == "stream":
        agg = driver.build_aggregator(config)
        one = SingleDeviceSharding(devices[0])

        def of(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

        rows, scheme, dtype = config["participants_chunk"], agg.scheme, agg._field.dtype
        accs = (of((scheme.output_size, dim // scheme.input_size), dtype),
                of((dim,), dtype))
        return [agg._step_fn((rows, dim)).lower(
                    of((rows, dim), jnp.int64), of((2,), jnp.uint32),
                    of((2,), jnp.uint32), of((), jnp.int32), of((), jnp.int32),
                    *accs),
                agg._final_fn(dim).lower(*accs)]
    codec = None
    if config["driver"] in ("pod_fedavg", "pod_fedavg_sporadic"):
        pod, codec = harness.load_module(cell.home, "drivers", "pod_fedavg").build_pod(
            config, devices)
    elif config["driver"] == "pod":
        pod = driver.build_pod(config, devices)
    else:
        pod = driver.build_pod(config, dim, devices)

    def on(spec, shape, dtype):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(pod.mesh, PartitionSpec(*spec)))

    key = on((), (2,), jnp.uint32)
    if codec is not None:
        from sda_tpu.models import federated

        reported = config["driver"] == "pod_fedavg_sporadic"
        program = federated._resident_program(pod, codec, participants, dim,
                                              reported=reported)
        who = [on(("p",), (participants,), jnp.bool_)] if reported else []
        return [program.lower(on(("d",), (dim,), jnp.float32),
                              on(("p", "d"), (participants, dim), jnp.float32), key,
                              *who)]
    padded = pod.padded_shape(participants, dim)
    dtype = jnp.int64 if traffic["input"] == "host" else jnp.uint32
    return [pod.aggregate_fn(*padded).lower(on(("p", "d"), padded, dtype), key)]


@pytest.mark.parametrize("name", sorted(LOWERED_SHA256))
def test_an_accepted_cells_round_lowers_to_the_text_it_had(v5e_host, name):
    import hashlib

    texts = [_without_mosaic_locations(lowered.as_text())
             for lowered in _accepted_cell_lowered(name, v5e_host)]
    assert "body\\22" not in "".join(texts)   # every kernel's module was read
    digest = hashlib.sha256("\n".join(texts).encode()).hexdigest()
    assert digest == LOWERED_SHA256[name], (name, digest)
