"""Programs of the main path compiled at their real size for a TPU v5e
that is described, not attached: what the chip's compiler makes of them,
at no chip time. Nothing runs; a compile that passes is not a chip run.

One file, and the topology is described inside a fixture: only one process
may load the TPU's library, so the worker that is given this file loads it
and every other worker collects the same tests without touching it.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest

from sda_tpu.fields.ops import FieldOps
from sda_tpu.mesh import simpod
from sda_tpu.protocol import ChaChaMasking

MODULUS = 536870233  # 2^29 - 679: the uint32 field path


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or it logs under /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mask_stage_compiled(one_chip):
    """``_mask_stage``'s ChaCha branch on one scan block of the cell
    ``additive-chacha-1m``: 8 rows x 1,000,000, a traced block counter."""
    rows, dim = 8, 1_000_000
    field = FieldOps.create(MODULUS)

    def stage(x, key, round_key, pid_base, block0):
        return simpod._mask_stage(ChaChaMasking(MODULUS, dim, 128), field, x, key,
                                  round_key, pid_base=pid_base, d_block0=block0)[:2]

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep it out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return jax.jit(stage).lower(
            arg((rows, dim), jnp.uint32), arg((2,), jnp.uint32), arg((2,), jnp.uint32),
            arg((), jnp.int32), arg((), jnp.int32)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _lane_padded(text: str, least: int = 1 << 20):
    """Arrays of ``least`` elements or more whose minor dimension is under
    the 128 lanes of their ``T(8,128)`` tiles: ``u32[8,125000,8]{2,1,0:...}``
    holds 8 words in every 128 it occupies."""
    found = set()
    for dims, order in re.findall(r"\w+\[([\d,]+)\]\{([\d,]+):T\(8,128\)", text):
        dims = [int(n) for n in dims.split(",")]
        minor = dims[int(order.split(",")[0])]
        count = 1
        for n in dims:
            count *= n
        if count >= least and minor < 128:
            found.add((tuple(dims), order))
    return found


def test_mask_stage_changes_layout_with_no_gather_no_row_loop_and_no_padded_plane(
        mask_stage_compiled):
    """Until PR 30 this block compiled to two gathers over the keystream, a
    copy into ``u32[8,125000,16]`` (16 words in 128 lanes), a flatten and a
    row loop: two thirds of the round. The draws now stay word-major until
    they are residues, and those change layout once, through the matrix
    unit (``chacha_jax.element_order``)."""
    text = mask_stage_compiled.as_text()
    assert " gather(" not in text
    assert " while(" not in text
    assert not _lane_padded(text)
    assert "sda.mask.relayout" in text and "dot_general" in text


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
