"""The XLA step's participant scan (``simpod._scan_combine``) against the
block-by-block combine it replaced, kept here as the oracle: every scan
block folded its rows, expanded its ChaCha masks, ordered them and made
its shares, and the blocks' shares were summed. Now the rows fold once,
the ChaCha masks' sum is made once a round and the shares once after the
scan, which carries the draws alone. The draws keep their keys, so the
shares and the masks' sum are the oracle's bit for bit, except where the
on-core cipher expands the rows as they are and the oracle expanded the
zero rows of a ragged last block besides: there they differ by exactly
those masks and their shares."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sda_tpu.fields import numtheory
from sda_tpu.mesh import simpod
from sda_tpu.mesh.simpod import SimulatedPod, make_mesh
from sda_tpu.protocol import (AdditiveSharing, ChaChaMasking, FullMasking,
                              NoMasking, PackedShamirSharing)

from util import chacha_mask_rows

MODULUS = 536870233  # 2^29 - 679: the uint32 field path
DIM, CHUNK, SEED_BITS = 96, 8, 128


def _parent_scan_combine(scheme, f, M_host, masking, x, key, *, round_key,
                         pid_base, d_block0, chunk=CHUNK, reported=None,
                         cipher="xla"):
    """``_scan_combine`` as it was block by block, its stage scopes left out:
    the cohort padded to whole blocks and cut into them; in each block the
    rows that reported folded, their masks drawn (full: from the first
    half of the block key's split) or expanded (ChaCha: the block's rows)
    and folded onto them, and the shares made from that fold and the
    block's draws (full: the second half of the split; else the block
    key)."""
    P, d = x.shape
    chunk, padded_rows = simpod._scan_rows(P, chunk)
    pad = padded_rows - P
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad, d), x.dtype)], axis=0)
    nblk = x.shape[0] // chunk
    xb = x.reshape(nblk, chunk, d)
    rb = None
    if reported is not None:
        rb = jnp.pad(reported, (0, pad)).reshape(nblk, chunk)
    has_mask = not isinstance(masking, NoMasking)

    def body(carry, blk_i):
        acc_s, acc_m = carry
        blk, blk_reported, i = blk_i
        masked_sum = f.sum(simpod._reported_rows(blk, blk_reported), axis=0)
        skey, mask_sum = jax.random.fold_in(key, i), None
        if isinstance(masking, FullMasking):
            mkey, skey = jax.random.split(skey)
            mask_sum = f.sum(f.uniform(mkey, (chunk, d)), axis=0)
        elif isinstance(masking, ChaChaMasking):
            mask_sum = simpod._chacha_mask_sum(
                masking, f, round_key, pid_base + i * chunk, chunk, d, d_block0,
                cipher)
        if mask_sum is not None:
            masked_sum = f.add(masked_sum, mask_sum)
        shares = simpod._share_combine(
            scheme, f, M_host, masked_sum,
            simpod._share_draws(scheme, f, chunk, d, skey))
        acc_s = f.add(acc_s, shares)
        if mask_sum is not None:
            acc_m = f.add(acc_m, mask_sum)
        return (acc_s, acc_m), None

    init_s = jnp.zeros((scheme.output_size, d // scheme.input_size), f.dtype)
    init_m = jnp.zeros((d,), f.dtype)
    (acc_s, acc_m), _ = jax.lax.scan(
        body, (init_s, init_m), (xb, rb, jnp.arange(nblk, dtype=jnp.int32)))
    return acc_s, (acc_m if has_mask else None)


def _scheme(kind: str):
    if kind == "additive":
        return AdditiveSharing(3, MODULUS)
    t, p, w2, w3 = numtheory.generate_packed_params(3, 8, 28)
    assert p == MODULUS
    return PackedShamirSharing(3, 8, t, p, w2, w3)


def _masking(kind: str):
    if kind == "none":
        return NoMasking()
    if kind == "full":
        return FullMasking(MODULUS)
    return ChaChaMasking(MODULUS, DIM, SEED_BITS)


@pytest.mark.parametrize("rows", [16, 13], ids=["whole-blocks", "ragged"])
@pytest.mark.parametrize("told", [False, True], ids=["all", "reported"])
@pytest.mark.parametrize("masking", ["none", "full", "chacha-xla", "chacha-interpret"])
@pytest.mark.parametrize("kind", ["additive", "packed"])
def test_the_scan_carries_the_draws_alone_and_combines_as_the_blocks_did(
        monkeypatch, kind, masking, told, rows):
    cipher = masking.split("-")[1] if masking.startswith("chacha") else "xla"
    # steps built for the CPU take the cipher of the case, as a TPU's do
    monkeypatch.setitem(simpod._ON_CORE_CIPHER, "cpu", cipher)
    pod = SimulatedPod(_scheme(kind), _masking(masking.split("-")[0]),
                       mesh=make_mesh(1, 1))
    assert not pod.pallas_active and pod._cipher == cipher
    assert pod.scan_chunk == simpod._SCAN_CHUNK == CHUNK
    f, scheme, M_host = pod._field, pod.scheme, pod._plan.M_host
    rng = np.random.default_rng(rows * 10 + len(masking))
    inputs = rng.integers(0, 1 << 20, size=(rows, DIM), dtype=np.int64)
    reported = rng.random(rows) < 0.6 if told else None
    key, round_key = jax.random.PRNGKey(rows), jax.random.PRNGKey(rows + 1)
    args = (scheme, f, M_host, pod.masking, jnp.asarray(inputs, f.dtype), key)
    kwargs = dict(round_key=round_key, pid_base=0, d_block0=0, cipher=cipher,
                  reported=None if reported is None else jnp.asarray(reported))
    acc_s, acc_m = jax.jit(lambda: simpod._scan_combine(*args, **kwargs))()
    want_s, want_m = jax.jit(lambda: _parent_scan_combine(*args, **kwargs))()

    assert (acc_m is None) == (want_m is None) == (masking == "none")
    # the masks the oracle expanded and the change does not: those of the
    # zero rows of a ragged last block, where the on-core cipher expands
    # the rows as they are (the XLA block function pads to 8 rows as the
    # scan did)
    skipped = jnp.zeros((DIM,), f.dtype)
    if masking == "chacha-interpret" and rows % CHUNK:
        pad = -rows % CHUNK
        skipped = f.sum(chacha_mask_rows(f, round_key, rows, pad, DIM, 0, SEED_BITS),
                        axis=0)
    if acc_m is not None:
        np.testing.assert_array_equal(np.asarray(f.add(acc_m, skipped)),
                                      np.asarray(want_m))
        if rows % CHUNK == 0:
            np.testing.assert_array_equal(np.asarray(acc_m), np.asarray(want_m))
    # the shares: the oracle's bit for bit, less the shares of the masks it
    # expanded besides (none but in the ragged case above)
    zero_draws = jnp.zeros(simpod._drawn_shape(scheme, DIM), f.dtype)
    extra = simpod._share_combine(scheme, f, M_host, skipped, zero_draws)
    np.testing.assert_array_equal(np.asarray(f.add(acc_s, extra)), np.asarray(want_s))
    if not skipped.any():
        np.testing.assert_array_equal(np.asarray(acc_s), np.asarray(want_s))

    # the pod's round reveals the plain sum of the rows that reported
    out = np.asarray(pod.aggregate(inputs, jax.random.PRNGKey(7), reported=reported))
    counted = inputs if reported is None else inputs[reported]
    np.testing.assert_array_equal(out, counted.sum(axis=0) % MODULUS)


@pytest.mark.parametrize("masking", ["full", "chacha"])
def test_every_driver_on_the_xla_step_reveals_the_plain_sum_through_ragged_blocks(
        masking):
    """``StreamingAggregator``, ``StreamedPod`` and ``ModelScaleRound`` take
    the pod's XLA step: 13 rows a device and a step, a whole scan block and
    a ragged one, two dim tiles (the second at its own ChaCha window), on
    a (2, 1) mesh where there is one."""
    from sda_tpu.mesh import ModelScaleRound, StreamedPod, StreamingAggregator

    scheme, mask = _scheme("packed"), _masking(masking)
    rng = np.random.default_rng(len(masking))
    inputs = rng.integers(0, 1 << 20, size=(26, DIM), dtype=np.int64)
    drivers = [
        StreamingAggregator(scheme, mask, participants_chunk=13, dim_chunk=48),
        StreamedPod(scheme, mask, mesh=make_mesh(2, 1), participants_chunk=26,
                    dim_chunk=48),
        ModelScaleRound(scheme, mask, mesh=make_mesh(2, 1), dim_tile=48)]
    for driver in drivers:
        assert not driver.pallas_active
        out = np.asarray(driver.aggregate(inputs, jax.random.PRNGKey(3)))
        np.testing.assert_array_equal(out, inputs.sum(axis=0) % MODULUS)
