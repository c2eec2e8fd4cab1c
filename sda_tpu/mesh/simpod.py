"""Simulated-pod mode: the clerk committee on a TPU device mesh.

The TPU-native execution mode the reference cannot express: instead of
participants HTTP-POSTing encrypted shares to a broker that transposes them
into per-clerk jobs (server/src/snapshot.rs), the whole aggregation round
runs as ONE jitted SPMD program over a `jax.sharding.Mesh`, with XLA
collectives over ICI replacing every server round-trip.

Mesh axes and their protocol meaning (SURVEY.md §2.4 mapping):

- ``p`` — participant shards; the clerk committee also lives along this
  axis (clerk c's combined share lands on device c // (n/p_shards)).
- ``d`` — vector-dimension shards (the reference's analog of sequence/
  tensor parallelism: batching layer chunks, §5.7).

Dataflow per round, per (p, d) device:

1. mask + share the local [P/p, d/d'] participant block (threefry or
   device-ChaCha per participant, share matmul on the local dim chunk);
2. sum local participants' shares — participant parallelism is a *local*
   reduction;
3. ``psum_scatter`` over ``p`` splits the clerk axis while summing across
   participant shards — this one collective IS the snapshot transpose plus
   every clerk's combine, riding ICI instead of the broker;
4. ``all_gather`` over ``p`` hands the recipient all clerk rows; the
   reconstruct (Lagrange matmul for packed Shamir, share-sum for additive)
   and unmask run dim-sharded.

Scheme coverage matches the reference's full pluggability
(client/src/crypto/masking/mod.rs:33-94, sharing/mod.rs:35-96): sharing is
Packed-Shamir OR additive; masking is None, Full, or ChaCha (seed-
compressed masks expanded on device at each shard's dim offset,
fields/chacha_jax.py). Inputs are auto-padded to the mesh/scheme grain:
zero participants and zero components aggregate as zero and are stripped
from the output.

Trust model: this mode computes the same algebra with the same scheme
parameters but no transport encryption (devices of one pod trust each
other); the scheme enums already model pluggable encryption — the
federated HTTP mode keeps sealed boxes.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..fields import chacha_jax, chacha_kernel, fastfield, numtheory, sharing
from ..fields.ops import FieldOps
from .. import obs
from ..obs import devprof
from ..utils import metrics, timed_phase
from ..protocol import (
    AdditiveSharing,
    BasicShamirSharing,
    ChaChaMasking,
    FullMasking,
    LinearMaskingScheme,
    LinearSecretSharingScheme,
    NoMasking,
    PackedShamirSharing,
)

#: schemes whose share/reconstruct are host-built matrices applied as
#: device matmuls (numtheory.share_matrix_for / reconstruct_matrix_for)
SHAMIR_SCHEMES = (PackedShamirSharing, BasicShamirSharing)


def _shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with per-shard replication checking off."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _scheme_modulus(scheme: LinearSecretSharingScheme) -> int:
    if isinstance(scheme, SHAMIR_SCHEMES):
        return scheme.prime_modulus
    if isinstance(scheme, AdditiveSharing):
        return scheme.modulus
    raise ValueError(f"unsupported sharing scheme {type(scheme).__name__}")


def make_mesh(p_shards: int, d_shards: int, devices=None) -> Mesh:
    devices = np.asarray(devices if devices is not None else jax.devices())
    n = p_shards * d_shards
    if devices.size < n:
        raise ValueError(f"need {n} devices, have {devices.size}")
    return Mesh(devices.reshape(-1)[:n].reshape(p_shards, d_shards), ("p", "d"))


def default_mesh_shape(n_devices: int, share_count: int) -> Tuple[int, int]:
    """Largest p axis that divides both the device count and the committee."""
    p_shards = math.gcd(n_devices, share_count)
    return p_shards, n_devices // p_shards


def make_multislice_mesh(
    n_slices: int, p_per_slice: int, d_shards: int, devices=None
) -> Mesh:
    """A ('p', 'd') mesh whose participant axis spans multiple slices.

    Multi-slice layout rule (the DCN story, SURVEY §5.8): the ``d`` axis —
    whose collectives run every round-stage — must stay *inside* a slice on
    ICI, so ``d`` is the minor device axis within each slice's contiguous
    device block; the participant axis is slice-major, so only the
    all-reduce fold over ``p`` crosses the slice boundary, and XLA phases
    that reduction into an intra-slice (ICI) step plus one inter-slice
    (DCN) step of size ``n_slices``. Device order: devices[i] blocks of
    ``p_per_slice * d_shards`` per slice, exactly the contiguous-slice
    ordering ``jax.devices()`` returns on real multislice TPU deployments.
    The returned mesh has plain ('p', 'd') axes, so every pod/streaming
    code path works unchanged on it.
    """
    devices = np.asarray(devices if devices is not None else jax.devices())
    n = n_slices * p_per_slice * d_shards
    if devices.size < n:
        raise ValueError(f"need {n} devices, have {devices.size}")
    block = devices.reshape(-1)[:n].reshape(n_slices, p_per_slice, d_shards)
    return Mesh(block.reshape(n_slices * p_per_slice, d_shards), ("p", "d"))


# ---------------------------------------------------------------------------
# Round stages, shared by the SPMD pod body and the single-chip round.
# Every function takes canonical residues in the FieldOps working dtype.

#: fold_in tag separating the ChaCha-seed key stream from share randomness
_SEED_TAG = 0x5EED

#: fold_in tag separating per-device/tile driver keys from the seed stream:
#: without it, a tile index equal to _SEED_TAG would alias the tile's
#: share/mask randomness onto the ChaCha seed-word PRF stream
_TILE_TAG = 0x711E


def _tile_key(round_key, *indices):
    """Per-device/tile randomness key, domain-separated from _SEED_TAG."""
    k = jax.random.fold_in(round_key, _TILE_TAG)
    for ix in indices:
        k = jax.random.fold_in(k, ix)
    return k


def _reported_rows(x, reported):
    """[S, d_loc] residues with the rows that did not report read as zero
    (``reported`` [S] bool, traced; None: every row counts, and ``x`` is
    handed back as it is). Called inside ``sda.fold``, in front of the
    fold's one read of the rows: the compiler fuses the select into that
    read as it does the residue pass, and no [S, d_loc] array of selected
    residues exists. Masks and share rows are drawn for every row all the
    same, as for padding rows: they cancel whatever the rows hold."""
    if reported is None:
        return x
    return jnp.where(reported[:, None], x, jnp.zeros((), x.dtype))


def _chacha_seed_words(key, global_ids, seed_bitsize: int):
    """[S] global participant ids -> [S, 8] uint32 seed words.

    The seed depends only on (round key, participant id) — every dim shard
    of one participant derives the SAME seed and expands disjoint windows
    of one stream, which is the whole point of seed-compressed masks.
    Words beyond ceil(seed_bitsize/32) are zero, matching the host spec's
    zero-padded ChaCha key (fields/chacha.py).
    """
    seed_key = jax.random.fold_in(key, _SEED_TAG)
    words = (int(seed_bitsize) + 31) // 32
    if words > 8:
        raise ValueError("seed_bitsize > 256 unsupported")

    def one(i):
        w = jax.random.bits(jax.random.fold_in(seed_key, i), (8,), jnp.uint32)
        keep = (jnp.arange(8) < words)
        return jnp.where(keep, w, jnp.uint32(0))

    return jax.vmap(one)(global_ids)


#: participant rows a block of the XLA step's scan holds by default, and
#: the rows the kernel path's XLA cipher expands ChaCha masks for at a time
_SCAN_CHUNK = 8

#: the platforms whose steps expand ChaCha masks with the on-core cipher
#: (``fields/chacha_kernel.py``) over a uint32 field, each with the cipher
#: they take: the chip compiles the kernel with Mosaic. Every other step
#: runs the XLA block function (``_chacha_cipher``)
_ON_CORE_CIPHER = {"tpu": "kernel"}


def _chacha_cipher(f: FieldOps, devices) -> str:
    """The cipher a step over ``f`` built for ``devices`` expands its
    ChaCha masks with: ``"kernel"`` (the on-core cipher, compiled) where
    they are TPUs and ``f`` is a uint32 Solinas field, ``"xla"`` (the XLA
    block function) everywhere else; ``"interpret"`` is the kernel
    interpreted, which only a test asks for. Decided from what the step is
    built for, never by an option, and settled before it is traced: the
    other cipher is neither traced nor lowered."""
    platforms = {d.platform for d in np.ravel(devices)}
    if f.sp is None or len(platforms) != 1:
        return "xla"
    return _ON_CORE_CIPHER.get(platforms.pop(), "xla")


def _chacha_seeds(masking, round_key, pid_base, rows: int, d_loc: int):
    """[rows, 8] seed words of the participants ``pid_base .. + rows``."""
    if d_loc % 8:
        raise ValueError(
            "dimension must be a multiple of 8 (one ChaCha block)")
    return _chacha_seed_words(round_key, pid_base + jnp.arange(rows),
                              masking.seed_bitsize)


def _chacha_masks(masking, f: FieldOps, round_key, pid_base, rows: int,
                  d_loc: int, d_block0):
    """-> residues [rows, 8, d_loc/8] of the participants ``pid_base .. +
    rows``: each one's CHACHA_PRG_V1 stream from block ``d_block0`` on,
    reduced modulo the field's modulus, in the block function's word-major
    layout (``out[s, j, b]`` masks element ``8 * b + j``). The XLA block
    function's."""
    seeds = _chacha_seeds(masking, round_key, pid_base, rows, d_loc)
    # The draws keep the block function's word-major layout
    # [S, 8, d_loc/8] through pairing and reduction, both elementwise,
    # and so does the fold over the rows that every reader of the masks
    # starts with (``_chacha_block_fold``): the layout change is a
    # permutation and commutes with it. A scope each, so the device
    # trace tells cipher, reduction, fold and layout change apart
    # (docs/observability.md)
    with jax.named_scope("sda.mask.chacha"):
        draws = chacha_jax.stream_u64_words_at(
            seeds, d_block0, nblocks=d_loc // 8)
    with jax.named_scope("sda.mask.reduce"):
        return f.from_u64(draws)


def _chacha_block_fold(masking, f: FieldOps, round_key, pid_base, rows: int,
                       d_loc: int, d_block0):
    """``_chacha_mask_fold`` with the XLA block function: the masks of
    ``_chacha_masks`` summed over the rows."""
    masks = _chacha_masks(masking, f, round_key, pid_base, rows, d_loc,
                          d_block0)
    with jax.named_scope("sda.mask.fold"):
        return f.sum(masks, axis=0)


def _chacha_kernel_fold(masking, f: FieldOps, round_key, pid_base,
                        rows: int, d_loc: int, d_block0, interpret: bool):
    """``_chacha_mask_fold`` with the on-core cipher: ONE Pallas kernel,
    ``sda_chacha_mask_fold``, makes the sum of the rows' reduced draws
    (``fields/chacha_kernel.py``), bit for bit ``_chacha_block_fold``'s."""
    seeds = _chacha_seeds(masking, round_key, pid_base, rows, d_loc)
    with jax.named_scope("sda.mask.chacha"):
        return chacha_kernel.mask_fold(seeds, d_block0, nblocks=d_loc // 8,
                                       sp=f.sp, interpret=interpret)


def _chacha_mask_fold(masking, f: FieldOps, round_key, pid_base, rows: int,
                      d_loc: int, d_block0, cipher: str = "xla"):
    """-> [8, d_loc/8]: the masks of ``rows`` participants summed over the
    rows, word-major as ``_chacha_masks`` makes them, by the ``cipher`` of
    ``_chacha_cipher``: the on-core cipher's one kernel call, or the XLA
    block function, the reduction and the fold."""
    if cipher == "xla":
        return _chacha_block_fold(masking, f, round_key, pid_base, rows,
                                  d_loc, d_block0)
    return _chacha_kernel_fold(masking, f, round_key, pid_base, rows, d_loc,
                               d_block0, interpret=cipher == "interpret")


def _element_order(folded):
    """Word-major [8, d_loc/8] -> [d_loc], the stream's order: the one
    layout change of the mask expansion, on a fold of the masks and never
    on a participant's row (one one-hot matmul per byte a row,
    ``chacha_jax.element_order``: a row costs the matrix unit 8 GFLOP at
    a million elements)."""
    with jax.named_scope("sda.mask.relayout"):
        return chacha_jax.element_order(folded)


def _chacha_mask_sum(masking, f: FieldOps, round_key, pid_base, rows: int,
                     d_loc: int, d_block0, cipher: str = "xla"):
    """-> [d_loc] sum of the ChaCha masks of ``rows`` participants, put in
    element order ONCE. The on-core cipher makes the sum of all ``rows``
    in ONE kernel call, whatever their number. The XLA block function
    expands them ``_SCAN_CHUNK`` rows at a time under a word-major running
    sum: what is live is one block's draws, whatever ``rows`` (the whole
    [rows, d_loc] block at once is 22 MB of temporaries a row at a million
    elements, and 1200 rows do not compile for a v5e: PERF.md).
    There, rows that do not fill the last block are expanded too: the
    sum is added to the fold of the inputs and subtracted from the reveal,
    so every mask in it cancels. Both steps take the masks' sum from here,
    once a round."""
    with jax.named_scope("sda.mask"):
        if cipher != "xla":
            return _element_order(_chacha_mask_fold(
                masking, f, round_key, pid_base, rows, d_loc, d_block0,
                cipher))
        chunk, padded_rows = _scan_rows(rows, _SCAN_CHUNK)

        def body(acc, i):
            fold = _chacha_block_fold(masking, f, round_key,
                                      pid_base + i * chunk, chunk, d_loc,
                                      d_block0)
            with jax.named_scope("sda.mask.fold"):
                return f.add(acc, fold), None

        with jax.named_scope("sda.mask.fold"):
            init = jnp.zeros((8, d_loc // 8), f.dtype)
        acc, _ = jax.lax.scan(
            body, init, jnp.arange(padded_rows // chunk, dtype=jnp.int32))
        return _element_order(acc)


def _share_draws(scheme, f: FieldOps, rows: int, d: int, skey):
    """The share randomness of ``rows`` participants over ``d`` elements,
    folded over the participants: ``[t, B]`` for a Shamir scheme, the
    ``[n - 1, d]`` free rows for the additive one (the n-th row of a
    participant is its secret less the others).

    ``f.uniform`` rows, every element reduced from the 64 bits of one
    threefry block of its own. The ``[rows, ...]`` draws never reach HBM:
    they have ONE consumer, the fold over participants, and the draw, its
    reduction and the fold compile to one fusion."""
    with jax.named_scope("sda.share"):
        return f.sum(f.uniform(skey, (rows,) + _drawn_shape(scheme, d)), axis=0)


def _drawn_shape(scheme, d: int) -> Tuple[int, int]:
    """The shape of one participant's share randomness over ``d``
    elements (``_share_draws``)."""
    if isinstance(scheme, SHAMIR_SCHEMES):
        return scheme.privacy_threshold, -(-d // scheme.secret_count)
    return scheme.share_count - 1, d


def _share_combine(scheme, f: FieldOps, M_host, masked_sum, drawn):
    """[d_loc] fold of the participants' masked residues and the fold of
    their share randomness (``_share_draws``) -> [n, B] participant-SUMMED
    share rows.

    Share generation is linear in the (secrets, randomness) vector, so the
    clerk-combined output Σ_p M @ v_p equals M @ Σ_p v_p: participants
    fold with cheap modular adds FIRST and the share matmul runs once --
    the [S, n, B] per-participant share tensor is never materialized
    (those rows live on the participants' own devices in the federated
    protocol; a pod computing the aggregate needs only their sum).
    Bit-exact vs summing per-participant shares from
    ``sharing.packed_share32``/``packed_share``/``additive_share`` (the
    federated client path) drawn from the same key: mod-m arithmetic is
    exact, so fold order is free (tests/test_mesh.py)."""
    d = masked_sum.shape[0]
    with jax.named_scope("sda.share"):
        if isinstance(scheme, SHAMIR_SCHEMES):
            k = scheme.secret_count
            sk = sharing.batch_columns(masked_sum, k)              # [k, B]
            zeros = jnp.zeros((1, -(-d // k)), sk.dtype)
            values = jnp.concatenate([zeros, sk, drawn], axis=0)   # [m2, B]
            if f.sp is not None:
                return fastfield.modmatmul32(M_host, values, f.sp)
            from ..fields import modular

            return modular.modmatmul(jnp.asarray(M_host), values, f.m)
        # additive: Σ_p last_p = Σ_p masked_p - Σ over all draws. The
        # folded rows come off one by one, n - 1 subtractions: the
        # compiler turns f.sum(drawn, axis=0) into a second reduce over the
        # draws themselves, and a draw with two consumers is written to HBM
        # and read twice (or made twice) instead of fusing into its fold.
        # They come off as rows, [1, d] less drawn[i:i+1]: a flat [d] vector
        # and a row of [n - 1, d] tile differently on the TPU, and cutting
        # the rows into flat vectors is a pass over them that changes
        # nothing but the layout
        last = masked_sum[None, :]                                 # [1, d]
        for i in range(scheme.share_count - 1):
            last = f.sub(last, drawn[i:i + 1])
        return jnp.concatenate([drawn, last], axis=0)


def _pallas_stage(scheme, f: FieldOps, M_host, masking, x, dev_key, *,
                  round_key=None, pid_base=0, d_block0=0,
                  interpret: bool = False, external_bits_fn=None,
                  reported=None, cipher: str = "xla"):
    """[S, d_loc] canonical residues -> (combined shares [n, B0],
    mask sum [d_loc] | None) on the fused Pallas kernel.

    The kernel path's local step (``_RoundPlan``), ``_scan_combine``'s
    twin. Fold first, lay out second, as ``_share_combine`` does: the
    residues fold over the participants on
    their native [S, d_loc] layout (``sda.fold``: one read of the input,
    exact), the column-per-batch relayout and the pad to the kernel's
    column tile run on the folded [d_loc] vector (``sda.relayout``), and
    the kernel (``sda.mask_share``, pallas_round.py) draws the S
    participants' masks and share randomness on-core and shares the
    folds. Nothing per participant is laid out, and with none/full
    masking the compiler fuses the residue pass into the fold, so no
    op writes S x d_loc elements (PERF.md §5). The round
    result is exact for ANY mask/share randomness — masks cancel in the
    final subtract and the random polynomial rows are annihilated by the
    reconstruction matrix — so swapping the XLA threefry draws for the
    kernel's on-core PRNG (or injected external bits) never changes the
    aggregate; tests pin pallas-pod == xla-pod == plain sum.

    ChaCha masking: the mask is the CHACHA_PRG_V1 stream, a function of
    (round key, global participant id, dim offset). The masks' sum is made
    by ``_chacha_mask_sum`` with the ``cipher`` the step was built for (on
    a TPU one call of the on-core cipher for all S rows; elsewhere the XLA
    step's expansion, ``_SCAN_CHUNK`` rows at a time under a running sum)
    and added to the fold, and the kernel
    then runs mask-free on that masked fold: no [S, d_loc] array of draws,
    masks or masked inputs exists. ``round_key``/``pid_base``/
    ``d_block0`` locate this tile in the global stream exactly like the
    XLA path. This is prg-tag-independent by the same cancellation
    argument as above: pod masks never leave the round, so the scheme's
    wire ``prg`` (default rand-0.3) only governs FEDERATED seed uploads,
    which pod mode never produces.

    ``external_bits_fn(key, S, draws, B)`` (tests/util.external_bits
    layout) enables interpret-mode runs on CPU, where the TPU PRNG
    primitive is unavailable.

    ``reported`` ([S] bool, traced): the rows that count; the others are
    read as zero inside the fold (``_reported_rows``). The kernel never
    sees rows, only their fold, and draws for all S as before.
    """
    from ..fields import pallas_round

    S, d_loc = x.shape
    k, t = scheme.secret_count, scheme.privacy_threshold
    masked = isinstance(masking, FullMasking)
    with jax.named_scope("sda.fold"):
        x_sum = f.sum(_reported_rows(x, reported), axis=0)  # [d_loc]
    chacha_mask_sum = None
    if isinstance(masking, ChaChaMasking):
        # sum_p (x_p + m_p) = sum_p x_p + sum_p m_p mod p, bit for bit: the
        # masks never meet the [S, d_loc] input, only its fold
        chacha_mask_sum = _chacha_mask_sum(
            masking, f, round_key, pid_base, S, d_loc, d_block0, cipher)
        with jax.named_scope("sda.mask"), jax.named_scope("sda.mask.fold"):
            x_sum = f.add(x_sum, chacha_mask_sum)
    # sda.relayout: the XLA passes that put the folded secrets into the
    # kernel's [k, B] tile layout (and take the mask sum back out of it,
    # below)
    with jax.named_scope("sda.relayout"):
        x_cols = sharing.batch_columns(x_sum, k)            # [k, B0]
    B0 = x_cols.shape[-1]
    tile = pallas_round.column_tile(B0)
    pad = (-B0) % tile
    if pad:  # padded columns are sliced off below; their shares never land
        with jax.named_scope("sda.relayout"):
            x_cols = jnp.pad(x_cols, ((0, 0), (0, pad)))
    # the kernel with what it draws from -- its seed (and a test's bits) --
    # and its share rows cut to the real columns
    with jax.named_scope("sda.mask_share"):
        seed = jax.random.randint(dev_key, (), 0, np.int32(2**31 - 1),
                                  dtype=jnp.int32)
        ext = None
        if external_bits_fn is not None:
            draws = (k + t) if masked else t
            ext = external_bits_fn(dev_key, S, draws, B0 + pad)
        shares, mask_tot = pallas_round.fused_mask_share_combine(
            x_cols, S, seed, f.sp, M_host, t, masked,
            tile=tile, external_bits=ext, interpret=interpret,
        )
        shares = shares[:, :B0]
    if not masked:
        return shares, chacha_mask_sum
    with jax.named_scope("sda.relayout"):
        return shares, sharing.unbatch_columns(mask_tot[:, :B0], d_loc)


def _scan_rows(rows: int, chunk: int) -> Tuple[int, int]:
    """(block size, rows after the pad to whole blocks) of the XLA step's
    participant scan over ``rows`` local rows."""
    chunk = max(1, min(int(chunk), rows))
    return chunk, -(-rows // chunk) * chunk


def _scan_combine(scheme, f: FieldOps, M_host, masking, x, key, *,
                  round_key=None, pid_base=0, d_block0=0, reported=None,
                  cipher: str = "xla"):
    """[P, d] canonical residues -> (acc_shares [n, B], acc_mask [d]|None):
    the XLA step, every driver's local step where the fused kernel is not
    asked for (``_RoundPlan``); ``_pallas_stage``'s call form.

    All that is linear in the rows happens once a round, outside the
    participant scan: the rows fold in ONE read of the input
    (``sda.fold``; ``reported`` [P] bool, traced, reads the rows that did
    not report as zero inside it), the ChaCha masks' sum is made by
    ``_chacha_mask_sum`` (keyed by round key and participant id alone; on
    a TPU one call of the on-core cipher and ONE ``_element_order``), and
    the shares are made once from the masked fold and the folded
    randomness (``_share_combine``: share generation is linear).

    The scan carries only what is drawn by block, from the block's key
    ``fold_in(key, i)``: the share randomness of ``_SCAN_CHUNK`` rows
    (``_share_draws``) and, under full masking, their masks (``split`` of
    that key), each folded into its running sum. What is live is one
    block's draws, [chunk, ...] instead of [P, ...]. The draws of the
    rows that pad the last block, or did not report, only cancel.
    """
    P, d = x.shape
    chunk, padded_rows = _scan_rows(P, _SCAN_CHUNK)
    full = isinstance(masking, FullMasking)
    with jax.named_scope("sda.fold"):
        x_sum = f.sum(_reported_rows(x, reported), axis=0)
    mask_sum = None
    if isinstance(masking, ChaChaMasking):
        mask_sum = _chacha_mask_sum(masking, f, round_key, pid_base, P, d,
                                    d_block0, cipher)

    def body(carry, i):
        drawn, acc_m = carry
        with jax.named_scope("sda.share"):
            skey = jax.random.fold_in(key, i)
        if full:
            with jax.named_scope("sda.mask"):
                mkey, skey = jax.random.split(skey)
                masks = f.uniform(mkey, (chunk, d))
                with jax.named_scope("sda.mask.fold"):
                    acc_m = f.add(acc_m, f.sum(masks, axis=0))
        # an accumulator's add stands under the stage whose result it adds
        block = _share_draws(scheme, f, chunk, d, skey)
        with jax.named_scope("sda.share"):
            return (f.add(drawn, block), acc_m), None

    with jax.named_scope("sda.share"):
        init_d = jnp.zeros(_drawn_shape(scheme, d), f.dtype)
    init_m = None
    if full:
        with jax.named_scope("sda.mask"), jax.named_scope("sda.mask.fold"):
            init_m = jnp.zeros((d,), f.dtype)
    # sda.blocks: the scan's block counter
    with jax.named_scope("sda.blocks"):
        counter = jnp.arange(padded_rows // chunk, dtype=jnp.int32)
    (drawn, acc_m), _ = jax.lax.scan(body, (init_d, init_m), counter)
    if full:
        mask_sum = acc_m
    if mask_sum is not None:
        with jax.named_scope("sda.mask"), jax.named_scope("sda.mask.fold"):
            x_sum = f.add(x_sum, mask_sum)
    return _share_combine(scheme, f, M_host, x_sum, drawn), mask_sum


def _reconstruct_stage(scheme, f: FieldOps, L_host, gathered, d_loc: int,
                       survivors=None):
    """[n, B] clerk rows -> [d_loc] masked totals, from the ``survivors``
    rows alone where a quorum is given (clerk dropout: the rows a lost
    device or process hosted never enter the reconstruction)."""
    with jax.named_scope("sda.reconstruct"):
        if survivors is not None:
            gathered = gathered[jnp.asarray(survivors), :]
        if isinstance(scheme, SHAMIR_SCHEMES):
            if f.sp is not None:
                return sharing.packed_reconstruct32(
                    gathered, L_host, f.sp, dimension=d_loc
                )
            return sharing.packed_reconstruct(
                gathered, jnp.asarray(L_host),
                prime=scheme.prime_modulus, dimension=d_loc,
            )
        return f.sum(gathered, axis=0)  # additive: plain share sum


def _chacha_blocks(masking, rows: int, d_total: int, p_shards: int) -> int:
    """ChaCha20 blocks one round asks of the mesh (8 u64 draws a block), 0
    under any other masking. Both steps make the masks' sum with
    ``_chacha_mask_sum``: ``rows`` per p shard, rounded up to the whole
    blocks of ``_SCAN_CHUNK`` rows of its XLA expansion (its one kernel
    call expands the rows as they are)."""
    if not isinstance(masking, ChaChaMasking):
        return 0
    return p_shards * _scan_rows(rows, _SCAN_CHUNK)[1] * (d_total // 8)


def _dim_grain(scheme, masking) -> int:
    """Smallest dim-chunk size a single device can hold: packing width,
    times the ChaCha block width when masks are stream-expanded."""
    grain = scheme.input_size
    if isinstance(masking, ChaChaMasking):
        grain = math.lcm(grain, 8)
    return grain


def _build_matrices(scheme, survivors: Optional[Tuple[int, ...]] = None):
    if not isinstance(scheme, SHAMIR_SCHEMES):
        return None, None
    M = numtheory.share_matrix_for(scheme)
    L = numtheory.reconstruct_matrix_for(
        scheme,
        tuple(range(scheme.share_count)) if survivors is None else survivors,
    )
    return M, L


def _normalize_survivors(scheme, surviving_clerks) -> Optional[Tuple[int, ...]]:
    """Validate a clerk-dropout quorum for the mesh modes (SURVEY §2.4
    fault-tolerant-quorum row; reference semantics crypto.rs:146-153).

    The pod/streamed finale reconstructs from clerk ROWS; a lost device or
    process loses the clerk rows it hosts, never the mask sums (masks
    travel participant->recipient, not through clerks — receive.rs:102-118),
    so dropping to a quorum of rows recovers the exact aggregate. Truncates
    to exactly reconstruction_threshold rows so the finale has ONE compiled
    shape per survivor count (the fixed-quorum design of
    crypto/sharing.py::PackedShamirReconstructor).
    """
    if surviving_clerks is None:
        return None
    survivors = tuple(int(i) for i in surviving_clerks)
    n = scheme.output_size
    if any(i < 0 or i >= n for i in survivors) or len(set(survivors)) != len(survivors):
        raise ValueError(f"surviving clerks {survivors} must be distinct in [0, {n})")
    if not isinstance(scheme, SHAMIR_SCHEMES):
        if len(survivors) < n:
            raise ValueError(
                "additive sharing needs every clerk row; clerk dropout "
                "requires a Shamir scheme (crypto.rs:146-153)"
            )
        return None  # all rows = the normal finale
    r = scheme.reconstruction_threshold
    if len(survivors) < r:
        raise ValueError(
            f"need at least reconstruction_threshold={r} surviving clerks, "
            f"got {len(survivors)}"
        )
    return survivors[:r]


def refuse_reported(reported, driver: str) -> None:
    """The drivers that stream a cohort block by block take no ``reported``
    operand yet: handed one they raise, they never sum the rows it rules
    out."""
    if reported is not None:
        raise NotImplementedError(
            f"{driver} takes no `reported` operand: a round over the rows "
            "that reported is SimulatedPod's (aggregate, aggregate_fn, "
            "round_program); stream the rows that reported alone")


def _reported_operand(reported, rows: int, padded_rows: int):
    """The caller's ``reported`` as the round's operand: [padded_rows]
    bool, the padding rows not reported; a ``jax.Array`` stays where it
    lies."""
    if not isinstance(reported, jax.Array):
        reported = np.asarray(reported)
    if reported.shape != (rows,):
        raise ValueError(f"reported has shape {reported.shape}; the cohort "
                         f"has {rows} rows")
    reported = reported.astype(bool)
    if padded_rows == rows:
        return reported
    pad = jnp.pad if isinstance(reported, jax.Array) else np.pad
    return pad(reported, (0, padded_rows - rows))


class _RoundPlan:
    """What every round driver settles before it traces a step, in one
    place: one per driver, built by its constructor from the caller's
    arguments. It holds the scheme's modulus; the masking, defaulted and
    checked against the lattice and the modulus; the mesh (``None``: the
    default mesh over every device) and its committee layout; the clerk
    quorum and the share / reconstruct matrices; the field, with the
    headroom its collectives need over ``p``; and the local step.

    ``local_step(x [S, d_loc], dev_key, *, round_key, pid_base, d_block0,
    reported=None) -> (participant-summed shares [n, B], masks' sum
    [d_loc] | None)`` is the fused kernel (``_pallas_stage``) where it is
    asked for and the XLA step (``_scan_combine``) everywhere else, with
    the ChaCha ``cipher`` the steps are built for (``_chacha_cipher``).
    This is the one place the two are chosen, before anything is traced;
    a ``functools.partial``, so no Python frame stands between a driver's
    traced function and its stage. ``draws`` is the stage scope a step
    derives its place on the mesh and its keys under: the stage that draws
    from them (docs/observability.md, "Stage scopes").

    The fused kernel serves packed and basic Shamir over a Solinas prime
    with none/full/ChaCha masking; asked for on another scheme or modulus
    it raises, and nothing falls back to the XLA step behind the caller.
    Pod-internal masks are generated AND cancelled inside the round, so
    the choice is independent of the masking's wire ``prg`` tag.
    """

    def __init__(self, scheme: LinearSecretSharingScheme, masking, mesh,
                 use_pallas: bool = False, surviving_clerks=None,
                 pallas_interpret: bool = False, pallas_external_bits_fn=None):
        self.scheme = scheme
        self.modulus = _scheme_modulus(scheme)
        self.masking = masking or NoMasking()
        if not isinstance(self.masking, (NoMasking, FullMasking, ChaChaMasking)):
            raise ValueError(
                f"unsupported masking scheme {type(self.masking).__name__}")
        # the mask/unmask algebra only cancels when masking and sharing
        # operate in the same group
        mask_mod = getattr(self.masking, "modulus", None)
        if mask_mod is not None and mask_mod != self.modulus:
            raise ValueError(f"masking modulus {mask_mod} != sharing modulus "
                             f"{self.modulus}: masks would not cancel")
        if mesh is None:
            mesh = make_mesh(*default_mesh_shape(len(jax.devices()),
                                                 scheme.output_size))
        self.mesh = mesh
        p_shards = mesh.devices.shape[0]
        if scheme.output_size % p_shards:
            raise ValueError(
                f"committee size {scheme.output_size} must be divisible by "
                f"the p axis ({p_shards})")
        self.survivors = _normalize_survivors(scheme, surviving_clerks)
        self.M_host, self.L_host = _build_matrices(scheme, self.survivors)
        # cross-shard share/mask sums ride collectives between canonicalizes
        # (psum/psum_scatter add p_shards canonical residues): past the
        # uint32 path's bound FieldOps.create falls back to int64, and the
        # int64 path, which cannot chunk inside a collective, must hold it
        self.field = f = FieldOps.create(self.modulus, cross_terms=p_shards)
        if f.sp is None and p_shards * (f.m - 1) >= (1 << 63):
            raise ValueError(
                f"modulus {f.m} too large for {p_shards}-way participant "
                f"shards: cross-shard sums would overflow int64 — use fewer "
                f"p shards or a smaller modulus")
        self.pallas_active = bool(use_pallas)
        if self.pallas_active and not (isinstance(scheme, SHAMIR_SCHEMES)
                                       and f.sp is not None):
            raise ValueError("the pallas step requires packed-Shamir over a "
                             "Solinas prime (none/full/chacha masking)")
        self.cipher = _chacha_cipher(f, mesh.devices)
        head = (scheme, f, self.M_host, self.masking)
        if self.pallas_active:
            self.draws = "sda.mask_share"
            self.local_step = functools.partial(
                _pallas_stage, *head, cipher=self.cipher,
                interpret=bool(pallas_interpret),
                external_bits_fn=pallas_external_bits_fn)
        else:
            self.draws = "sda.share"
            self.local_step = functools.partial(_scan_combine, *head,
                                                cipher=self.cipher)

    def finale(self, shares, mask_sum, d_loc: int, collective: bool = True):
        """[n, B] participant-summed shares and the masks' sum (None: no
        masks) -> the [d_loc] int64 aggregate: reconstruct, then unmask.

        ``collective``: a step under ``shard_map`` over the mesh, whose
        rows and sums are its device's part. The snapshot transpose and
        every clerk's combine are then ONE ``psum_scatter`` over ``p`` (the
        clerk axis split across ``p`` while the participant shards' partial
        sums combine), the recipient gathers every clerk row with one
        ``all_gather``, and the masks' sums are ``psum``'d over ``p``
        before they are subtracted. Without, the rows and the sum are the
        whole committee's already."""
        f = self.field
        if collective:
            with jax.named_scope("sda.clerk_combine"):
                rows = jax.lax.psum_scatter(shares, "p", scatter_dimension=0,
                                            tiled=True)            # [n/p, B]
                rows = f.canon(rows)
                shares = jax.lax.all_gather(rows, "p", axis=0, tiled=True)
        total = _reconstruct_stage(self.scheme, f, self.L_host, shares, d_loc,
                                   self.survivors)                 # [d_loc]
        with jax.named_scope("sda.unmask"):
            if mask_sum is None:
                return f.to_int64(total)
            if collective:
                mask_sum = f.canon(jax.lax.psum(mask_sum.reshape(d_loc), "p"))
            return f.to_int64(f.sub(total, mask_sum))


class _Planned:
    """A round driver's plan (``self._plan``) under the names its callers
    and the benchmark read."""

    scheme = property(lambda self: self._plan.scheme)
    mesh = property(lambda self: self._plan.mesh)
    masking = property(lambda self: self._plan.masking)
    modulus = property(lambda self: self._plan.modulus)
    surviving_clerks = property(lambda self: self._plan.survivors)
    pallas_active = property(lambda self: self._plan.pallas_active)
    _field = property(lambda self: self._plan.field)
    #: Solinas parameters when the uint32 fast path is active, else None
    _sp = property(lambda self: self._plan.field.sp)
    _cipher = property(lambda self: self._plan.cipher)


class SimulatedPod(_Planned):
    """One secure-aggregation round as a single SPMD program.

    Committee size must be divisible by the ``p`` axis; participant and
    dimension counts are auto-padded to the mesh/scheme grain (zero rows
    and components aggregate as zero; padding is stripped from the output).

    Two local steps compute the same round (``_RoundPlan``). The **XLA
    step** is the default (``use_pallas=False``) and serves every scheme
    and masking: ``_scan_combine`` folds the rows once and draws the share
    randomness (and full masks) in a scan over blocks of ``scan_chunk``
    rows. The **fused Pallas kernel** (``use_pallas=True``) serves packed
    and basic Shamir over a Solinas prime with none/full/ChaCha masking;
    asked for on additive sharing or a non-Solinas modulus it raises, so
    an additive-sharing aggregation always runs the XLA step.
    ``pallas_active`` says which step this pod took.

    Under ChaCha masking every dispatch of the round counts
    ``mesh.mask.chacha_calls``, ``mesh.mask.chacha_blocks`` (the ChaCha20
    blocks asked of the mesh, from static shapes) and
    ``mesh.mask.chacha_kernel_blocks`` (those of them the on-core cipher
    is asked for: all of them on a TPU mesh over a uint32 field, else 0);
    a pod with any other masking counts nothing.
    """

    #: participant rows a block of the XLA step's scan holds
    scan_chunk = _SCAN_CHUNK

    def __init__(
        self,
        sharing_scheme: LinearSecretSharingScheme,
        masking_scheme: Optional[LinearMaskingScheme] = None,
        mesh: Optional[Mesh] = None,
        use_pallas: bool = False,
        pallas_interpret: bool = False,
        pallas_external_bits_fn=None,
        surviving_clerks=None,
    ):
        self._plan = _RoundPlan(sharing_scheme, masking_scheme, mesh,
                                use_pallas, surviving_clerks, pallas_interpret,
                                pallas_external_bits_fn)
        self._step = None
        self._step_shape = None
        self._programs = {}  # round_program: what callers built, by their key

    # ------------------------------------------------------------------
    def _local_round(self, inputs, key, reported=None):
        """Per-device body under shard_map: inputs [P_loc, d_loc]; with
        ``reported`` [P_loc] (bool, traced) the rows that did not report
        count as zero, and the mesh's count of reporters is returned
        beside the aggregate."""
        plan = self._plan
        P_loc, d_loc = inputs.shape
        with jax.named_scope(plan.draws):
            pi = jax.lax.axis_index("p")
            di = jax.lax.axis_index("d")
            # distinct randomness per device block, domain-separated from
            # the ChaCha seed stream; seeds fold the raw round key so every
            # dim shard derives the same per-participant seed
            dev_key = _tile_key(key, pi, di)

        x = plan.field.to_residues(inputs)
        with jax.named_scope(plan.draws):  # behind the residue pass, as it lowers
            pid0, dblk0 = pi * P_loc, di * (d_loc // 8)
        # participant parallelism is a local fold: no [P_loc, n, B_loc]
        # share tensor on either step
        local_sum, local_mask_sum = plan.local_step(
            x, dev_key, round_key=key, pid_base=pid0, d_block0=dblk0,
            reported=reported)                                     # [n, B_loc]
        total = plan.finale(local_sum, local_mask_sum, d_loc)
        if reported is None:
            return total
        with jax.named_scope("sda.unmask"):
            # the divisor of a mean over the rows that reported: a scalar
            # the program reads, the same on every device
            return total, jax.lax.psum(
                jnp.sum(reported, dtype=jnp.int32), "p")

    def _build(self, P_total: int, d_total: int, around=None,
               name: str = "mesh.simpod.round", reported: bool = False):
        p_shards, d_shards = self.mesh.devices.shape
        if P_total % p_shards:
            raise ValueError(f"participants {P_total} not divisible by p axis {p_shards}")
        grain = _dim_grain(self.scheme, self.masking) * d_shards
        if d_total % grain:
            raise ValueError(
                f"dimension {d_total} must be divisible by the scheme/mesh "
                f"grain {grain}"
            )
        metrics.count("mesh.round.builds")
        fn = _shard_map(
            self._local_round,
            mesh=self.mesh,
            in_specs=(P("p", "d"), P()) + ((P("p"),) if reported else ()),
            out_specs=(P("d"), P()) if reported else P("d"),
        )
        if around is not None:  # round_program: one program with the round
            fn = around(fn)
        # devprof: compiled-shape registry + retrace span events + (opt-in)
        # cost analysis for the roofline block — one profile entry for the
        # whole SPMD round regardless of how many shapes get built. Every
        # holder of the callable (aggregate(), aggregate_fn() callers,
        # multihost) gets the pod.dispatch span around its calls, and under
        # ChaCha masking the mask counters: static amounts, settled here
        blocks = _chacha_blocks(self.masking, P_total // p_shards, d_total,
                                p_shards)
        on_core = self._cipher != "xla"
        counts = {"mesh.mask.chacha_calls": 1,
                  "mesh.mask.chacha_blocks": blocks,
                  "mesh.mask.chacha_kernel_blocks": blocks if on_core else 0,
                  } if blocks else None
        return devprof.instrument(name, jax.jit(fn), span="pod.dispatch",
                                  counts=counts)

    def padded_shape(self, P_total: int, d_total: int) -> Tuple[int, int]:
        p_shards, d_shards = self.mesh.devices.shape
        grain = _dim_grain(self.scheme, self.masking) * d_shards
        return (
            -(-P_total // p_shards) * p_shards,
            -(-d_total // grain) * grain,
        )

    def aggregate(self, inputs, key=None, reported=None):
        """[P, d] participant inputs -> [d] aggregate (one full round).

        ``reported`` ([P] of 0/1, NumPy, a sequence or a ``jax.Array``):
        the rows that count. A row whose entry is 0 adds exactly zero to
        the aggregate whatever it holds; the operand is a value the
        compiled round reads, so every set of reporters over a buffer of
        ``P`` rows runs one program. The caller knows its count.

        Spans: ``pod.pad`` (only when a pad happens), then ``mesh.round``
        = ``pod.feed`` + ``pod.dispatch`` + ``pod.wait``, then
        ``pod.strip`` -- siblings in one trace. Counters at the same
        boundaries: ``mesh.feed.{calls,bytes,pad_bytes}``
        (docs/observability.md)."""
        # an array the devices hold stays on them: the feed below then moves
        # it between shardings at most, and a pad is made where it lives
        resident = isinstance(inputs, jax.Array)
        if not resident:
            inputs = np.asarray(inputs)
        if key is None:
            from ..crypto.core import fresh_prng_key

            key = fresh_prng_key()
        P_total, d_total = inputs.shape
        P_pad, d_pad = self.padded_shape(P_total, d_total)
        if reported is not None:
            reported = _reported_operand(reported, P_total, P_pad)
        trace = obs.sibling_context()
        pad_bytes = 0
        if (P_pad, d_pad) != (P_total, d_total):
            # zero participants/components aggregate as zero (masks on the
            # padding cancel like any other mask); strip below
            with obs.span("pod.pad", parent=trace):
                if resident:
                    inputs = jnp.pad(inputs, ((0, P_pad - P_total),
                                              (0, d_pad - d_total)))
                else:
                    padded = np.zeros((P_pad, d_pad), dtype=inputs.dtype)
                    padded[:P_total, :d_total] = inputs
                    inputs = padded
            pad_bytes = inputs.nbytes
        step = self._get_step(P_pad, d_pad, reported is not None)
        sharding = NamedSharding(self.mesh, P("p", "d"))
        metrics.count("mesh.feed.calls")
        metrics.count("mesh.feed.bytes", inputs.nbytes)
        metrics.count("mesh.feed.pad_bytes", pad_bytes)
        # first round per shape includes jit compilation (jax.jit is lazy):
        # it shows in the phase stats as max_s >> min_s
        with timed_phase("mesh.round", parent=trace):
            # host array straight onto the mesh: each device receives only
            # its own shard (jnp.asarray first would commit the whole
            # [P, d] matrix to device 0 and reshard from there)
            with obs.span("pod.feed", attributes={
                    "bytes": inputs.nbytes, "dtype": str(inputs.dtype),
                    "shape": list(inputs.shape)}):
                device_inputs = jax.device_put(inputs, sharding)
            # the step opens pod.dispatch (_build)
            if reported is None:
                out = step(device_inputs, key)
            else:
                out, _ = step(device_inputs, key, jax.device_put(
                    reported, NamedSharding(self.mesh, P("p"))))
            with obs.span("pod.wait"):
                out.block_until_ready()
        with obs.span("pod.strip", parent=trace):
            return out[:d_total]

    def _get_step(self, P_pad: int, d_pad: int, reported: bool = False):
        """The jitted SPMD round for an already-padded shape (one-shape
        cache, shared by aggregate() and multihost.aggregate_process_local);
        the round that takes ``reported`` is another program than the one
        that does not."""
        shape = (P_pad, d_pad, reported)
        if self._step is None or self._step_shape != shape:
            self._step = self._build(P_pad, d_pad, reported=reported)
            self._step_shape = shape
        return self._step

    def aggregate_fn(self, P_total: int, d_total: int,
                     reported: bool = False):
        """The raw jitted SPMD round for benchmarking/compile checks
        (shapes must already satisfy the mesh/scheme grain):
        ``(inputs, key) -> aggregate``, and with ``reported``
        ``(inputs, key, reported [P_total] bool) -> (aggregate, the count
        of rows that reported, int32)``."""
        return self._build(P_total, d_total, reported=reported)

    def round_program(self, P_total: int, d_total: int, around, name: str,
                      key=None, reported: bool = False):
        """The round of ``aggregate_fn`` traced into a caller's program:
        ``around(round_)`` is handed the shard-mapped round
        ``round_(inputs [P_total, d_total], key) -> [d_total] int64``
        (with ``reported``: ``round_(inputs, key, reported [P_total] bool)
        -> (aggregate, count)``) and
        returns the function to jit in its place, instrumented as ``name``
        with the span and the counters every round's callable has. For a
        caller whose inputs are made on the devices (``models.federated``):
        producer, round and consumer compile as one program, so the
        compiler may fuse what makes the residues into the fold that reads
        them, and no ``[P_total, d_total]`` array of residues need stand in
        HBM beside what they were made from. With a hashable ``key`` the
        program is built once and kept with the pod: key it on the
        buffer's rows, never on a count of reporters."""
        if key is None:
            return self._build(P_total, d_total, around, name, reported)
        if (name, key) not in self._programs:
            self._programs[name, key] = self._build(P_total, d_total, around,
                                                    name, reported)
        return self._programs[name, key]


def single_chip_round(
    sharing_scheme: LinearSecretSharingScheme,
    masking_scheme: Optional[LinearMaskingScheme] = None,
    dim_tile: Optional[int] = None,
):
    """Collective-free full aggregation round, jittable on one device.

    Same algebra as SimulatedPod (mask -> share -> combine -> reconstruct ->
    unmask) with the committee resident on a single chip — the flagship
    single-chip "forward step" and the unit benchmark kernel. For Solinas
    moduli the whole round runs on the uint32 fast path (fields.fastfield);
    results are bit-identical either way. ChaCha masking requires the
    dimension to be a multiple of 8 (one ChaCha block).

    ``dim_tile``: process the dimension in fixed-width tiles via
    ``lax.scan`` instead of one full-width program, which bounds every
    tile's live set and makes round cost linear in d by construction
    (fields/dimtile.py; whether the full-width program is superlinear in
    d on the chip is not measured). Exact for any tile width:
    each tile is a complete mask->share->combine->reconstruct->unmask
    round over its own columns (masks cancel per tile; ChaCha tiles read
    their window of the global stream via d_block0).
    """
    # the round is jitted by the caller and runs on the default device
    plan = _RoundPlan(sharing_scheme, masking_scheme, make_mesh(1, 1))
    f = plan.field
    # tile grain: whole packing columns (input_size) and whole ChaCha
    # blocks (8 u64 draws) — same grain as the streaming driver
    grain = math.lcm(sharing_scheme.input_size, 8)

    def one_tile(x, bkey, round_key, d_block0, d_loc):
        combined, mask_total = plan.local_step(
            x, bkey, round_key=round_key, pid_base=0, d_block0=d_block0)
        return plan.finale(combined, mask_total, d_loc, collective=False)

    if dim_tile is None:
        def round_fn(inputs, key):
            P_total, d = inputs.shape
            return one_tile(f.to_residues(inputs), key, key, 0, d)

        return round_fn

    from ..fields.dimtile import scan_dim_tiles

    def tile_body(blk, round_key, tile_key, i, width):
        # per-tile residue conversion fuses into the tile program; the
        # ChaCha block counter locates this tile in the global stream
        return one_tile(f.to_residues(blk), tile_key, round_key,
                        i * (width // 8), width)

    return scan_dim_tiles(tile_body, grain, dim_tile)
