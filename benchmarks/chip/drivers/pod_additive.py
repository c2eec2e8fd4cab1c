"""Driver ``pod_additive``: the secure-sum round under additive n-of-n
sharing and ChaCha seed masks as one SPMD program on a mesh
(``sda_tpu.mesh.SimulatedPod``, the XLA step), one round at a time.

``drivers/pod.py`` runs packed Shamir under full masking and reads what
only such a scheme has; this driver builds what its configuration states
-- ``scheme`` {``kind``: ``additive``}, ``masking`` {``kind``: ``chacha``}
-- and refuses a file it cannot build. Inputs, expected sums, the round
and the on-device check are made as ``pod.py`` makes them for a resident
cell: 32-bit residues from the seed, left in HBM; a round is the jitted
program from ``pod.aggregate_fn`` on the resident array, blocked on;
every round is checked against the plain sum.

Before any round is timed, set-up holds the program's mask streams to the
reference's plain ChaCha20 (:func:`check_streams`), and it keys the
compile cache on op metadata too (:func:`setup` says why).
"""

from __future__ import annotations

import numpy as np

#: participants whose streams set-up checks, and the draws per window
#: (the first, a middle and the last window of each row)
CHECKED_PARTICIPANTS = 8
CHECKED_DRAWS = 1024


def build_pod(config: dict, dim: int, devices):
    """The configuration's ``SimulatedPod`` on ``devices``, masking a
    vector of ``dim`` elements."""
    from sda_tpu.mesh.simpod import (SimulatedPod, default_mesh_shape,
                                     make_mesh)
    from sda_tpu.protocol import AdditiveSharing, ChaChaMasking

    scheme, masking = config["scheme"], config["masking"]
    if not isinstance(scheme, dict) or scheme.get("kind") != "additive":
        raise ValueError("driver 'pod_additive' runs additive sharing; the "
                         f"configuration states scheme {scheme!r}")
    if not isinstance(masking, dict) or masking.get("kind") != "chacha":
        raise ValueError("driver 'pod_additive' runs ChaCha seed masks; the "
                         f"configuration states masking {masking!r}")
    if config["mesh"] != "default":
        raise ValueError("driver 'pod_additive' lays the committee out by "
                         "default_mesh_shape")
    if config["use_pallas"] is not False:
        raise ValueError("the fused kernel serves no additive scheme: the "
                         "configuration must state use_pallas false")
    sharing = AdditiveSharing(scheme["share_count"], scheme["modulus"])
    mesh = make_mesh(*default_mesh_shape(len(devices), sharing.share_count),
                     devices=devices)
    pod = SimulatedPod(
        sharing,
        ChaChaMasking(sharing.modulus, dim, masking["seed_bitsize"]),
        mesh=mesh, use_pallas=False)
    if pod.pallas_active:
        raise RuntimeError("the pod did not take the XLA step")
    return pod


def check_streams(pod, reference, key, width: int) -> None:
    """The program's mask streams against the reference's plain ChaCha20,
    on the device at the cell's padded ``width``, untimed: the seeds the
    round derives for the first participants under ``key``
    (``_chacha_seed_words``) carry ``seed_bitsize`` bits and zeros beyond,
    and the first, a middle and the last window of each one's stream
    (``stream_u64_at``), reduced modulo the modulus, equal
    ``reference.mask_stream``. Raises on a mismatch."""
    import jax
    import jax.numpy as jnp

    from sda_tpu.fields import chacha_jax
    from sda_tpu.mesh.simpod import _chacha_seed_words

    bits = pod.masking.seed_bitsize
    count = min(CHECKED_DRAWS, width)
    starts = sorted({0, (width - count) // 2, width - count})

    @jax.jit
    def expand(key):
        seeds = _chacha_seed_words(key, jnp.arange(CHECKED_PARTICIPANTS), bits)
        draws = chacha_jax.stream_u64_at(seeds, 0, dimension=width)
        return seeds, [draws[:, s:s + count] for s in starts]

    seeds, windows = expand(key)
    windows = {s: np.asarray(w) for s, w in zip(starts, windows)}
    seeds = np.asarray(seeds)
    words = -(-bits // 32)
    if seeds[:, words:].any() or not seeds[:, :words].any():
        raise RuntimeError(f"a {bits}-bit seed must fill {words} key words "
                           f"and leave the rest zero: {seeds.tolist()}")
    modulus = np.uint64(pod.modulus)
    for row, seed in enumerate(seeds):
        for start, window in windows.items():
            want = reference.mask_stream(seed[:words], start, count, pod.modulus)
            got = (window[row] % modulus).astype(np.int64)
            if not np.array_equal(got, want):
                raise RuntimeError(
                    f"participant {row}'s mask stream departs from the "
                    f"plain ChaCha20 at draws [{start}, {start + count})")


class PodAdditive:
    def __init__(self, cell, seed: int, devices):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec

        config, traffic = cell.config, cell.traffic
        if traffic["input"] != "resident":
            raise ValueError("driver 'pod_additive' runs resident inputs; "
                             f"the traffic states {traffic['input']!r}")
        participants, dim = traffic["participants"], traffic["dim"]
        self.pod = build_pod(config, dim, devices)
        mesh = self.pod.mesh
        self.modulus = self.pod.modulus
        padded = self.pod.padded_shape(participants, dim)
        reference = cell_reference(cell)

        self.key = jax.random.PRNGKey(seed)
        self.fold_in = jax.random.fold_in
        # first, while little is on the device: the check's own arrays
        # must not stand on top of the round's in the memory peak
        check_streams(self.pod, reference, self.fold_in(self.key, 0), padded[1])

        sharding = NamedSharding(mesh, PartitionSpec("p", "d"))
        shift = 32 - traffic["value_bits"]

        def generate(key):
            # zero rows and columns aggregate as zero, as aggregate() pads
            values = jax.random.bits(key, padded, jnp.uint32) >> shift
            rows = jnp.arange(padded[0])[:, None] < participants
            cols = jnp.arange(padded[1])[None, :] < dim
            return jnp.where(rows & cols, values, jnp.uint32(0))

        generate = jax.jit(generate, out_shardings=sharding)
        expected = jax.jit(reference.on_device, static_argnums=1)
        self.inputs = generate(jax.random.fold_in(self.key, 0x1A7A))
        self.expected = expected(self.inputs, self.modulus)[:dim]
        self.step = self.pod.aggregate_fn(*padded)
        # the flag has one sharding from the start, so the check compiles once
        everywhere = NamedSharding(mesh, PartitionSpec())
        self.inexact = jax.device_put(jnp.zeros((), jnp.int32), everywhere)
        self.count_inexact = jax.jit(
            lambda bad, out, want:
                jnp.where(jnp.array_equal(out[:dim], want), bad, bad + 1),
            out_shardings=everywhere)
        self.out = None
        self.facts = {
            "participants": participants, "dim": dim, "padded": list(padded),
            "elements_per_round": participants * dim,
            "input_itemsize": 4,
            "share_count": self.pod.scheme.share_count,
            "scan_chunk": self.pod.scan_chunk,
            "mesh": list(mesh.devices.shape),
            "pallas_active": self.pod.pallas_active,
            "cost_model": "additive_chacha_round",
        }
        # warm this shape (compiles or loads from the cache), and hold the
        # warm-up round to the reference before any round is timed
        self.round(-1)
        self.verify(-1)
        if self.finish():
            raise RuntimeError("the warm-up round did not reveal the plain sum")

    def round(self, index: int) -> None:
        key = self.fold_in(self.key, index + 1)  # a fresh key every round
        self.out = self.step(self.inputs, key)
        self.out.block_until_ready()

    def verify(self, _index: int) -> None:
        # stays on the device: one flag, read once after the window
        self.inexact = self.count_inexact(self.inexact, self.out, self.expected)

    def finish(self) -> int:
        """Rounds that did not reveal the plain sum."""
        return int(self.inexact)

    def close(self) -> None:
        self.inputs = self.expected = self.out = None


def cell_reference(cell):
    from harness import load_module

    return load_module(cell.home, "references", cell.config["reference"])


def setup(cell, seed: int, devices, rehearsal: bool) -> PodAdditive:
    import jax

    # This cell's per-layer metrics read the round's named scopes off the
    # executable's op metadata. JAX's persistent-cache key leaves metadata
    # out by default, so a round compiled by a program with other scopes
    # (the parent commit, on a machine that keeps its cache) would be
    # loaded in place of this program's, scopes and all (my chip run, PR
    # 29: the first traced run showed the cached program's scopes). With
    # the metadata in the key each program runs its own executable.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    # the XLA step needs no interpreter off the chip: a rehearsal runs the
    # same program at the traffic file's toy sizes
    del rehearsal
    return PodAdditive(cell, seed, devices)
