"""Driver ``pod_packed_chacha``: the secure-sum round under packed Shamir
sharing and ChaCha seed masks as one SPMD program on a mesh
(``sda_tpu.mesh.SimulatedPod`` with the fused kernel), one round at a
time.

``drivers/pod.py`` runs the packed scheme under full masking and
``drivers/pod_additive.py`` ChaCha masks under additive sharing on the XLA
step; this driver builds the pair its configuration states -- ``scheme``
{``kind``: ``packed_shamir``}, ``masking`` {``kind``: ``chacha``},
``use_pallas`` true -- and refuses a file it cannot build. Inputs, the
round and the on-device check are made as they make them for a resident
cell: 32-bit residues from the seed, left in HBM at the padded shape; a
round is the jitted program from ``pod.aggregate_fn`` on the resident
array, blocked on; every round is checked against the plain sum.

Before any round is timed, set-up holds the program's mask streams to the
reference's plain ChaCha20 (``check_streams`` of ``drivers/pod_additive.py``)
and keys the compile cache on op metadata too (:func:`setup` says why).
Set-up must not set the memory peak ``hbm_peak_share`` reads: the stream
check runs while the device is empty, and the generator and the expected
sum of 1200 x 1,000,008 hold 96,768 B and 0 B of temporaries (compiled for
a described v5e, PR 35: the widening to int64 fuses into the reduction),
so the sum is taken at once, as the other resident drivers take it.
"""

from __future__ import annotations


def build_pod(config: dict, dim: int, devices, interpret: bool = False):
    """The configuration's ``SimulatedPod`` on ``devices`` (attached, or
    only described), masking a vector of ``dim`` elements."""
    import jax
    import jax.numpy as jnp

    from schemes import packed_shamir
    from sda_tpu.mesh.simpod import (SimulatedPod, default_mesh_shape,
                                     make_mesh)
    from sda_tpu.protocol import ChaChaMasking

    scheme, masking = config["scheme"], config["masking"]
    if not isinstance(scheme, dict) or scheme.get("kind") != "packed_shamir":
        raise ValueError("driver 'pod_packed_chacha' runs packed Shamir "
                         f"sharing; the configuration states scheme {scheme!r}")
    if not isinstance(masking, dict) or masking.get("kind") != "chacha":
        raise ValueError("driver 'pod_packed_chacha' runs ChaCha seed masks; "
                         f"the configuration states masking {masking!r}")
    if config["mesh"] != "default":
        raise ValueError("driver 'pod_packed_chacha' lays the committee out "
                         "by default_mesh_shape")
    if config["use_pallas"] is not True:
        raise ValueError("this deployment runs the fused kernel: the "
                         "configuration must state use_pallas true")
    sharing = packed_shamir(config)
    mesh = make_mesh(*default_mesh_shape(len(devices), sharing.share_count),
                     devices=devices)
    interpreted = {}
    if interpret:  # no Mosaic and no on-core PRNG off the chip
        interpreted = dict(
            pallas_interpret=True,
            pallas_external_bits_fn=lambda key, rows, draws, columns:
                jax.random.bits(key, (rows, 2 * draws, columns), jnp.uint32))
    pod = SimulatedPod(
        sharing,
        ChaChaMasking(sharing.prime_modulus, dim, masking["seed_bitsize"]),
        mesh=mesh, use_pallas=True, **interpreted)
    if not pod.pallas_active:
        raise RuntimeError("the pod did not take the fused kernel")
    return pod


class PodPackedChaCha:
    def __init__(self, cell, seed: int, devices, rehearsal: bool):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec

        from harness import load_module

        config, traffic = cell.config, cell.traffic
        if traffic["input"] != "resident":
            raise ValueError("driver 'pod_packed_chacha' runs resident "
                             f"inputs; the traffic states {traffic['input']!r}")
        participants, dim = traffic["participants"], traffic["dim"]
        self.pod = build_pod(config, dim, devices, interpret=rehearsal)
        scheme, mesh = self.pod.scheme, self.pod.mesh
        self.modulus = self.pod.modulus
        padded = self.pod.padded_shape(participants, dim)
        reference = load_module(cell.home, "references", config["reference"])
        check_streams = load_module(
            cell.home, "drivers", "pod_additive").check_streams

        self.key = jax.random.PRNGKey(seed)
        self.fold_in = jax.random.fold_in
        # first, while nothing is on the device: the check's own arrays
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
            "secret_count": scheme.secret_count,
            "share_count": scheme.share_count,
            "privacy_threshold": scheme.privacy_threshold,
            "mesh": list(mesh.devices.shape),
            "pallas_active": self.pod.pallas_active,
            "cost_model": "packed_chacha_round",
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


def setup(cell, seed: int, devices, rehearsal: bool) -> PodPackedChaCha:
    import jax

    # This cell's per-layer metrics read the round's named scopes off the
    # executable's op metadata, which JAX's persistent-cache key leaves out
    # by default: a round compiled by a program with other scopes (the
    # parent commit, on a machine that keeps its cache) would be loaded in
    # place of this program's, scopes and all (drivers/pod_additive.py,
    # PR 29). With the metadata in the key each program runs its own.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return PodPackedChaCha(cell, seed, devices, rehearsal)
