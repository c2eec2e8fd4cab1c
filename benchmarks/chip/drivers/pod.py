"""Driver ``pod``: the secure-sum round as one SPMD program on a mesh
(``sda_tpu.mesh.SimulatedPod``), one round at a time.

The traffic file says where a round's inputs live:

- ``"input": "resident"`` -- 32-bit residues already in HBM (sharded
  ``P('p', 'd')`` on a mesh), as on-device local training leaves them.
  A round is the jitted program from ``pod.aggregate_fn`` on the resident
  array, blocked on.
- ``"input": "host"`` -- the same values as an int64 NumPy matrix in host
  memory. A round is ``np.asarray(pod.aggregate(inputs, key))``: what
  ``sda-sim`` does, transfer, int64 -> residue pass and read-back included.

Both make the same values from the seed with one generator on the
device, run the same ``SimulatedPod`` at the same padded shape, and
check every round against the plain sum.
"""

from __future__ import annotations

import numpy as np


def build_pod(config: dict, devices, interpret: bool = False):
    """The configuration's ``SimulatedPod`` on ``devices`` (attached, or
    only described: compile_check.py)."""
    import jax
    import jax.numpy as jnp

    from schemes import packed_shamir
    from sda_tpu.mesh.simpod import (SimulatedPod, default_mesh_shape,
                                     make_mesh)
    from sda_tpu.protocol import FullMasking

    scheme = packed_shamir(config)
    if config["masking"] != "full":
        raise ValueError("driver 'pod' runs full masking")
    if config["mesh"] != "default":
        raise ValueError("driver 'pod' lays the committee out by "
                         "default_mesh_shape")
    mesh = make_mesh(*default_mesh_shape(len(devices), scheme.share_count),
                     devices=devices)
    interpreted = {}
    if interpret:  # no Mosaic and no on-core PRNG off the chip
        interpreted = dict(
            pallas_interpret=True,
            pallas_external_bits_fn=lambda key, rows, draws, columns:
                jax.random.bits(key, (rows, 2 * draws, columns), jnp.uint32))
    pod = SimulatedPod(scheme, FullMasking(scheme.prime_modulus), mesh=mesh,
                       use_pallas=config["use_pallas"], **interpreted)
    if pod.pallas_active != config["use_pallas"]:
        raise RuntimeError("the pod did not take the configured kernel path")
    return pod


class Pod:
    def __init__(self, cell, seed: int, devices, rehearsal: bool):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec

        config, traffic = cell.config, cell.traffic
        self.pod = build_pod(config, devices, interpret=rehearsal)
        scheme, mesh = self.pod.scheme, self.pod.mesh
        self.modulus = scheme.prime_modulus

        participants, dim = traffic["participants"], traffic["dim"]
        padded = self.pod.padded_shape(participants, dim)
        sharding = NamedSharding(mesh, PartitionSpec("p", "d"))
        shift = 32 - traffic["value_bits"]

        def generate(key):
            # zero rows and columns aggregate as zero, as aggregate() pads
            values = jax.random.bits(key, padded, jnp.uint32) >> shift
            rows = jnp.arange(padded[0])[:, None] < participants
            cols = jnp.arange(padded[1])[None, :] < dim
            return jnp.where(rows & cols, values, jnp.uint32(0))

        self.key = jax.random.PRNGKey(seed)
        self.fold_in = jax.random.fold_in
        generate = jax.jit(generate, out_shardings=sharding)
        reference = jax.jit(cell_reference(cell).on_device, static_argnums=1)
        self.host_fed = traffic["input"] == "host"
        if not self.host_fed and traffic["input"] != "resident":
            raise ValueError(f"unknown input {traffic['input']!r}")
        self.inputs = generate(jax.random.fold_in(self.key, 0x1A7A))
        self.expected = reference(self.inputs, self.modulus)[:dim]
        if self.host_fed:
            self.inputs = np.asarray(self.inputs)[:participants, :dim].astype(np.int64)
            self.expected = np.asarray(self.expected)
            self.inexact = 0
        else:
            self.step = self.pod.aggregate_fn(*padded)
            # the flag has one sharding from the start, so the check
            # compiles once
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
            "input_itemsize": 8 if self.host_fed else 4,
            "secret_count": scheme.secret_count,
            "share_count": scheme.share_count,
            "mesh": list(mesh.devices.shape),
            "pallas_active": self.pod.pallas_active,
            "cost_model": "pod_round",
        }
        # warm this shape (compiles or loads from the cache), and hold the
        # warm-up round to the reference before any round is timed
        self.round(-1)
        self.verify(-1)
        if self.finish():
            raise RuntimeError("the warm-up round did not reveal the plain sum")

    def round(self, index: int) -> None:
        key = self.fold_in(self.key, index + 1)  # a fresh key every round
        if self.host_fed:
            self.out = np.asarray(self.pod.aggregate(self.inputs, key))
        else:
            self.out = self.step(self.inputs, key)
            self.out.block_until_ready()

    def verify(self, _index: int) -> None:
        if self.host_fed:
            self.inexact += int(not np.array_equal(self.out, self.expected))
        else:  # stays on the device: one flag, read once after the window
            self.inexact = self.count_inexact(
                self.inexact, self.out, self.expected)

    def finish(self) -> int:
        """Rounds that did not reveal the plain sum."""
        return int(self.inexact)

    def close(self) -> None:
        self.inputs = self.expected = self.out = None


def cell_reference(cell):
    from harness import load_module

    return load_module(cell.home, "references", cell.config["reference"])


def setup(cell, seed: int, devices, rehearsal: bool) -> Pod:
    return Pod(cell, seed, devices, rehearsal)
