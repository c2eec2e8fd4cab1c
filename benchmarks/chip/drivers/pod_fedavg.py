"""Driver ``pod_fedavg``: a FedAvg round of float32 client weights that
stay in HBM, through the library's entry point for it,
``sda_tpu.models.pod_fedavg_round`` on ``jax.Array``s: delta, fixed-point
encode, the secure-sum round (``SimulatedPod``: packed Shamir, full masks,
the fused kernel), decode and the new global vector as ONE device program,
one round at a time.

The other pod drivers start behind the codec, on residues; this one
starts where on-device local training stops. The traffic file states
``"dtype": "float32"`` and ``"input": "resident"``: the global vector is
uniform in (-1, 1) and a client's weights are the global vector plus a
standard normal delta (about 4.6 % of the elements beyond a clip of 2),
every row from a key of its own, so the cohort's first rows can be made
without the rest. Both are made on the device from the seed and left
there; a round is ``pod_fedavg_round(pod, codec, global, clients, key)``,
blocked on; the new global vector is not fed back, so what every round
must return stays fixed: the plain reference's
(``references/fedavg.py``), every element within its ``tolerance``.

Set-up, in this order, so that nothing of it stands on top of the round's
arrays in the memory peak ``hbm_peak_share`` reads:

1. it fails at once, with nothing on the device, on a tree whose codec
   has no device decode: there the entry point takes the cohort to the
   host and back, twice, minutes a round;
2. the integer stage, held exactly: the cohort's first ``CHECKED_ROWS``
   rows alone, through ``codec.encode_device`` and ``pod.aggregate_fn``,
   against the reference's integer sum, bit for bit;
3. the whole cohort; the reference's integer sum of it on the device, its
   float64 end on the host; the expected vector and the tolerance back on
   the device (4 + 8 MB);
4. the warm-up round, held to the reference before any round is timed.

It keys the compile cache on op metadata too (:func:`setup` says why).
"""

from __future__ import annotations

#: rows of the cohort whose integer aggregate set-up checks exactly
CHECKED_ROWS = 96


def build_pod(config: dict, devices, interpret: bool = False):
    """The configuration's ``SimulatedPod`` and ``FixedPointCodec`` on
    ``devices`` (attached, or only described)."""
    import jax
    import jax.numpy as jnp

    from schemes import packed_shamir
    from sda_tpu.mesh.simpod import (SimulatedPod, default_mesh_shape,
                                     make_mesh)
    from sda_tpu.models import FixedPointCodec
    from sda_tpu.protocol import FullMasking

    if config["masking"] != "full":
        raise ValueError("driver 'pod_fedavg' runs full masking; the "
                         f"configuration states {config['masking']!r}")
    if config["mesh"] != "default":
        raise ValueError("driver 'pod_fedavg' lays the committee out by "
                         "default_mesh_shape")
    if config["use_pallas"] is not True:
        raise ValueError("this deployment runs the fused kernel: the "
                         "configuration must state use_pallas true")
    scheme = packed_shamir(config)
    stated = config["codec"]
    codec = FixedPointCodec(stated["modulus"], stated["fractional_bits"],
                            stated["max_summands"], clip=stated["clip"])
    if codec.modulus != scheme.prime_modulus:
        raise ValueError("the codec and the scheme state two moduli")
    mesh = make_mesh(*default_mesh_shape(len(devices), scheme.share_count),
                     devices=devices)
    interpreted = {}
    if interpret:  # no Mosaic and no on-core PRNG off the chip
        interpreted = dict(
            pallas_interpret=True,
            pallas_external_bits_fn=lambda key, rows, draws, columns:
                jax.random.bits(key, (rows, 2 * draws, columns), jnp.uint32))
    pod = SimulatedPod(scheme, FullMasking(scheme.prime_modulus), mesh=mesh,
                       use_pallas=True, **interpreted)
    if not pod.pallas_active:
        raise RuntimeError("the pod did not take the fused kernel")
    return pod, codec


class PodFedAvg:
    def __init__(self, cell, seed: int, devices, rehearsal: bool):
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec

        from harness import load_module, log
        from sda_tpu.models import pod_fedavg_round

        config, traffic = cell.config, cell.traffic
        if traffic["input"] != "resident" or traffic["dtype"] != "float32":
            raise ValueError(
                "driver 'pod_fedavg' runs float32 weights resident on the "
                f"device; the traffic states {traffic['dtype']!r}, "
                f"{traffic['input']!r}")
        participants, dim = traffic["participants"], traffic["dim"]
        self.pod, self.codec = build_pod(config, devices, interpret=rehearsal)
        pod, codec, scheme = self.pod, self.codec, self.pod.scheme
        if participants > codec.max_summands:
            raise ValueError(f"{participants} participants exceed the "
                             f"codec's {codec.max_summands} summands")
        reference = load_module(cell.home, "references", config["reference"])
        stated = (codec.modulus, codec.clip, codec.fractional_bits)
        mesh = pod.mesh
        rows_sharded = NamedSharding(mesh, PartitionSpec("p", "d"))
        dim_sharded = NamedSharding(mesh, PartitionSpec("d"))
        everywhere = NamedSharding(mesh, PartitionSpec())

        self.key = jax.random.PRNGKey(seed)
        self.fold_in = jax.random.fold_in
        data_key = jax.random.fold_in(self.key, 0x1A7A)

        @jax.jit
        def make_global(key):
            return jax.random.uniform(key, (dim,), jnp.float32, -1.0, 1.0)

        def make_clients(rows):
            def make(key, global_vec):
                keys = jax.vmap(lambda row: jax.random.fold_in(key, row))(
                    jnp.arange(rows))
                deltas = jax.vmap(
                    lambda k: jax.random.normal(k, (dim,), jnp.float32))(keys)
                return global_vec[None, :] + deltas
            return jax.jit(make, out_shardings=rows_sharded)

        integer_sum = jax.jit(
            lambda g, c: reference.integer_sum(g, c, *stated, xp=jnp))

        self.global_vec = jax.device_put(
            make_global(jax.random.fold_in(data_key, 0)), dim_sharded)
        client_key = jax.random.fold_in(data_key, 1)

        # the integer stage, exactly, on the first rows alone: while the
        # cohort is not there, the check's arrays set no memory peak
        checked = min(CHECKED_ROWS, participants)
        head = make_clients(checked)(client_key, self.global_vec)
        padded = pod.padded_shape(checked, dim)
        residues = jax.jit(
            lambda g, c: jnp.pad(
                codec.encode_device(c - g[None, :]),
                ((0, padded[0] - checked), (0, padded[1] - dim))),
            out_shardings=rows_sharded)(self.global_vec, head)
        revealed = pod.aggregate_fn(*padded)(
            residues, self.fold_in(self.key, 0))[:dim]
        if not bool(jnp.array_equal(
                revealed, integer_sum(self.global_vec, head))):
            raise RuntimeError(
                f"the round's integer aggregate of the first {checked} rows "
                "is not the reference's sum of their quantized deltas")
        del head, residues, revealed

        self.clients = make_clients(participants)(client_key, self.global_vec)
        exact, mean = reference.new_global(
            np.asarray(self.global_vec),
            np.asarray(integer_sum(self.global_vec, self.clients)),
            participants, codec.modulus, codec.fractional_bits)
        limit = reference.tolerance(np.asarray(self.global_vec), mean)
        self.expected = jax.device_put(exact.astype(np.float32), dim_sharded)
        self.limit = jax.device_put(limit, dim_sharded)

        def check(tally, out, want, limit):
            outside, differ, share = reference.outside(out, want, limit, xp=jnp)
            return (tally[0] + (outside > 0), tally[1] + differ,
                    jnp.maximum(tally[2], share))

        # the tally has one sharding from the start, so the check compiles once
        self.tally = jax.device_put(
            (jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int64),
             jnp.zeros((), jnp.float64)), everywhere)
        self.check = jax.jit(check, out_shardings=everywhere)
        self.fedavg_round = pod_fedavg_round
        self.log = log
        self.out = None
        self.facts = {
            "participants": participants, "dim": dim,
            "padded": list(pod.padded_shape(participants, dim)),
            "elements_per_round": participants * dim,
            "input_itemsize": 4,
            "secret_count": scheme.secret_count,
            "share_count": scheme.share_count,
            "mesh": list(mesh.devices.shape),
            "pallas_active": pod.pallas_active,
            "cost_model": "pod_round",
        }
        # warm this shape (compiles or loads from the cache), and hold the
        # warm-up round to the reference before any round is timed
        self.round(-1)
        self.verify(-1)
        if self.finish():
            raise RuntimeError("the warm-up round is not the reference's "
                               "new global vector")

    def round(self, index: int) -> None:
        key = self.fold_in(self.key, index + 1)  # a fresh key every round
        self.out = self.fedavg_round(self.pod, self.codec, self.global_vec,
                                     self.clients, key)
        self.out.block_until_ready()

    def verify(self, _index: int) -> None:
        # stays on the device: one tally, read once after the window
        self.tally = self.check(self.tally, self.out, self.expected,
                                self.limit)

    def finish(self) -> int:
        """Rounds with an element outside the reference's tolerance."""
        failed, differ, share = (float(t) for t in self.tally)
        self.log(f"fedavg check: {int(failed)} round(s) outside the "
                 f"tolerance; {int(differ)} element(s) differ from the "
                 f"reference at all, the furthest at {share:.4f} of its limit")
        return int(failed)

    def close(self) -> None:
        self.clients = self.global_vec = self.expected = self.limit = None
        self.out = None


def setup(cell, seed: int, devices, rehearsal: bool) -> PodFedAvg:
    import jax

    from sda_tpu.models import FixedPointCodec

    if not hasattr(FixedPointCodec, "decode_mean_device"):
        # before anything is on the device: without the resident path
        # pod_fedavg_round pulls the cohort to the host, doubles it and
        # sends the encoded matrix there and back again
        raise SystemExit(
            "driver 'pod_fedavg' needs FixedPointCodec.decode_mean_device "
            "and pod_fedavg_round's resident path: this tree has neither")
    # This cell's per-layer metrics read the program's named scopes off the
    # executable's op metadata, which JAX's persistent-cache key leaves out
    # by default: a program compiled under other scopes would be loaded in
    # place of this one, scopes and all (drivers/pod_additive.py, PR 29).
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return PodFedAvg(cell, seed, devices, rehearsal)
