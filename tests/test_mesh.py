"""Simulated-pod tests on the virtual 8-device CPU mesh.

Validates that the one-program SPMD round (psum_scatter transpose+combine,
all_gather reconstruct) computes exactly what the protocol stack computes.
"""

import os

import jax
import numpy as np
import pytest

from sda_tpu.mesh import SimulatedPod, default_mesh_shape, make_mesh, single_chip_round
from sda_tpu.protocol import (
    AdditiveSharing,
    ChaChaMasking,
    FullMasking,
    PackedShamirSharing,
)

GOLDEN = PackedShamirSharing(3, 8, 4, 433, 354, 150)


from util import scheme_lattice_config as _pod_scheme_config


def needs_devices(n):
    return pytest.mark.skipif(
        len(jax.devices()) < n, reason=f"needs {n} virtual devices"
    )


@needs_devices(8)
@pytest.mark.parametrize("mesh_shape", [(8, 1), (4, 2), (2, 4), (1, 8)])
def test_pod_aggregate_matches_sum(mesh_shape):
    mesh = make_mesh(*mesh_shape)
    pod = SimulatedPod(GOLDEN, mesh=mesh)
    P_total, d = 16, 48  # divisible by p axis and by k*d_shards for all shapes
    rng = np.random.default_rng(0)
    inputs = rng.integers(0, 20, size=(P_total, d))
    out = np.asarray(pod.aggregate(inputs))
    np.testing.assert_array_equal(out, inputs.sum(axis=0) % 433)


@needs_devices(8)
def test_pod_with_full_masking():
    pod = SimulatedPod(GOLDEN, masking_scheme=FullMasking(433), mesh=make_mesh(4, 2))
    P_total, d = 8, 24
    rng = np.random.default_rng(1)
    inputs = rng.integers(0, 433, size=(P_total, d))
    out = np.asarray(pod.aggregate(inputs))
    np.testing.assert_array_equal(out, inputs.sum(axis=0) % 433)


@needs_devices(8)
def test_pod_deterministic_given_key():
    pod = SimulatedPod(GOLDEN, mesh=make_mesh(4, 2))
    inputs = np.ones((8, 24), dtype=np.int64)
    key = jax.random.PRNGKey(7)
    a = np.asarray(pod.aggregate(inputs, key))
    b = np.asarray(pod.aggregate(inputs, key))
    np.testing.assert_array_equal(a, b)


@needs_devices(8)
@pytest.mark.parametrize("mesh_shape", [(8, 1), (4, 2)])
@pytest.mark.parametrize("config", [
    "add-none", "add-full", "add-chacha", "shamir-none", "shamir-full",
    "shamir-chacha", "basic-none", "basic-full", "basic-chacha",
])
def test_pod_scheme_parity(mesh_shape, config):
    """Every masking x sharing point of the scheme lattice runs in pod mode
    and aggregates exactly — round-1 verdict: only shamir/full did."""
    dim = 50  # off-grain on purpose: exercises auto-padding for every config
    sharing, masking = _pod_scheme_config(config, dim)
    pod = SimulatedPod(sharing, masking_scheme=masking, mesh=make_mesh(*mesh_shape))
    rng = np.random.default_rng(11)
    inputs = rng.integers(0, 433, size=(6, dim))
    out = np.asarray(pod.aggregate(inputs, key=jax.random.PRNGKey(3)))
    np.testing.assert_array_equal(out, inputs.sum(axis=0) % 433)


@pytest.mark.parametrize("config", [
    "add-none", "add-full", "add-chacha", "shamir-none", "shamir-full",
    "shamir-chacha", "basic-none", "basic-full", "basic-chacha",
])
def test_single_chip_scheme_parity(config):
    """The collective-free round covers the same scheme lattice (ChaCha
    dims must align to the 8-draw ChaCha block)."""
    dim = 48
    sharing, masking = _pod_scheme_config(config, dim)
    if config.startswith("add"):
        sharing = AdditiveSharing(share_count=3, modulus=433)  # golden 3-way
    fn = jax.jit(single_chip_round(sharing, masking))
    rng = np.random.default_rng(12)
    inputs = rng.integers(0, 433, size=(5, dim))
    out = np.asarray(fn(inputs, jax.random.PRNGKey(4)))
    np.testing.assert_array_equal(out, inputs.sum(axis=0) % 433)


def test_single_chip_additive_large_modulus():
    """Additive sharing needs no prime: any ring modulus < 2^62 works —
    including moduli where a flat int64 sum of 8 shares would wrap 2^63
    (reviewer repro: modsum must chunk-fold, not plain-sum)."""
    m = (1 << 61) + 3
    fn = jax.jit(single_chip_round(
        AdditiveSharing(share_count=8, modulus=m), FullMasking(m)))
    rng = np.random.default_rng(13)
    inputs = rng.integers(0, 1 << 50, size=(6, 16))
    out = np.asarray(fn(inputs, jax.random.PRNGKey(5)))
    np.testing.assert_array_equal(out, inputs.sum(axis=0) % m)


@needs_devices(8)
def test_pod_chacha_sharding_invariant():
    """Seed-compressed masks must expand consistently across dim shards:
    the same round key yields the same aggregate on a (8,1) and a (4,2)
    mesh, and both equal the plain sum."""
    dim = 48
    sharing, masking = _pod_scheme_config("shamir-chacha", dim)
    rng = np.random.default_rng(14)
    inputs = rng.integers(0, 433, size=(8, dim))
    outs = []
    for shape in [(8, 1), (4, 2)]:
        pod = SimulatedPod(sharing, masking_scheme=masking,
                           mesh=make_mesh(*shape))
        outs.append(np.asarray(pod.aggregate(inputs, key=jax.random.PRNGKey(6))))
    np.testing.assert_array_equal(outs[0], inputs.sum(axis=0) % 433)
    np.testing.assert_array_equal(outs[1], inputs.sum(axis=0) % 433)


@needs_devices(8)
def test_pod_large_committee_exact():
    """80-clerk Packed-Shamir committee (81 = 3^4 points) as one SPMD
    round: the clerk axis splits 10 rows per device over the 8-way p axis."""
    from sda_tpu.fields import numtheory

    t, p, w2, w3 = numtheory.generate_packed_params(3, 80, 20)
    s = PackedShamirSharing(3, 80, t, p, w2, w3)
    pod = SimulatedPod(s, mesh=make_mesh(8, 1))
    rng = np.random.default_rng(15)
    inputs = rng.integers(0, 433, size=(8, 24))
    out = np.asarray(pod.aggregate(inputs, key=jax.random.PRNGKey(8)))
    np.testing.assert_array_equal(out, inputs.sum(axis=0) % p)


def test_default_mesh_shape():
    assert default_mesh_shape(8, 8) == (8, 1)
    assert default_mesh_shape(6, 8) == (2, 3)
    assert default_mesh_shape(5, 8) == (1, 5)


@needs_devices(8)
def test_pod_auto_padding():
    """Shapes off the mesh/scheme grain are zero-padded, not rejected
    (round-1 verdict: divisibility errors pushed padding onto callers)."""
    pod = SimulatedPod(GOLDEN, mesh=make_mesh(4, 2))
    rng = np.random.default_rng(4)
    for P_total, dim in [(7, 24), (8, 25), (5, 7)]:
        inputs = rng.integers(0, 433, size=(P_total, dim))
        out = np.asarray(pod.aggregate(inputs, key=jax.random.PRNGKey(1)))
        assert out.shape == (dim,)
        np.testing.assert_array_equal(out, inputs.sum(axis=0) % 433)


def test_pod_scheme_validation():
    with pytest.raises(ValueError):
        SimulatedPod(GOLDEN, mesh=make_mesh(8, 1), masking_scheme="bogus")
    with pytest.raises(ValueError):
        # mask modulus must equal the sharing prime or masks don't cancel
        SimulatedPod(GOLDEN, mesh=make_mesh(8, 1), masking_scheme=FullMasking(1000))


@needs_devices(8)
def test_pod_noncanonical_inputs():
    """Regression: unmasked inputs outside [0, p) must be canonicalized
    before sharing, not silently overflowed."""
    from sda_tpu.mesh import single_chip_round
    import jax.numpy as jnp

    from sda_tpu.fields import numtheory
    from sda_tpu.protocol import PackedShamirSharing

    t, p, w2, w3 = numtheory.generate_packed_params(3, 8, 29)
    scheme = PackedShamirSharing(3, 8, t, p, w2, w3)
    fn = jax.jit(single_chip_round(scheme))
    inputs = jnp.full((4, 6), 1 << 40, dtype=jnp.int64)
    out = np.asarray(fn(inputs, jax.random.PRNGKey(0)))
    np.testing.assert_array_equal(out, np.full(6, (4 * (1 << 40)) % p))


def test_share_sum_stage_equals_per_participant_fold():
    """The share stage's linearity fusion (``_share_draws`` folded over the
    participants where they are drawn, ``_share_combine`` once on the
    folds) must be bit-exact vs summing per-participant share rows drawn
    from the same key (both schemes, both field paths)."""
    import jax.numpy as jnp

    from sda_tpu.fields import numtheory, sharing
    from sda_tpu.fields.ops import FieldOps
    from sda_tpu.mesh.simpod import _build_matrices, _share_combine, _share_draws

    key = jax.random.PRNGKey(17)
    rng = np.random.default_rng(17)

    for scheme in (
        GOLDEN,                                    # generic int64 path
        PackedShamirSharing(                       # uint32 Solinas path
            3, 8, *numtheory.generate_packed_params(3, 8, 28)[0:1],
            *numtheory.generate_packed_params(3, 8, 28)[1:],
        ),
        AdditiveSharing(share_count=8, modulus=433),
        AdditiveSharing(share_count=3, modulus=536870233),  # uint32 path
    ):
        mod = getattr(scheme, "prime_modulus", getattr(scheme, "modulus", None))
        f = FieldOps.create(mod)
        M_host, _ = _build_matrices(scheme)
        masked = f.to_residues(rng.integers(0, mod, size=(5, 36)))
        drawn = _share_draws(scheme, f, masked.shape[0], masked.shape[1], key)
        fused = np.asarray(_share_combine(
            scheme, f, M_host, f.sum(masked, axis=0), drawn))
        if isinstance(scheme, PackedShamirSharing):
            if f.sp is not None:
                per = sharing.packed_share32(
                    key, masked, M_host, f.sp,
                    secret_count=scheme.secret_count,
                    privacy_threshold=scheme.privacy_threshold,
                )
            else:
                per = sharing.packed_share(
                    key, masked, jnp.asarray(M_host),
                    prime=scheme.prime_modulus,
                    secret_count=scheme.secret_count,
                    privacy_threshold=scheme.privacy_threshold,
                )
        else:
            per = sharing.additive_share(
                key, masked, share_count=scheme.share_count, modulus=mod
            )
        np.testing.assert_array_equal(
            fused, np.asarray(f.sum(per, axis=0)),
            err_msg=f"linearity fusion diverged for {type(scheme).__name__}",
        )


@needs_devices(8)
def test_pod_aggregate_fn_compiles_and_runs():
    """aggregate_fn: the raw jitted SPMD round exposed for benchmarking and
    compile checks must lower and execute on mesh-aligned shapes."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    mesh = make_mesh(8, 1)
    pod = SimulatedPod(GOLDEN, FullMasking(433), mesh=mesh)
    P_total, d_total = 16, 24
    fn = pod.aggregate_fn(P_total, d_total)
    rng = np.random.default_rng(3)
    x = rng.integers(0, 433, size=(P_total, d_total))
    dev = jax.device_put(
        jnp.asarray(x), NamedSharding(mesh, PartitionSpec("p", "d"))
    )
    out = np.asarray(fn(dev, jax.random.PRNGKey(4)))
    np.testing.assert_array_equal(out, x.sum(axis=0) % 433)


@needs_devices(8)
def test_multislice_mesh_pod_and_streamed_exact():
    """2 slices x 2 p-shards x 2 d-shards: the slice-major participant axis
    (make_multislice_mesh layout rule — d stays on intra-slice ICI, only the
    p-fold crosses the DCN boundary) is transparent to both pod modes."""
    from sda_tpu.mesh import StreamedPod, make_multislice_mesh

    mesh = make_multislice_mesh(2, 2, 2)
    assert mesh.devices.shape == (4, 2)
    assert mesh.axis_names == ("p", "d")
    # slice-contiguity: each slice's block holds consecutive devices
    flat = mesh.devices.reshape(2, 2, 2).reshape(2, -1)
    for slice_devs in flat:
        ids = sorted(d.id for d in slice_devs)
        assert ids == list(range(ids[0], ids[0] + 4))

    rng = np.random.default_rng(3)
    inputs = rng.integers(0, 50, size=(8, 24))
    pod = SimulatedPod(GOLDEN, masking_scheme=FullMasking(433), mesh=mesh)
    np.testing.assert_array_equal(
        np.asarray(pod.aggregate(inputs, key=jax.random.PRNGKey(0))),
        inputs.sum(axis=0) % 433,
    )

    streamed = StreamedPod(
        AdditiveSharing(share_count=8, modulus=433),
        ChaChaMasking(433, 24, 128),
        mesh=mesh,
        participants_chunk=4,
        dim_chunk=12,
    )
    np.testing.assert_array_equal(
        np.asarray(streamed.aggregate(inputs, key=jax.random.PRNGKey(1))),
        inputs.sum(axis=0) % 433,
    )


@pytest.mark.parametrize("n_devices,shapes", [
    (16, ((8, 2), (4, 4), (2, 8))),
    (32, ((8, 4), (4, 8))),
])
def test_wide_virtual_mesh_rounds_subprocess(n_devices, shapes):
    """16- and 32-device meshes (beyond the suite's 8 virtual devices):
    packed + BasicShamir quorum rounds on several (p, d) factorizations.
    Runs in a subprocess because the virtual device count is fixed at
    backend init (round-3 verdict #6: the 8x1 shape can't catch the
    divisibility/sharding bugs wider meshes and d-heavy shards can)."""
    import subprocess
    import sys
    import textwrap

    code = textwrap.dedent(f"""
        from sda_tpu.utils.backend import force_cpu
        force_cpu({n_devices})
        import jax
        import numpy as np
        from sda_tpu.mesh import SimulatedPod, make_mesh
        from sda_tpu.protocol import (BasicShamirSharing, FullMasking,
                                      PackedShamirSharing)

        scheme = PackedShamirSharing(3, 8, 4, 433, 354, 150)
        basic = BasicShamirSharing(share_count=8, privacy_threshold=3,
                                   prime_modulus=433)
        rng = np.random.default_rng(0)
        for ps, ds in {shapes!r}:
            mesh = make_mesh(ps, ds)
            dim = scheme.secret_count * ds * 4
            x = rng.integers(0, 50, size=(2 * ps + 1, dim))
            exp = x.sum(axis=0) % 433
            pod = SimulatedPod(scheme, masking_scheme=FullMasking(433),
                               mesh=mesh)
            np.testing.assert_array_equal(
                np.asarray(pod.aggregate(x, key=jax.random.PRNGKey(1))), exp)
            bpod = SimulatedPod(basic, masking_scheme=FullMasking(433),
                                mesh=mesh, surviving_clerks=(1, 3, 5, 7))
            np.testing.assert_array_equal(
                np.asarray(bpod.aggregate(x, key=jax.random.PRNGKey(2))), exp)
            print("OK", ps, ds, flush=True)
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=900,
                       env={**os.environ, "XLA_FLAGS": ""})
    assert r.returncode == 0, r.stderr[-2000:]
    for ps, ds in shapes:
        assert f"OK {ps} {ds}" in r.stdout
