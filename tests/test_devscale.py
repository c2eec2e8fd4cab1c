"""Model-scale device plane (mesh/devscale.py + loadgen/devscale.py).

The composition ROADMAP item 3 asked for, pinned piece by piece:

- the watermark tile rule derives grain-aligned widths that scale with
  the budget (no magic constants);
- the sharded scan round (ONE shard_map program streaming dim tiles via
  scan_dim_tiles) is bit-exact vs the plain column sum on the XLA lane
  AND the fused Pallas lane (interpret mode, external randomness) —
  which proves lane equality, since the aggregate is deterministic;
- the DeviceTileSink feeds the streamed pod device-resident tiles,
  bit-exact with the direct provider, prefetched in stream order;
- the DeviceTileCombiner matches crypto.sharing.mod_combine bit-for-bit
  (canonical and unreduced inputs) with one compiled fold shape;
- run_devscale emits the full BENCH record with the comparability tags
  the regression gate keys on.
"""

import jax
import numpy as np
import pytest

from util import external_bits

from sda_tpu import obs
from sda_tpu.crypto.sharing import mod_combine
from sda_tpu.fields import numtheory
from sda_tpu.mesh import (
    DeviceTileCombiner,
    DeviceTileSink,
    ModelScaleRound,
    StreamedPod,
    make_mesh,
    watermark_dim_tile,
)
from sda_tpu.mesh.devscale import bytes_per_dim_column, stream_schedule
from sda_tpu.mesh.streaming import synthetic_block_provider32
from sda_tpu.obs import devprof
from sda_tpu.protocol import (
    ChaChaMasking,
    FullMasking,
    NoMasking,
    PackedShamirSharing,
)
from sda_tpu.utils import metrics


@pytest.fixture(autouse=True)
def _fresh_obs():
    obs.reset_all()
    yield
    obs.reset_all()
    devprof.enable_cost_analysis(False)


def fast_scheme():
    t, p, w2, w3 = numtheory.generate_packed_params(3, 8, 28)
    return PackedShamirSharing(3, 8, t, p, w2, w3)


def needs_devices(n):
    return pytest.mark.skipif(
        len(jax.devices()) < n, reason=f"needs {n} virtual devices")


# -- the watermark tile rule --------------------------------------------------

def test_watermark_tile_scales_with_budget_and_stays_on_grain():
    s = fast_scheme()
    mask = FullMasking(s.prime_modulus)
    small = watermark_dim_tile(s, mask, participants_chunk=8, p_shards=4,
                               d_shards=2, watermark_bytes=1 << 20)
    big = watermark_dim_tile(s, mask, participants_chunk=8, p_shards=4,
                             d_shards=2, watermark_bytes=1 << 26)
    grain = 24 * 2  # lcm(k=3, 8 chacha words) x d_shards
    assert small % grain == 0 and big % grain == 0
    assert big > small, "a larger budget must afford a wider tile"
    # more resident participants per device -> narrower tiles
    crowded = watermark_dim_tile(s, mask, participants_chunk=64, p_shards=4,
                                 d_shards=2, watermark_bytes=1 << 20)
    assert crowded < small


def test_watermark_tile_clamps_to_dim_and_floor():
    s = fast_scheme()
    mask = FullMasking(s.prime_modulus)
    tiny_budget = watermark_dim_tile(
        s, mask, participants_chunk=8, p_shards=4, d_shards=2,
        watermark_bytes=1)
    assert tiny_budget == 24 * 2, "floor is one mesh grain"
    clamped = watermark_dim_tile(
        s, mask, participants_chunk=8, p_shards=4, d_shards=2,
        watermark_bytes=1 << 34, dim=1000)
    assert clamped == -(-1000 // 48) * 48


def test_bytes_per_dim_column_counts_masking():
    s = fast_scheme()
    masked = bytes_per_dim_column(s, FullMasking(s.prime_modulus), 8)
    unmasked = bytes_per_dim_column(s, NoMasking(), 8)
    assert masked > unmasked > 0


def test_hbm_watermark_env_override(monkeypatch):
    monkeypatch.setenv("SDA_HBM_WATERMARK", str(123456789))
    assert devprof.hbm_watermark() == 123456789
    monkeypatch.delenv("SDA_HBM_WATERMARK")
    default = devprof.hbm_watermark()
    assert 0 < default <= devprof.CPU_PLANNING_HBM_BYTES


def test_watermark_report_shape(monkeypatch):
    monkeypatch.setenv("SDA_HBM_WATERMARK", "1000")
    block = devprof.watermark_report(peak_bytes=800)
    assert block["within_watermark"] and block["hbm_watermark_ratio"] == 0.8
    over = devprof.watermark_report(peak_bytes=1500)
    assert not over["within_watermark"]


# -- the sharded scan round ---------------------------------------------------

@needs_devices(8)
@pytest.mark.parametrize("mesh_shape", [(4, 2), (8, 1)])
@pytest.mark.parametrize("masking", [
    "none", "full",
    # the device ChaCha expansion compiles are the expensive part of the
    # lattice: covered in the full CI pytest pass, not the tier-1 cut
    pytest.param("chacha", marks=pytest.mark.slow),
])
def test_model_scale_round_xla_lane_exact(mesh_shape, masking):
    s = fast_scheme()
    p = s.prime_modulus
    mask = {"none": None, "full": FullMasking(p),
            "chacha": ChaChaMasking(p, 250, 128)}[masking]
    r = ModelScaleRound(s, mask, mesh=make_mesh(*mesh_shape), dim_tile=96)
    rng = np.random.default_rng(1)
    # ragged: P off the p axis, dim off the tile grain AND the mesh grain
    x = rng.integers(0, 1 << 20, size=(13, 250), dtype=np.int64)
    out = np.asarray(r.aggregate(x, jax.random.PRNGKey(2)))
    np.testing.assert_array_equal(out, x.sum(axis=0) % p)


@needs_devices(8)
def test_model_scale_round_pallas_lane_exact_vs_xla():
    s = fast_scheme()
    p = s.prime_modulus
    key = jax.random.PRNGKey(5)
    rng = np.random.default_rng(3)
    x = rng.integers(0, 1 << 20, size=(16, 288), dtype=np.int64)
    kw = dict(mesh=make_mesh(4, 2), dim_tile=96)
    xla = ModelScaleRound(s, FullMasking(p), **kw)
    pl = ModelScaleRound(s, FullMasking(p), use_pallas=True,
                         pallas_interpret=True,
                         pallas_external_bits_fn=external_bits, **kw)
    assert pl.pallas_active and not xla.pallas_active
    out_x = np.asarray(xla.aggregate(x, key))
    out_p = np.asarray(pl.aggregate(x, key))
    expected = x.sum(axis=0) % p
    np.testing.assert_array_equal(out_x, expected)
    # the aggregate is deterministic, so XLA lane == Pallas lane bit-
    # for-bit whatever randomness each drew (masks cancel per tile,
    # random polynomial rows are annihilated by reconstruction)
    np.testing.assert_array_equal(out_p, out_x)


@needs_devices(8)
def test_model_scale_round_quorum_reveal():
    s = fast_scheme()
    p = s.prime_modulus
    survivors = tuple(range(s.reconstruction_threshold))
    r = ModelScaleRound(s, FullMasking(p), mesh=make_mesh(4, 2),
                        dim_tile=96, surviving_clerks=survivors)
    rng = np.random.default_rng(4)
    x = rng.integers(0, 1 << 20, size=(8, 192), dtype=np.int64)
    out = np.asarray(r.aggregate(x, jax.random.PRNGKey(6)))
    np.testing.assert_array_equal(out, x.sum(axis=0) % p)


@needs_devices(8)
def test_model_scale_round_watermark_default_tile():
    s = fast_scheme()
    r = ModelScaleRound(s, FullMasking(s.prime_modulus),
                        mesh=make_mesh(4, 2))
    assert r.dim_tile % r._grain == 0 and r.dim_tile > 0


# -- streamed pod: uniform tails ---------------------------------------------

@needs_devices(8)
def test_streamed_pod_uniform_tail_exact_and_single_step_shape():
    s = fast_scheme()
    p = s.prime_modulus
    pod = StreamedPod(s, FullMasking(p), mesh=make_mesh(4, 2),
                      participants_chunk=8, dim_chunk=96, uniform_tail=True)
    rng = np.random.default_rng(5)
    x = rng.integers(0, 1 << 20, size=(19, 250), dtype=np.int64)
    out = pod.aggregate(x, key=jax.random.PRNGKey(7))
    np.testing.assert_array_equal(np.asarray(out), x.sum(axis=0) % p)
    prof = devprof.profile("stream.pod.step")
    assert len(prof.shapes) == 1, prof.block_shapes()
    assert prof.retraces == 0


# -- the host -> device sink --------------------------------------------------

def test_stream_schedule_mirrors_drive_order():
    # 2 participant chunks x 3 uniform d tiles, drive order d-outer
    sched = stream_schedule(10, 250, 8, 96, 48, uniform_tail=True)
    assert sched[0] == (0, 8, 0, 96, 96)
    assert sched[1] == (8, 10, 0, 96, 96)
    assert sched[-1] == (8, 10, 192, 250, 96)  # uniform tail keeps width
    ragged = stream_schedule(10, 250, 8, 96, 48, uniform_tail=False)
    assert ragged[-1] == (8, 10, 192, 250, 96)  # grain-rounded 58 -> 96


@needs_devices(8)
def test_sink_fed_streamed_pod_bit_exact_and_prefetched():
    from jax.sharding import NamedSharding, PartitionSpec as P

    s = fast_scheme()
    p = s.prime_modulus
    key = jax.random.PRNGKey(11)
    host = synthetic_block_provider32(p, seed=9)

    def make_pod():
        return StreamedPod(s, FullMasking(p), mesh=make_mesh(4, 2),
                           participants_chunk=8, dim_chunk=96,
                           uniform_tail=True)

    pod = make_pod()
    sink = DeviceTileSink(host, 20, 250, pod.participants_chunk,
                          pod.dim_chunk, grain=pod._grain, uniform_tail=True,
                          sharding=NamedSharding(pod.mesh, P("p", "d")))
    out_sink = pod.aggregate_blocks(sink.provider(), 20, 250, key)
    out_direct = make_pod().aggregate_blocks(host, 20, 250, key)
    np.testing.assert_array_equal(out_sink, out_direct)
    counters = metrics.counter_report("devscale.sink.")
    assert counters.get("devscale.sink.hit", 0) == 9  # 3 p-chunks x 3 tiles
    assert counters.get("devscale.sink.miss", 0) == 0


def test_sink_out_of_order_request_degrades_to_direct_decode():
    host = synthetic_block_provider32(433, seed=1)
    sink = DeviceTileSink(host, 8, 96, 8, 48, grain=24, uniform_tail=True)
    get = sink.provider()
    # not the predicted first block: correct bytes, counted as a miss
    blk = np.asarray(get(0, 8, 48, 96))
    np.testing.assert_array_equal(blk, np.asarray(host(0, 8, 48, 96)))
    assert metrics.counter_report("devscale.sink.").get(
        "devscale.sink.miss") == 1


# -- the device tile combiner -------------------------------------------------

def test_device_tile_combiner_matches_mod_combine():
    p = fast_scheme().prime_modulus
    rng = np.random.default_rng(13)
    vecs = [rng.integers(0, p, size=1000).astype(np.int64)
            for _ in range(9)]
    c = DeviceTileCombiner(p, dim_tile=192)  # 1000 = 5x192 + tail 40
    c.fold(np.stack(vecs[:4]))
    c.fold(np.stack(vecs[4:8]))
    c.fold(vecs[8])  # single-vector bundle
    np.testing.assert_array_equal(c.result(), mod_combine(vecs, p))
    prof = devprof.profile("devscale.clerk_combine")
    # one compiled fold shape per bundle-rows value (4-row and 1-row)
    assert prof.retraces <= 1 and len(prof.shapes) <= 2


def test_device_tile_combiner_unreduced_inputs():
    # Paillier-premixed clerk batches decrypt to UNREDUCED sums: the
    # device fold must canonicalize exactly like mod_combine
    p = 433
    rng = np.random.default_rng(14)
    vecs = [rng.integers(0, 10 * p, size=50).astype(np.int64)
            for _ in range(3)]
    c = DeviceTileCombiner(p, dim_tile=32)
    for v in vecs:
        c.fold(v)
    np.testing.assert_array_equal(c.result(), mod_combine(vecs, p))


def test_device_tile_combiner_empty_and_dim_guard():
    c = DeviceTileCombiner(433)
    assert c.result().size == 0 and c.folded == 0
    c.fold(np.ones((2, 10), dtype=np.int64))
    with pytest.raises(ValueError, match="bundle dim"):
        c.fold(np.ones((2, 11), dtype=np.int64))


def test_device_tile_combiner_watermark_sized_tile(monkeypatch):
    monkeypatch.setenv("SDA_HBM_WATERMARK", str(1 << 20))
    c = DeviceTileCombiner(fast_scheme().prime_modulus)
    c.fold(np.ones((4, 100_000), dtype=np.int64))
    assert c._dim_tile is not None and 128 <= c._dim_tile
    assert c._plan_t.n_tiles >= 1
    np.testing.assert_array_equal(
        c.result(), np.full(100_000, 4, dtype=np.int64))


# -- the benched configuration ------------------------------------------------

@needs_devices(8)
@pytest.mark.slow  # ci.sh runs the same path every CI as the devscale drill
def test_run_devscale_record_smoke():
    from sda_tpu.loadgen import DevScaleProfile, run_devscale

    record = run_devscale(DevScaleProfile(
        dim=25_000, participants=8, participants_chunk=8,
        p_shards=4, d_shards=2, rounds=3, seed=20260804))
    assert record["ok"] and record["exact"]
    assert record["retraces"] == 0 and record["warm_program_reused"]
    assert record["tile_rule"] == "hbm_watermark"
    assert record["dim_tile"] % 48 == 0
    assert record["clerk_fed"]["exact"]
    assert record["clerk_fed"]["sink_misses"] == 0
    assert record["scan_lane"]["exact"]
    assert record["hbm"]["within_watermark"]
    assert record["value"] > 0
    # the comparability tags the regression gate keys on
    for tag in ("dim", "p_shards", "d_shards", "pallas", "platform"):
        assert tag in record, tag
    # counts hold on any backend; a roofline needs a device the peak
    # table knows, which the CPU is not
    assert record["cost"]["flops"] > 0
    assert "roofline" not in record
    assert record["platform"] == "cpu" and record["device_count"] == 8
    assert record["compiled_shapes"] == {"stream.pod.step": 1,
                                         "stream.pod.finale": 1}


@needs_devices(8)
@pytest.mark.slow  # the ci.sh devscale drill runs the pallas lane fixed-seed
def test_run_devscale_pallas_interpret_lane():
    from sda_tpu.loadgen import DevScaleProfile, run_devscale

    record = run_devscale(DevScaleProfile(
        dim=4_800, participants=8, participants_chunk=8,
        p_shards=4, d_shards=2, rounds=2, pallas=True,
        pallas_interpret=True, clerk_fed=False, seed=1))
    assert record["ok"] and record["exact"] and record["pallas"]
    assert record["scan_lane"]["exact"]


def test_flagship_dims_pinned():
    from sda_tpu.fl import FLAGSHIP_FAMILIES, flagship_dim, flagship_dims

    dims = flagship_dims()
    assert set(FLAGSHIP_FAMILIES) <= set(dims)
    assert dims["mobilelite"] == 3_731_890   # MobileLite default config
    assert dims["lora"] == 11_782_400        # LoRAMLP adapter sub-tree
    assert dims["devscale"] == 100_000_000   # the ROADMAP model-scale rung
    with pytest.raises(ValueError, match="unknown flagship family"):
        flagship_dim("resnet")
