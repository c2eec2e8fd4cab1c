"""Device perf plane: compile/retrace telemetry, roofline cost analysis,
the /statusz debug endpoint, and the compile-count tripwires.

The tripwires guard two pinned claims:

- ``mesh/streaming.py``: "at most two compiled shapes per axis" (full
  chunk + remainder) — the compile-cache survival lever next to
  ``tests/test_compile_cache.py``'s persistent-cache contract;
- a repeated ``SimulatedPod.aggregate`` with identical shapes triggers
  ZERO retraces, while a forced shape change mid-run emits an
  ``xla.retrace`` span event into the exported trace.
"""

import numpy as np
import pytest
import requests

from sda_tpu import obs
from sda_tpu.fields import numtheory
from sda_tpu.http import SdaHttpServer
from sda_tpu.mesh import SimulatedPod, StreamingAggregator
from sda_tpu.obs import devprof
from sda_tpu.protocol import FullMasking, PackedShamirSharing
from sda_tpu.server import new_memory_server
from sda_tpu.utils import metrics


@pytest.fixture(autouse=True)
def _fresh_obs():
    obs.reset_all()
    yield
    obs.reset_all()
    devprof.enable_cost_analysis(False)


def _scheme():
    t, p, w2, w3 = numtheory.generate_packed_params(3, 8, 28)
    return PackedShamirSharing(3, 8, t, p, w2, w3), p


# -- compile-count tripwires -------------------------------------------------

def test_simpod_identical_shapes_zero_retraces():
    scheme, p = _scheme()
    pod = SimulatedPod(scheme, FullMasking(p))
    rng = np.random.default_rng(0)
    x = rng.integers(0, 1 << 20, size=(8, 48), dtype=np.int64)
    out = None
    for _ in range(3):
        out = pod.aggregate(x)
    assert (np.asarray(out).astype(object)
            == x.astype(object).sum(axis=0) % p).all()
    prof = devprof.profile("mesh.simpod.round")
    assert prof.calls == 3
    assert prof.compiles == 1, "identical shapes must reuse the compile"
    assert prof.retraces == 0
    assert len(prof.shapes) == 1
    assert metrics.counter_report("xla.compile.retrace") == {}


def test_simpod_shape_change_midrun_emits_retrace_span_event():
    scheme, p = _scheme()
    pod = SimulatedPod(scheme, FullMasking(p))
    rng = np.random.default_rng(0)
    pod.aggregate(rng.integers(0, 99, size=(8, 48), dtype=np.int64))
    # forcing a shape change mid-run: the next dispatch pays a retrace
    pod.aggregate(rng.integers(0, 99, size=(8, 96), dtype=np.int64))
    prof = devprof.profile("mesh.simpod.round")
    assert prof.compiles == 2 and prof.retraces == 1
    counters = metrics.counter_report("xla.compile.retrace")
    assert counters.get("xla.compile.retrace") == 1
    assert counters.get("xla.compile.retrace.mesh.simpod.round") == 1
    # ... and the retrace is attributed in the exported trace: it lands on
    # the pod.dispatch span that paid it, whose parent is a mesh.round span
    trace = obs.chrome_trace()
    instants = [e for e in trace["traceEvents"]
                if e.get("ph") == "i" and e["name"] == "xla.retrace"]
    assert len(instants) == 1
    assert instants[0]["args"]["function"] == "mesh.simpod.round"
    spans = {e["args"]["span_id"]: e for e in trace["traceEvents"]
             if e.get("ph") == "X"}
    dispatch = spans[instants[0]["args"]["span_id"]]
    assert dispatch["name"] == "pod.dispatch"
    assert spans[dispatch["args"]["parent_id"]]["name"] == "mesh.round"


def test_streaming_at_most_two_compiled_shapes_per_axis():
    scheme, p = _scheme()
    agg = StreamingAggregator(scheme, FullMasking(p),
                              participants_chunk=4, dim_chunk=24)
    rng = np.random.default_rng(1)
    # ragged on BOTH axes: 10 = 2x4 + 2 participants, 60 = 2x24 + 12 dims
    x = rng.integers(0, 1 << 10, size=(10, 60), dtype=np.int64)
    out = agg.aggregate(x)
    assert (np.asarray(out).astype(object)
            == x.astype(object).sum(axis=0) % p).all()
    steps = devprof.profile("stream.step").block_shapes()
    assert steps, "stream.step never profiled"
    p_shapes = {s[0] for s in steps}
    d_shapes = {s[1] for s in steps}
    assert len(p_shapes) <= 2, f"participant-axis shapes {p_shapes}"
    assert len(d_shapes) <= 2, f"dim-axis shapes {d_shapes}"
    finales = devprof.profile("stream.finale").block_shapes()
    assert len({s[-1] for s in finales}) <= 2


def test_model_scale_rounds_one_shape_per_stage_zero_retraces():
    """The sharded+streamed model-scale path (mesh/devscale.py drives
    StreamedPod with uniform tails): repeated same-shape rounds must
    register at most ONE compiled shape per stage, and a TILE-COUNT
    change (a different dim at the same tile width) must reuse the
    per-tile step program — only the per-dim-size finale may add a
    shape."""
    import jax

    from sda_tpu.mesh import StreamedPod, make_mesh

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    scheme, p = _scheme()
    pod = StreamedPod(scheme, FullMasking(p), mesh=make_mesh(4, 2),
                      participants_chunk=8, dim_chunk=96, uniform_tail=True)
    rng = np.random.default_rng(7)
    x = rng.integers(0, 1 << 10, size=(16, 250), dtype=np.int64)
    for _ in range(3):  # 3 rounds, 3 tiles each: same shapes throughout
        out = pod.aggregate(x, key=jax.random.PRNGKey(1))
    assert (np.asarray(out) == x.sum(axis=0) % p).all()
    step = devprof.profile("stream.pod.step")
    finale = devprof.profile("stream.pod.finale")
    assert len(step.shapes) == 1, step.block_shapes()
    assert len(finale.shapes) == 1
    assert step.retraces == 0 and finale.retraces == 0
    step_compiles = step.compiles
    # 5 tiles instead of 3: the per-tile program must NOT retrace
    x2 = rng.integers(0, 1 << 10, size=(16, 460), dtype=np.int64)
    out2 = pod.aggregate(x2, key=jax.random.PRNGKey(2))
    assert (np.asarray(out2) == x2.sum(axis=0) % p).all()
    step = devprof.profile("stream.pod.step")
    assert len(step.shapes) == 1, \
        f"tile-count change retraced the per-tile program: " \
        f"{step.block_shapes()}"
    assert step.compiles == step_compiles and step.retraces == 0
    assert metrics.counter_report("xla.compile.retrace") == {}


def test_streaming_uniform_tail_single_step_shape():
    scheme, p = _scheme()
    agg = StreamingAggregator(scheme, FullMasking(p), participants_chunk=4,
                              dim_chunk=24, uniform_tail=True)
    rng = np.random.default_rng(2)
    x = rng.integers(0, 1 << 10, size=(10, 60), dtype=np.int64)
    out = agg.aggregate(x)
    assert (np.asarray(out).astype(object)
            == x.astype(object).sum(axis=0) % p).all()
    prof = devprof.profile("stream.step")
    assert len(prof.shapes) == 1, prof.block_shapes()
    assert prof.compiles == 1 and prof.retraces == 0


# -- cost analysis / roofline ------------------------------------------------

def test_cost_analysis_feeds_cost_block_and_cpu_gets_no_roofline():
    devprof.enable_cost_analysis()
    scheme, p = _scheme()
    pod = SimulatedPod(scheme, FullMasking(p))
    rng = np.random.default_rng(3)
    pod.aggregate(rng.integers(0, 99, size=(8, 48), dtype=np.int64))
    block = devprof.cost_totals()
    assert block["flops"] > 0
    assert block["bytes"] > 0
    assert block["arithmetic_intensity"] > 0
    assert block["hbm_peak_bytes"] > 0
    assert "mesh.simpod.round" in block["phases"]
    # the CPU is not in the peak table: no roofline, not a made-up one
    assert devprof.roofline(seconds=0.25) is None
    # peak-HBM watermark gauges land in the metrics registry
    gauges = metrics.gauge_report("device.hbm.")
    assert gauges.get("device.hbm.peak_bytes", 0) > 0
    assert gauges.get("device.hbm.peak_bytes.mesh.simpod.round", 0) > 0


def test_roofline_reads_peaks_by_device_kind(monkeypatch):
    import jax

    class _Known:
        platform = "tpu"
        device_kind = "TPU unit"

    peaks = {"flops_per_s": 6.0e12, "flops_source": "unit test",
             "hbm_bytes_per_s": 819e9, "hbm_source": "unit test"}
    monkeypatch.setitem(devprof.CHIP_PEAKS, "TPU unit", peaks)
    prof = devprof.profile("unit.round")
    prof.shapes[("sig",)] = 2
    prof.costs[("sig",)] = {"flops": 3.0e9, "bytes_accessed": 1.0e9}
    monkeypatch.setattr(jax, "devices", lambda *a: [_Known()])
    block = devprof.roofline(seconds=1.0)
    assert block["device_kind"] == "TPU unit"
    assert block["peaks"] is peaks
    assert block["bound"] == "memory"  # AI 3 x 819e9 B/s < the compute peak
    assert block["utilization"] == pytest.approx(
        6.0e9 / (3.0 * 819e9), rel=1e-3)

    class _Unknown(_Known):
        device_kind = "TPU v99"

    monkeypatch.setattr(jax, "devices", lambda *a: [_Unknown()])
    assert devprof.roofline(seconds=1.0) is None


def test_v5e_row_is_sourced_only_and_yields_no_roofline(monkeypatch):
    # no published int32 VPU peak exists: the row carries the sourced HBM
    # figure alone, and a row without both peaks emits no utilization
    import jax

    class _V5e:
        platform = "tpu"
        device_kind = "TPU v5 lite"

    row = devprof.CHIP_PEAKS["TPU v5 lite"]
    assert "flops_per_s" not in row
    assert row["hbm_source"]
    prof = devprof.profile("unit.round")
    prof.shapes[("sig",)] = 1
    prof.costs[("sig",)] = {"flops": 3.0e9, "bytes_accessed": 1.0e9}
    monkeypatch.setattr(jax, "devices", lambda *a: [_V5e()])
    assert devprof.roofline(seconds=1.0) is None


def test_cost_analysis_off_by_default_keeps_single_compile(monkeypatch):
    monkeypatch.delenv("SDA_DEVPROF_COST", raising=False)
    assert not devprof.cost_analysis_enabled()
    scheme, p = _scheme()
    pod = SimulatedPod(scheme, FullMasking(p))
    pod.aggregate(np.ones((8, 48), dtype=np.int64))
    prof = devprof.profile("mesh.simpod.round")
    assert prof.costs == {}, "cost analysis must stay an entry-point opt-in"


def test_roofline_block_math():
    # AI = 10 flops/byte; attainable capped by compute peak; 50% achieved
    peaks = {"flops_per_s": 2000.0, "hbm_bytes_per_s": 1000.0}
    block = devprof.roofline_block(1000.0, 100.0, peaks, seconds=1.0)
    assert block["arithmetic_intensity"] == 10.0
    assert block["attainable_flops_per_s"] == 2000.0
    assert block["bound"] == "compute"
    assert block["utilization"] == pytest.approx(0.5)


def test_reset_all_clears_devprof_state():
    devprof.profile("unit.fn").calls = 5
    metrics.count("xla.compile.retrace")
    obs.reset_all()
    assert devprof.report() == {}
    assert metrics.counter_report("xla.") == {}


def test_wrappers_built_before_reset_keep_reporting():
    # module-level instrumented functions (fields/sharing.py) are wrapped
    # at import, long before any obs.reset_all(); stats from calls AFTER
    # a reset must land in the fresh registry, not an orphaned profile
    import jax.numpy as jnp

    from sda_tpu.fields import sharing

    obs.reset_all()
    sharing.combine(jnp.ones((3, 8), jnp.int64), modulus=97)
    prof = devprof.profile("fields.combine")
    assert prof.calls == 1
    assert "fields.combine" in devprof.report()


def test_eager_function_never_counts_compiles():
    # a non-jit callable wrapped for call counting must not fabricate
    # "compiles"/"retraces" per new argument shape
    eager = devprof.instrument("unit.eager", lambda x: x * 2)
    assert eager(np.ones((2,))) is not None
    assert eager(np.ones((4,))) is not None
    prof = devprof.profile("unit.eager")
    assert prof.calls == 2 and len(prof.shapes) == 2
    assert prof.compiles == 0 and prof.retraces == 0
    assert metrics.counter_report("xla.compile.retrace") == {}


def test_instrument_passes_through_inside_outer_trace():
    import jax
    import jax.numpy as jnp

    inner = devprof.instrument("unit.inner", jax.jit(lambda v: v * 2))

    @jax.jit
    def outer(v):
        return inner(v) + 1

    out = outer(jnp.arange(4))
    assert list(np.asarray(out)) == [1, 3, 5, 7]
    # the traced call must not count as a device dispatch
    assert devprof.profile("unit.inner").calls == 0
    assert devprof.profile("unit.inner").compiles == 0


# -- /statusz ----------------------------------------------------------------

def test_statusz_off_by_default_and_reports_when_enabled():
    srv = SdaHttpServer(new_memory_server(),
                        bind="127.0.0.1:0").start_background()
    try:
        assert requests.get(srv.address + "/statusz").status_code == 404
    finally:
        srv.shutdown()
    srv = SdaHttpServer(new_memory_server(), bind="127.0.0.1:0",
                        statusz_endpoint=True).start_background()
    try:
        requests.get(srv.address + "/v1/ping")
        r = requests.get(srv.address + "/statusz")
        assert r.status_code == 200
        payload = r.json()
        assert payload["uptime_s"] >= 0
        assert payload["store"] == "memory"
        assert "inflight" in payload and "inflight_peak" in payload
        assert payload["lease"]["lease_seconds"] is None
        assert "functions" in payload["devprof"]
        assert "cache" in payload["devprof"]
    finally:
        srv.shutdown()
