"""The pod's round timed where the work happens (mesh/simpod.py):
``mesh.round`` split into ``pod.feed`` / ``pod.dispatch`` / ``pod.wait``
with ``pod.pad`` before and ``pod.strip`` after it, byte counters at the
same boundaries, the residue pass and the kernel's relayout named on the
device side, and ``timed_phase`` as one span that also feeds the phase
registry. Toy sizes on the CPU; the Pallas step is interpreted, or
lowered for the TPU where only the program's text is read."""

import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sda_tpu import obs
from sda_tpu.fields import numtheory
from sda_tpu.fields.ops import FieldOps
from sda_tpu.mesh import SimulatedPod
from sda_tpu.obs import devprof
from sda_tpu.protocol import FullMasking, PackedShamirSharing
from sda_tpu.utils import metrics, phase_report, timed_phase

from util import external_bits

CHILDREN = ("pod.feed", "pod.dispatch", "pod.wait")
STEPS = ("xla", "pallas")


def _pod(step: str, interpret: bool = True) -> SimulatedPod:
    t, p, w2, w3 = numtheory.generate_packed_params(3, 8, 28)
    scheme = PackedShamirSharing(3, 8, t, p, w2, w3)
    pallas = dict(use_pallas=True)
    if interpret:
        pallas.update(pallas_interpret=True,
                      pallas_external_bits_fn=external_bits)
    return SimulatedPod(scheme, FullMasking(p),
                        **(pallas if step == "pallas" else {}))


@pytest.fixture(scope="module", params=[
    (step, shape) for step in STEPS for shape in ("unpadded", "padded")],
    ids=lambda param: "-".join(param))
def one_round(request):
    """One warm ``aggregate()`` of each step at a shape on the grain and
    at one off it: its spans, counters, phase stats and result."""
    step, shape = request.param
    pod = _pod(step)
    rng = np.random.default_rng(7)
    rows, dim = pod.padded_shape(1, 1)
    rows, dim = (2 * rows, 16 * dim) if shape == "unpadded" \
        else (2 * rows - 1, 16 * dim - 1)
    inputs = rng.integers(0, 1 << 20, size=(rows, dim), dtype=np.int64)
    pod.aggregate(inputs)  # compiles; the round looked at is warm
    obs.reset_all()
    out = np.asarray(pod.aggregate(inputs, jax.random.PRNGKey(3)))
    return {
        "padded": shape == "padded", "inputs": inputs, "out": out,
        "padded_shape": pod.padded_shape(rows, dim),
        "modulus": pod.modulus,
        "spans": obs.finished_spans(),
        "counters": metrics.counter_report("mesh."),
        "phases": phase_report(),
    }


def _by_name(spans) -> dict:
    names = [s.name for s in spans]
    assert len(names) == len(set(names)), names
    return {s.name: s for s in spans}


def test_aggregate_yields_the_tables_spans(one_round):
    spans = _by_name(one_round["spans"])
    expected = {"mesh.round", "pod.strip", *CHILDREN}
    if one_round["padded"]:
        expected.add("pod.pad")  # present only when a pad happens
    assert set(spans) == expected
    # one trace; pad, round and strip are siblings, the rest the round's
    assert len({s.trace_id for s in spans.values()}) == 1
    round_ = spans["mesh.round"]
    assert round_.parent_id is None and spans["pod.strip"].parent_id is None
    if one_round["padded"]:
        assert spans["pod.pad"].parent_id is None
        pad = spans["pod.pad"]
        assert pad.start_mono + pad.duration_s <= round_.start_mono
    for name in CHILDREN:
        assert spans[name].parent_id == round_.span_id
    # children in order inside the round's interval, summing to no more
    feed, dispatch, wait = (spans[name] for name in CHILDREN)
    assert round_.start_mono <= feed.start_mono
    assert feed.start_mono + feed.duration_s <= dispatch.start_mono
    assert dispatch.start_mono + dispatch.duration_s <= wait.start_mono
    assert wait.start_mono + wait.duration_s \
        <= round_.start_mono + round_.duration_s
    assert sum(spans[name].duration_s for name in CHILDREN) \
        <= round_.duration_s
    assert spans["pod.strip"].start_mono \
        >= round_.start_mono + round_.duration_s
    # mesh.round is still the phase it was, with the span's own seconds
    assert one_round["phases"]["mesh.round"]["total_s"] == round_.duration_s


def test_feed_span_and_counters_are_exact(one_round):
    rows, dim = one_round["padded_shape"]
    nbytes = rows * dim * 8
    feed = _by_name(one_round["spans"])["pod.feed"]
    assert feed.attributes == {"bytes": nbytes, "dtype": "int64",
                               "shape": [rows, dim]}
    assert one_round["counters"] == {
        "mesh.feed.calls": 1, "mesh.feed.bytes": nbytes,
        "mesh.feed.pad_bytes": nbytes if one_round["padded"] else 0}


def test_aggregate_is_bit_exact_as_before(one_round):
    inputs = one_round["inputs"]
    assert one_round["out"].shape == (inputs.shape[1],)
    np.testing.assert_array_equal(
        one_round["out"], inputs.sum(axis=0) % one_round["modulus"])


def test_spans_nest_under_the_callers_span():
    pod = _pod("xla")
    inputs = np.arange(7 * 47, dtype=np.int64).reshape(7, 47)
    pod.aggregate(inputs)
    obs.reset_all()
    with obs.span("caller") as caller:
        pod.aggregate(inputs)
    spans = _by_name(obs.finished_spans())
    for name in ("pod.pad", "mesh.round", "pod.strip"):
        assert spans[name].parent_id == caller.span_id
    assert {s.trace_id for s in spans.values()} == {caller.trace_id}


@pytest.mark.parametrize("step", STEPS)
def test_aggregate_fn_callable_opens_one_dispatch_per_call(step):
    pod = _pod(step)
    rows, dim = pod.padded_shape(8, 48)
    fn = pod.aggregate_fn(rows, dim)
    # the AOT and jit-cache surface stays forwarded
    assert all(hasattr(fn, attr)
               for attr in ("lower", "trace", "eval_shape", "_cache_size"))
    inputs = jnp.ones((rows, dim), jnp.uint32)
    obs.reset_all()
    for calls in (1, 2):
        fn(inputs, jax.random.PRNGKey(calls)).block_until_ready()
        spans = [s for s in obs.finished_spans() if s.name.startswith(
            ("pod.", "mesh."))]
        assert [s.name for s in spans] == ["pod.dispatch"] * calls
    # a root each: no program span is open around a resident round
    assert all(s.parent_id is None for s in spans)
    assert spans[0].trace_id != spans[1].trace_id
    assert fn._cache_size() == 1
    assert metrics.counter_report("mesh.") == {}  # nothing was fed


@pytest.mark.parametrize("step", STEPS)
def test_lowered_step_names_the_device_stages(step):
    pod = _pod(step)
    rows, dim = pod.padded_shape(8, 48)
    text = pod.aggregate_fn(rows, dim).lower(
        jax.ShapeDtypeStruct((rows, dim), jnp.int64),
        jax.ShapeDtypeStruct((2,), jnp.uint32)).as_text(debug_info=True)
    assert "sda.residues" in text
    # both steps fold the rows on their native layout before anything else
    # (the XLA step a scan block at a time, since PR 40); the relayout in
    # front of the kernel is the kernel's: the XLA step has none
    assert "sda.fold" in text
    assert ("sda.relayout" in text) == (step == "pallas")
    assert ("sda.mask_share" in text) == (step == "pallas")


@pytest.mark.parametrize("cohort", ["resident", "host"])
def test_fedavg_round_is_one_phase_with_its_counters(cohort):
    """``pod_fedavg_round``: one ``fedavg.round`` phase around the work
    (attributes ``participants``, ``dimension``, ``resident``), and the
    counters ``models.fedavg.{rounds,host_bytes}``. Resident, its one
    child is the program's ``pod.dispatch`` and no byte is counted; from
    the host, ``aggregate()``'s spans stand under it and the deltas up
    and the aggregate down are counted."""
    from sda_tpu.models import FixedPointCodec, pod_fedavg_round

    pod = _pod("xla")
    rows, dim = pod.padded_shape(8, 48)
    codec = FixedPointCodec(pod.modulus, 16, max_summands=rows, clip=2.0)
    rng = np.random.default_rng(11)
    global_vec = rng.uniform(-1, 1, size=dim).astype(np.float32)
    clients = global_vec + rng.normal(size=(rows, dim)).astype(np.float32)
    if cohort == "resident":
        global_vec, clients = jnp.asarray(global_vec), jnp.asarray(clients)
    key = jax.random.PRNGKey(5)
    pod_fedavg_round(pod, codec, global_vec, clients, key)  # compiles
    obs.reset_all()
    jax.block_until_ready(
        pod_fedavg_round(pod, codec, global_vec, clients, key))
    spans = _by_name(obs.finished_spans())
    round_ = spans["fedavg.round"]
    assert round_.parent_id is None
    assert round_.attributes == {"participants": rows, "dimension": dim,
                                 "resident": cohort == "resident"}
    assert phase_report()["fedavg.round"]["total_s"] == round_.duration_s
    moved = 0 if cohort == "resident" else rows * dim * 4 + dim * 8
    assert metrics.counter_report("models.fedavg.") == {
        "models.fedavg.rounds": 1, "models.fedavg.host_bytes": moved}
    if cohort == "resident":
        assert set(spans) == {"fedavg.round", "pod.dispatch"}
        assert spans["pod.dispatch"].parent_id == round_.span_id
        assert metrics.counter_report("mesh.") == {}  # nothing was fed
        assert devprof.report()["models.fedavg.round"]["calls"] == 1
    else:
        assert set(spans) == {"fedavg.round", "mesh.round", "pod.strip",
                              *CHILDREN}
        assert spans["mesh.round"].parent_id == round_.span_id
        assert {s.trace_id for s in spans.values()} == {round_.trace_id}


def _tensor_sizes(line: str) -> list:
    """Element counts of the ranked tensor types on one line of MLIR."""
    return [math.prod(int(n) for n in dims[:-1].split("x"))
            for dims in re.findall(r"tensor<((?:\d+x)+)[a-z]+\d+>", line)]


@pytest.mark.parametrize("dim", [120, 384], ids=["tile-padded", "on-tile"])
def test_lowered_pallas_step_folds_before_the_layout_change(dim):
    """The participants fold on the input's native layout; the
    column-per-batch relayout and the pad to the kernel's column tile run
    on the folded [k, B] block, never on [S, k, B]. The Pallas step is
    the real one (on-core PRNG), lowered for the TPU from here: the
    kernel is the ``tpu_custom_call`` and its internals stay out of the
    text."""
    pod = _pod("pallas", interpret=False)
    rows = 5 * pod.mesh.devices.shape[0]            # 5 rows per device
    assert pod.padded_shape(rows, dim) == (rows, dim)
    text = pod.aggregate_fn(rows, dim).trace(
        jax.ShapeDtypeStruct((rows, dim), jnp.int64),
        jax.ShapeDtypeStruct((2,), jnp.uint32),
    ).lower(lowering_platforms=("tpu",)).as_text(debug_info=True)
    k, columns = pod.scheme.secret_count, dim // pod.scheme.secret_count
    padded = -(-columns // 128) * 128
    # nothing per participant is laid out: no [5, k, ...] tensor at all,
    # and the kernel takes the folded secrets [k, B_pad]
    assert f"tensor<5x{k}x" not in text
    (kernel,) = [line for line in text.splitlines()
                 if "@tpu_custom_call" in line]
    operands = kernel[kernel.rindex(" : ("):kernel.rindex(") -> ")]
    assert f"tensor<{k}x{padded}xui32>" in operands
    assert max(_tensor_sizes(operands)) == k * padded
    # one reduce folds the native [5, dim] block under sda.fold ...
    assert re.search(r'= loc\("sda\.fold/reduce"', text)
    assert f"across dimensions = [0] : (tensor<5x{dim}xui32>, " \
        f"tensor<ui32>) -> tensor<{dim}xui32>" in text
    # ... and the relayout touches nothing larger than the padded [k, B]
    # block, which is smaller than the 5 rows, but the one-hot of its
    # matmuls (fields/layout.py): [k, 128, 128 k], whatever the width
    onehot = k * 128 * 128 * k
    relayout = set(re.findall(r'(#loc\d+) = loc\("sda\.relayout/', text))
    sizes = {size for line in text.splitlines()
             if (at := re.search(r"loc\((#loc\d+)\)$", line))
             and at.group(1) in relayout
             for size in _tensor_sizes(line)}
    assert onehot in sizes
    assert 0 < max(sizes - {onehot}) <= k * padded < 5 * dim


def test_residue_pass_is_named_on_the_int64_path_too():
    field = FieldOps.create(433)  # not Solinas: generic int64 arithmetic
    assert field.sp is None
    text = jax.jit(field.to_residues).lower(
        jax.ShapeDtypeStruct((4, 6), jnp.int64)).as_text(debug_info=True)
    assert "sda.residues" in text


def test_no_other_instrumented_function_gains_a_span():
    plain = devprof.instrument("unit.plain", jax.jit(lambda x: x + 1))
    spanned = devprof.instrument("unit.spanned", jax.jit(lambda x: x + 1),
                                 span="unit.dispatch")
    obs.reset_all()
    plain(jnp.zeros(3))
    assert obs.finished_spans() == []
    spanned(jnp.zeros(3))
    assert [s.name for s in obs.finished_spans()] == ["unit.dispatch"]
    # called inside an outer trace it dispatches nothing: no span
    obs.reset_all()
    jax.jit(lambda x: spanned(x) * 2)(jnp.zeros(3))
    assert obs.finished_spans() == []


@pytest.mark.parametrize("raises", [False, True], ids=["ok", "raises"])
def test_timed_phase_is_one_span_with_the_phases_seconds(raises):
    obs.reset_all()
    try:
        with timed_phase("unit.phase") as span:
            assert obs.current_span() is span
            if raises:
                raise RuntimeError("boom")
    except RuntimeError:
        pass
    (finished,) = obs.finished_spans()
    assert finished is span and span.name == "unit.phase"
    assert span.status == ("error" if raises else "ok")
    stat = phase_report()["unit.phase"]
    assert stat["count"] == 1
    assert stat["total_s"] == span.duration_s  # one clock pair, one number


def test_sibling_context_joins_spans_no_span_encloses():
    """With no span open it is a fresh trace id and no span id: spans
    opened under it are roots of ONE trace. Inside a span it is that
    span's context, the default nesting."""
    obs.reset_all()
    trace = obs.sibling_context()
    assert trace.span_id is None and obs.current_context() is None
    for name in ("first", "second"):
        with obs.span(name, parent=trace):
            pass
    first, second = obs.finished_spans()
    assert first.trace_id == second.trace_id == trace.trace_id
    assert first.parent_id is None and second.parent_id is None
    assert obs.sibling_context().trace_id != trace.trace_id
    with obs.span("caller") as caller:
        assert obs.sibling_context() == caller.context
