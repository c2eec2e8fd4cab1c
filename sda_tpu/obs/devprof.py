"""Device observability plane: compile/retrace telemetry + cost analysis.

The span layer (``trace.py``) made the HOST side of a round observable;
this module does the same for the DEVICE side, where an unexpected XLA
retrace, a compile-cache miss, or a phase falling off the roofline used
to show up only as "the round got slower". Three instruments:

- **Compile/retrace telemetry** — ``instrument(name, fn)`` wraps a jitted
  callable with a compiled-shape registry: per-function call/compile
  counts, the set of distinct argument signatures (shapes + dtypes +
  static values), and a *retrace* detector. A retrace — a compile after
  the function already compiled once — increments ``xla.compile.retrace``
  and lands as an ``xla.retrace`` span event in the PR 3 trace, so the
  round timeline shows exactly which dispatch paid a mid-round compile.
  ``install_monitoring()`` additionally taps ``jax.monitoring`` for the
  process-wide ``xla.compile.backend`` counter, the ``xla.compile.seconds``
  histogram, and the persistent-cache ``xla.compile.cache.hit``/``.miss``
  counters (the cache ``utils/backend.py::arm_compile_cache`` places).

- **Cost analysis / roofline** — with ``enable_cost_analysis()`` on (an
  entry-point opt-in: one ahead-of-time lower+compile per new shape; the
  jit call that follows reused it on the chip — one backend compile per
  shape, CHANGES.md PR 22), every first-per-shape call also runs
  ``fn.lower(...).compile().cost_analysis()`` / ``memory_analysis()``,
  recording per-phase FLOPs, bytes accessed, and the executable's peak
  HBM footprint (``device.hbm.peak_bytes`` gauges). ``cost_totals()``
  folds those into the ``cost`` block (counts, valid on any backend);
  ``roofline()`` reads them against the peaks of the device the process
  runs on — :data:`CHIP_PEAKS`, keyed by ``device_kind`` — and returns
  None for a device the table has no complete, sourced row for (today:
  every device, the v5e included).

- **Device-lane attribution** — every device op of a round stands under
  one ``jax.named_scope`` of a closed list of stage scopes
  (docs/observability.md, "Stage scopes", holds the list and where each
  is opened), so XProf device lanes merged via
  ``obs.merge_chrome_traces`` attribute device time to protocol phases
  by name.

No ``jax`` import happens at module import time: the HTTP/loadgen
profiles use ``obs`` without JAX, and a bare import must stay free.
State resets through ``obs.reset_all()``.
"""

from __future__ import annotations

import functools
import os
import threading
from typing import Dict, Optional, Tuple

from ..utils import metrics
from . import trace as _trace

__all__ = [
    "CHIP_PEAKS",
    "FnProfile",
    "CPU_PLANNING_HBM_BYTES",
    "compile_totals",
    "cost_analysis_enabled",
    "cost_totals",
    "enable_cost_analysis",
    "hbm_peak_recorded",
    "hbm_watermark",
    "install_monitoring",
    "instrument",
    "profile",
    "report",
    "reset",
    "roofline",
    "roofline_block",
    "watermark_report",
]

#: Chip peaks for the roofline model, keyed by ``device_kind`` as JAX
#: reports it; every figure names its source. One table; a device that is
#: not in it, or whose row lacks a peak, gets no roofline block, never a
#: made-up one. ``TPU v5 lite`` has no ``flops_per_s``: the round is int32
#: VPU work, for which no published peak exists (the 6e12 ops/s of
#: benchmarks/ROOFLINE.md is an estimate), and XLA's byte count cannot
#: see inside the Pallas call — so no utilization is emitted on it until
#: a sourced or measured compute peak is entered here.
CHIP_PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "hbm_source": "Google Cloud documentation, 'TPU v5e'",
    },
}

#: HBM budget (bytes) for PLANNING a model-scale tile schedule on the CPU
#: backend, which has no device memory to ask: deliberately chip-sized
#: (a host-RAM-sized budget would let CI pick untiled widths no chip
#: could hold). A TPU is asked for its own ``bytes_limit`` instead.
CPU_PLANNING_HBM_BYTES = 1 << 30

#: fraction of the device budget the round may plan against — headroom
#: for the XLA allocator, collective scratch, and the framework itself
DEFAULT_WATERMARK_FRACTION = 0.8

_lock = threading.Lock()
_profiles: "Dict[str, FnProfile]" = {}
_cost_enabled = False
_monitoring_installed = False


class FnProfile:
    """Per-instrumented-function state: the compiled-shape registry plus
    call/compile/retrace tallies and (opt-in) cost-analysis entries.
    Mutated under the module lock."""

    __slots__ = ("name", "calls", "compiles", "retraces", "shapes", "costs")

    def __init__(self, name: str):
        self.name = name
        self.calls = 0
        self.compiles = 0
        self.retraces = 0
        #: signature -> call count; signature order == first-seen order
        self.shapes: Dict[Tuple, int] = {}
        #: signature -> {"flops", "bytes_accessed", "hbm_peak_bytes", ...}
        self.costs: Dict[Tuple, dict] = {}

    def block_shapes(self):
        """The leading array shape of each seen signature (tests use this
        to pin the "at most 2-3 compiled shapes per axis" claim)."""
        out = []
        for sig in self.shapes:
            for entry in sig:
                if entry[0] == "a":
                    out.append(entry[1])
                    break
        return out

    def totals(self) -> dict:
        """Cost totals across every call (per-signature cost x calls)."""
        flops = bytes_acc = 0.0
        hbm_peak = 0
        for sig, cost in self.costs.items():
            n = self.shapes.get(sig, 0)
            flops += n * float(cost.get("flops") or 0.0)
            bytes_acc += n * float(cost.get("bytes_accessed") or 0.0)
            hbm_peak = max(hbm_peak, int(cost.get("hbm_peak_bytes") or 0))
        return {"flops": flops, "bytes_accessed": bytes_acc,
                "hbm_peak_bytes": hbm_peak}

    def to_obj(self) -> dict:
        return {
            "calls": self.calls,
            "compiles": self.compiles,
            "retraces": self.retraces,
            "compiled_shapes": len(self.shapes),
            "block_shapes": [list(s) for s in self.block_shapes()],
        }


def _sig_entry(value, out) -> None:
    shape = getattr(value, "shape", None)
    dtype = getattr(value, "dtype", None)
    if shape is not None and dtype is not None:
        out.append(("a", tuple(shape), str(dtype)))
        return
    if isinstance(value, (tuple, list)):  # pytree containers, by structure
        out.append(("[", len(value)))
        for item in value:
            _sig_entry(item, out)
        return
    if isinstance(value, dict):
        out.append(("{", len(value)))
        for key in sorted(value, key=str):
            out.append(("k", str(key)))
            _sig_entry(value[key], out)
        return
    try:
        hash(value)
        out.append(("s", value))
    except TypeError:
        # unhashable non-container leaf: record the TYPE only — embedding
        # repr(value) would make every distinct VALUE a distinct
        # "compiled shape" (unbounded registry growth, one spurious AOT
        # cost-compile per call, parameter dumps in span events)
        out.append(("t", type(value).__name__))


def _signature(args, kwargs) -> Tuple:
    """Hashable trace signature of a call: array leaves by (shape, dtype)
    — pytree containers (tuples/lists/dicts, e.g. a trainer's params and
    optimizer state) are flattened structurally — and static values
    (scheme params etc.) by value. Mirrors what makes jax.jit retrace,
    which is the whole point of the registry."""
    entries = []
    items = list(enumerate(args)) + sorted(
        kwargs.items(), key=lambda kv: str(kv[0]))
    for _key, value in items:
        _sig_entry(value, entries)
    return tuple(entries)


def _is_traced(args, kwargs) -> bool:
    """True when the call happens INSIDE an outer trace (arguments are
    jax Tracers): the inner jit inlines into the enclosing program, so
    counting it as a device dispatch — or trying to lower it — would be
    wrong; only the named_scope annotation applies."""
    try:
        from jax.core import Tracer
    except Exception:
        try:  # newer jax moved the public alias
            from jax._src.core import Tracer
        except Exception:
            return False
    return any(isinstance(v, Tracer) for v in args) \
        or any(isinstance(v, Tracer) for v in kwargs.values())


def _cache_size(fn) -> Optional[int]:
    getter = getattr(fn, "_cache_size", None)
    if getter is None:
        return None
    try:
        return int(getter())
    except Exception:
        return None


def _normalize_cost(analysis) -> dict:
    """``Compiled.cost_analysis()`` returns a dict on current jax and a
    list of per-computation dicts on older releases; fold either into
    {"flops", "bytes_accessed"}."""
    out = {"flops": 0.0, "bytes_accessed": 0.0}
    if analysis is None:
        return out
    parts = analysis if isinstance(analysis, (list, tuple)) else [analysis]
    for part in parts:
        if not isinstance(part, dict):
            continue
        out["flops"] += float(part.get("flops") or 0.0)
        out["bytes_accessed"] += float(part.get("bytes accessed") or 0.0)
    return out


def _normalize_memory(stats) -> dict:
    """``Compiled.memory_analysis()`` -> byte-level footprint; the peak-HBM
    estimate is arguments + outputs + temps + generated code (the standard
    XLA live-set upper bound for one executable)."""
    if stats is None:
        return {}
    fields = {
        "argument_bytes": "argument_size_in_bytes",
        "output_bytes": "output_size_in_bytes",
        "temp_bytes": "temp_size_in_bytes",
        "generated_code_bytes": "generated_code_size_in_bytes",
        "alias_bytes": "alias_size_in_bytes",
    }
    out = {}
    for key, attr in fields.items():
        value = getattr(stats, attr, None)
        if value is not None:
            out[key] = int(value)
    out["hbm_peak_bytes"] = (
        out.get("argument_bytes", 0) + out.get("output_bytes", 0)
        + out.get("temp_bytes", 0) + out.get("generated_code_bytes", 0)
        - out.get("alias_bytes", 0)
    )
    return out


def enable_cost_analysis(on: bool = True) -> None:
    """Opt in to per-shape cost/memory analysis (one ahead-of-time
    lower+compile per new signature — bench/sim entry points only;
    library and test runs skip it). SDA_DEVPROF_COST=0/1 overrides."""
    global _cost_enabled
    _cost_enabled = bool(on)


def cost_analysis_enabled() -> bool:
    env = os.environ.get("SDA_DEVPROF_COST")
    if env is not None and env != "":
        return env not in ("0", "false", "no")
    return _cost_enabled


# -- jax.monitoring taps ------------------------------------------------------

def _on_event_duration(event: str, duration_s: float, **_kw) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        metrics.count("xla.compile.backend")
        metrics.observe("xla.compile.seconds", duration_s)


def _on_event(event: str, **_kw) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        metrics.count("xla.compile.cache.hit")
    elif event == "/jax/compilation_cache/cache_misses":
        metrics.count("xla.compile.cache.miss")


def install_monitoring() -> bool:
    """Register the ``jax.monitoring`` listeners feeding the process-wide
    ``xla.compile.*`` counters and the compile-seconds histogram.
    Idempotent; listeners stay for the process lifetime (jax offers no
    per-listener removal) and write only into the metrics registry, which
    ``obs.reset_all()`` clears. Returns False when jax is unavailable."""
    global _monitoring_installed
    with _lock:
        if _monitoring_installed:
            return True
        try:
            from jax import monitoring
        except Exception:  # no jax in this profile — devprof stays inert
            return False
        monitoring.register_event_duration_secs_listener(_on_event_duration)
        monitoring.register_event_listener(_on_event)
        _monitoring_installed = True
        return True


# -- the instrument wrapper ---------------------------------------------------

def profile(name: str) -> FnProfile:
    """The (created-on-demand) profile entry for ``name``."""
    with _lock:
        prof = _profiles.get(name)
        if prof is None:
            prof = _profiles[name] = FnProfile(name)
        return prof


def _capture_cost(prof: FnProfile, fn, sig: Tuple, args, kwargs) -> None:
    """AOT lower+compile for cost/memory analysis, BEFORE the real call so
    donated argument buffers are still alive. Any surprise is recorded,
    never raised — profiling must not fail the round it observes."""
    try:
        import warnings

        with warnings.catch_warnings():
            # the AOT compile never executes, so jax warns that donated
            # buffers went unused — noise for a cost-only compile
            warnings.filterwarnings(
                "ignore", message=".*donated buffers.*")
            compiled = fn.lower(*args, **kwargs).compile()
        entry = _normalize_cost(compiled.cost_analysis())
        entry.update(_normalize_memory(compiled.memory_analysis()))
    except Exception as e:  # noqa: BLE001 — observability stays best-effort
        entry = {"error": f"{type(e).__name__}: {str(e)[:200]}"}
    with _lock:
        prof.costs[sig] = entry
    peak = entry.get("hbm_peak_bytes")
    if peak:
        metrics.gauge_max("device.hbm.peak_bytes", peak)
        metrics.gauge_max(f"device.hbm.peak_bytes.{prof.name}", peak)


def _record_retrace(name: str, sig: Tuple, compiles: int) -> None:
    metrics.count("xla.compile.retrace")
    metrics.count(f"xla.compile.retrace.{name}")
    attrs = {"function": name, "signature": str(sig),
             "compiles_before": compiles}
    if _trace.current_span() is not None:
        _trace.add_event("xla.retrace", **attrs)
    else:
        # no open span (bare library call): a zero-length marker span keeps
        # the event exportable instead of silently dropping it
        with _trace.span("xla.retrace", attributes={"function": name}):
            _trace.add_event("xla.retrace", **attrs)


def instrument(name: str, fn, span: Optional[str] = None,
               counts: Optional[Dict[str, int]] = None):
    """Wrap a jitted callable with the compiled-shape registry.

    Repeated ``instrument`` calls with the same ``name`` (e.g. the
    streaming driver building one step per block shape) accumulate into
    ONE profile entry, so the registry reflects the logical phase, not
    the python object. The wrapper forwards ``lower``/``_cache_size`` so
    AOT consumers and the jit-cache tripwire tests keep working.

    ``span`` names an ``obs`` span to open around every top-level call,
    from entry until the (asynchronous) dispatch returns: the host's side
    of the call, registry bookkeeping included, on every path that holds
    the wrapper. A retrace event then lands on that span. Calls from
    inside an outer trace open none (they dispatch nothing).

    ``counts`` maps counter names to what one top-level call adds to each
    (``utils/metrics``), counted where the span opens: static amounts the
    builder of the step knows from its shapes. Whether a call counts is
    settled here, when the step is wrapped; a step without counts runs the
    same wrapper as before.
    """
    profile(name)  # eager registration; the wrapper re-resolves per call
    # compile accounting and cost capture only make sense for jit-like
    # callables; a plain eager function wrapped for counters must not
    # fabricate "compiles"/"retraces" per new argument shape
    jitlike = hasattr(fn, "lower") or _cache_size(fn) is not None

    def dispatch(args, kwargs):
        # re-resolved per call, NOT closed over: module-level wrappers
        # (fields/sharing.py) outlive obs.reset_all(), and stats written
        # into a pre-reset profile object would be invisible forever
        prof = profile(name)
        sig = _signature(args, kwargs)
        before = _cache_size(fn)
        with _lock:
            prof.calls += 1
            new_sig = sig not in prof.shapes
            prof.shapes[sig] = prof.shapes.get(sig, 0) + 1
        will_compile = (new_sig and jitlike) if before is None else None
        if new_sig and jitlike and cost_analysis_enabled():
            _capture_cost(prof, fn, sig, args, kwargs)
        try:
            import jax

            with jax.named_scope(name):
                out = fn(*args, **kwargs)
        except ImportError:  # pragma: no cover — jax-free profiles
            out = fn(*args, **kwargs)
        if will_compile is None:
            after = _cache_size(fn)
            will_compile = after is not None and before is not None \
                and after > before
        if will_compile:
            # account at COMPLETION time, under the lock: two threads
            # racing the function's first two compiles must still record
            # the second one as a retrace
            with _lock:
                compiles_before = prof.compiles
                prof.compiles += 1
                if compiles_before >= 1:
                    prof.retraces += 1
            metrics.count("xla.compile.fn")
            metrics.count(f"xla.compile.fn.{name}")
            if compiles_before >= 1:
                _record_retrace(name, sig, compiles_before)
        return out

    run = dispatch
    if counts:
        amounts = tuple(counts.items())

        def run(args, kwargs):
            for counter, amount in amounts:
                metrics.count(counter, amount)
            return dispatch(args, kwargs)

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        if _is_traced(args, kwargs):
            import jax

            with jax.named_scope(name):
                return fn(*args, **kwargs)
        if span is None:
            return run(args, kwargs)
        with _trace.span(span):
            return run(args, kwargs)

    wrapper.__wrapped__ = fn
    for attr in ("lower", "_cache_size", "trace", "eval_shape"):
        value = getattr(fn, attr, None)
        if value is not None:
            setattr(wrapper, attr, value)
    return wrapper


# -- reports ------------------------------------------------------------------

def report() -> Dict[str, dict]:
    """{function name: compile/shape/retrace summary} for every
    instrumented function CALLED since the last reset (instrument()
    registers profiles eagerly at import; zero-call entries are noise)."""
    with _lock:
        return {name: prof.to_obj()
                for name, prof in sorted(_profiles.items())
                if prof.calls or prof.compiles}


def compile_totals() -> dict:
    """The compile-telemetry summary (statusz / bench ``xla`` block):
    per-function registry, process-wide backend-compile counter + seconds
    histogram, persistent-cache hit/miss counters."""
    counters = metrics.counter_report("xla.compile.")
    hist = metrics.histogram_report("xla.compile.seconds").get(
        "xla.compile.seconds")
    return {
        "functions": report(),
        "backend_compiles": counters.get("xla.compile.backend", 0),
        "retraces": counters.get("xla.compile.retrace", 0),
        "compile_seconds": hist,
        "cache": {
            "hit": counters.get("xla.compile.cache.hit", 0),
            "miss": counters.get("xla.compile.cache.miss", 0),
        },
    }


def cost_totals(names=None, basis: str = "total") -> dict:
    """Fold the recorded cost entries into the ``cost`` block: FLOPs,
    bytes accessed, arithmetic intensity and the executables' peak HBM
    footprint, overall and per phase — what XLA counts from the shapes,
    so it holds on any backend.

    ``basis="total"`` sums cost x calls over every signature;
    ``basis="per_call"`` takes one call's worth per function (pair with
    a per-round time). ``names`` filters which instrumented functions
    contribute (default: all with cost data).
    """
    with _lock:
        profs = [p for n, p in sorted(_profiles.items())
                 if (names is None or n in names) and p.costs]
    flops = bytes_acc = 0.0
    hbm_peak = 0
    phases = {}
    for prof in profs:
        totals = prof.totals()
        if basis == "per_call":
            cost = prof.costs[next(reversed(prof.costs))]
            f = float(cost.get("flops") or 0.0)
            b = float(cost.get("bytes_accessed") or 0.0)
        else:
            f, b = totals["flops"], totals["bytes_accessed"]
        flops += f
        bytes_acc += b
        hbm_peak = max(hbm_peak, totals["hbm_peak_bytes"])
        phases[prof.name] = {
            "calls": prof.calls,
            "flops": f,
            "bytes": b,
            "arithmetic_intensity": round(f / b, 4) if b else 0.0,
            "hbm_peak_bytes": totals["hbm_peak_bytes"],
        }
    return {
        "flops": flops,
        "bytes": bytes_acc,
        "arithmetic_intensity": round(flops / bytes_acc, 4)
        if bytes_acc else 0.0,
        "hbm_peak_bytes": int(hbm_peak),
        "basis": basis,
        "phases": phases,
    }


def roofline_block(flops: float, bytes_accessed: float, peaks: dict,
                   seconds: Optional[float] = None) -> dict:
    """The roofline arithmetic for explicit totals and peaks: attainable
    rate (``min(peak_flops, AI x peak_bw)``), and achieved utilization
    when ``seconds`` is given."""
    ai = flops / bytes_accessed if bytes_accessed else 0.0
    attainable = min(peaks["flops_per_s"], ai * peaks["hbm_bytes_per_s"]) \
        if ai else peaks["flops_per_s"]
    block = {
        "peaks": peaks,
        "arithmetic_intensity": round(ai, 4),
        "attainable_flops_per_s": attainable,
        "bound": ("compute" if attainable == peaks["flops_per_s"]
                  else "memory"),
    }
    if seconds and seconds > 0:
        achieved = flops / seconds
        block["seconds"] = round(seconds, 6)
        block["achieved_flops_per_s"] = achieved
        block["utilization"] = float(f"{achieved / attainable:.4g}") \
            if attainable else 0.0
    return block


def roofline(seconds: Optional[float] = None, names=None,
             basis: str = "total") -> Optional[dict]:
    """:func:`cost_totals` read against the peaks of the device this
    process runs on, or None when :data:`CHIP_PEAKS` has no complete row
    for its ``device_kind`` — a CPU run, a chip nobody entered, or one
    whose compute peak has no source gets no roofline rather than one
    against invented peaks."""
    import jax

    kind = jax.devices()[0].device_kind
    peaks = CHIP_PEAKS.get(kind)
    if peaks is None or not {"flops_per_s", "hbm_bytes_per_s"} <= set(peaks):
        return None
    cost = cost_totals(names, basis)
    block = roofline_block(cost["flops"], cost["bytes"], peaks, seconds)
    block["device_kind"] = kind
    return block


# -- HBM watermark ------------------------------------------------------------

def hbm_watermark() -> int:
    """The per-device HBM budget (bytes) model-scale rounds must plan
    under — THE number the devscale tile-width rule divides by.

    ``SDA_HBM_WATERMARK`` is an explicit budget in bytes (already
    fraction-adjusted: what the operator says is what the planner gets).
    Otherwise the budget is the headroom fraction
    (``SDA_HBM_WATERMARK_FRACTION``, default 0.8) of the live device's
    ``memory_stats()["bytes_limit"]``; a TPU that reports none raises.
    Only the CPU backend, which has no device memory to ask, plans
    against the stand-in :data:`CPU_PLANNING_HBM_BYTES`.
    """
    raw = os.environ.get("SDA_HBM_WATERMARK")
    if raw:
        try:
            value = int(float(raw))
            if value > 0:
                return value
        except ValueError:
            pass
    frac = DEFAULT_WATERMARK_FRACTION
    fraw = os.environ.get("SDA_HBM_WATERMARK_FRACTION")
    if fraw:
        try:
            frac = min(1.0, max(0.05, float(fraw)))
        except ValueError:
            pass
    import jax

    device = jax.local_devices()[0]
    if device.platform == "cpu":
        return int(CPU_PLANNING_HBM_BYTES * frac)
    limit = int((device.memory_stats() or {}).get("bytes_limit") or 0)
    if limit <= 0:
        raise RuntimeError(
            f"{device.device_kind} reports no memory_stats()['bytes_limit']"
            f" to plan the HBM watermark from; set SDA_HBM_WATERMARK")
    return int(limit * frac)


def hbm_peak_recorded(names=None) -> int:
    """Max ``hbm_peak_bytes`` across the recorded cost entries (0 when
    cost analysis was off — the caller should say so, not guess)."""
    with _lock:
        profs = [p for n, p in _profiles.items()
                 if names is None or n in names]
    peak = 0
    for prof in profs:
        peak = max(peak, prof.totals()["hbm_peak_bytes"])
    return peak


def watermark_report(peak_bytes: Optional[int] = None, names=None) -> dict:
    """The ``hbm`` advisory block devscale records carry: measured peak,
    the watermark it was planned against, and their ratio (< 1.0 means
    the round kept its HBM promise)."""
    watermark = hbm_watermark()
    peak = int(peak_bytes if peak_bytes is not None
               else hbm_peak_recorded(names))
    block = {
        "hbm_peak_bytes": peak,
        "watermark_bytes": watermark,
        "within_watermark": peak <= watermark,
    }
    if watermark:
        block["hbm_watermark_ratio"] = round(peak / watermark, 4)
    if peak == 0:
        block["note"] = ("no cost entries recorded — enable_cost_analysis"
                         " was off or no instrumented call compiled")
    return block


def reset() -> None:
    """Clear the compiled-shape registry and cost entries (the
    ``xla.compile.*`` counters and HBM gauges live in the metrics
    registry, which ``obs.reset_all()`` clears alongside this)."""
    with _lock:
        _profiles.clear()
