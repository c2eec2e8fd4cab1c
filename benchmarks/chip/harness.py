"""The chip benchmark's harness: one cell, one process, one result line.

Driven by data. The harness knows no cell, configuration, traffic mix,
driver or metric by name: it reads the cell's entry in ``BENCHMARK.json``
and finds everything else by the names written there --

- ``configs/<config>.json`` (the ``file`` of the configuration's entry),
- ``traffic/<traffic>.json``,
- ``drivers/<driver>.py`` (the configuration names its driver),
- ``references/<reference>.py`` (the configuration names its reference),
- ``end_to_end/<metric>.py`` and ``layers/<metric>.py``, one reader each.

A later PR adds a cell, a configuration, a driver or a metric by adding
files and entries; it edits nothing that is here. README.md says how.

No ``SDA_*`` variable is set anywhere: the cells measure the program's
defaults, and a configuration passes only what a user passes.
"""

from __future__ import annotations

import importlib.util
import json
import statistics
import sys
import threading
import time
import traceback
from dataclasses import dataclass, field
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]

#: consecutive rounds that may raise before the window gives up
MAX_CONSECUTIVE_RAISES = 3


def log(message: str) -> None:
    print(f"[chipbench] {message}", file=sys.stderr, flush=True)


# -- finding the cell's files ------------------------------------------------

@dataclass
class Cell:
    """One entry of ``workloads`` with its configuration and traffic
    files loaded, and the metric entries that apply to it."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list
    home: Path  # the benchmark's directory (first of ``paths``)


def _applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_cell(root: Path, name: str) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    home = root / spec["paths"][0]
    entry = next((w for w in spec["workloads"] if w["name"] == name), None)
    if entry is None:
        known = ", ".join(w["name"] for w in spec["workloads"])
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has: {known}")
    config_entry = next(c for c in spec["configs"] if c["name"] == entry["config"])
    return Cell(
        name=name,
        chips=int(entry["chips"]),
        config=json.loads((root / config_entry["file"]).read_text()),
        traffic=json.loads((home / "traffic" / f"{entry['traffic']}.json").read_text()),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
        home=home,
    )


def load_module(home: Path, kind: str, name: str):
    """``<home>/<kind>/<name>.py`` as a module. Metric names carry dots,
    so files are loaded by path, never imported by name."""
    path = home / kind / f"{name}.py"
    if not path.is_file():
        raise SystemExit(f"{kind[:-1] if kind.endswith('s') else kind} "
                         f"{name!r} has no file {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# -- counting compiles -------------------------------------------------------

class CompileCounter:
    """Compile requests and persistent-cache hits and misses, from
    ``jax.monitoring``. A request is every program JAX had to obtain,
    whether XLA compiled it or the cache held it."""

    def __init__(self):
        from jax import monitoring

        self.requests = self.hits = self.misses = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, _seconds: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {"requests": self.requests, "hits": self.hits,
                "misses": self.misses}


class SpanLog:
    """The finished-span sink (``obs.set_span_sink``): seconds per span
    name, and in a traced run every span's interval on the epoch clock,
    which is the profiler's clock too. The program's ``timed_phase``
    phases and ``obs`` spans land here, and the harness adds its own
    ``bench.round`` / ``bench.verify`` marks.

    The profiler's host tracer stays off: it records the runtime's own
    spans with the annotations, and laying a host matrix out for the
    device emits millions of them (533 MB and 3.6x slower rounds for six
    host-fed rounds, my chip run, PR 23)."""

    def __init__(self, keep_intervals: bool):
        self.seconds: dict = {}
        self.intervals = [] if keep_intervals else None
        self._lock = threading.Lock()

    def __call__(self, span) -> None:
        self.add(span.name, int(span.start_s * 1e9),
                 int((span.start_s + (span.duration_s or 0.0)) * 1e9))

    def add(self, name: str, start_ns: int, end_ns: int) -> None:
        with self._lock:
            self.seconds[name] = self.seconds.get(name, 0.0) + (end_ns - start_ns) / 1e9
            if self.intervals is not None:
                self.intervals.append((name, start_ns, end_ns))


# -- the measured window -----------------------------------------------------

@dataclass
class Window:
    """What one window of rounds left behind, for the metric readers."""

    facts: dict                 # from the driver: shapes, work per round, ...
    chips: int
    device_kind: str
    setup_s: float
    round_walls: list = field(default_factory=list)   # seconds, per round
    round_starts: list = field(default_factory=list)  # perf_counter, per round
    attempted: int = 0
    raised: int = 0
    compiles_in_window: int = 0
    setup_cache: dict = field(default_factory=dict)   # hits/misses over set-up
    spans: dict = field(default_factory=dict)         # span name -> seconds in window:
    #   the program's timed_phase phases and obs spans, and bench.round/.verify
    memory_peak_bytes: int = 0
    memory_limit_bytes: int = 0
    trace: object = None        # reduce.Reduced when traced

    @property
    def median_round_s(self) -> float | None:
        return statistics.median(self.round_walls) if self.round_walls else None


def run_window(state, seconds: float, max_rounds: int | None, window: Window,
               spans: SpanLog) -> None:
    """Closed loop: one round at a time until ``seconds`` have passed (or
    ``max_rounds`` were made). Each round is marked ``bench.round`` and
    the check after it ``bench.verify`` in the span log, so a traced
    window knows its rounds and can label the device's idle gaps."""
    begin = time.perf_counter()
    consecutive = 0
    index = 0
    while True:
        start = time.perf_counter()
        if start - begin >= seconds or (max_rounds is not None and index >= max_rounds):
            break
        window.round_starts.append(start)
        round_ns = time.time_ns()
        try:
            state.round(index)
        except Exception:  # the window must go on: count, show, continue
            window.raised += 1
            consecutive += 1
            traceback.print_exc()
            if consecutive >= MAX_CONSECUTIVE_RAISES:
                raise
            revealed = False
        else:
            consecutive, revealed = 0, True
        window.round_walls.append(time.perf_counter() - start)
        verify_ns = time.time_ns()
        if revealed:
            state.verify(index)
        spans.add("bench.round", round_ns, verify_ns)
        spans.add("bench.verify", verify_ns, time.time_ns())
        index += 1
    window.attempted = index


def memory(devices) -> tuple[int, int]:
    """(peak bytes, bytes limit) on the fullest of ``devices``; zeros where
    the backend reports no memory statistics (the CPU). The peak is live
    buffers plus what the runtime reserved for the programs' temporaries:
    on the v5e ``peak_bytes_in_use`` counts arguments and results only,
    and a round's temporaries (several times its input here) appear under
    ``peak_bytes_reserved`` (my chip runs, PR 23)."""
    stats = [d.memory_stats() or {} for d in devices]
    return (max(int(s.get("peak_bytes_in_use", 0))
                + int(s.get("peak_bytes_reserved", 0)) for s in stats),
            max(int(s.get("bytes_limit", 0)) for s in stats))


def read_metrics(cell: Cell, entries: list, kind: str, window: Window) -> dict:
    """``{name: {"value", "unit"}}`` from the reader file of each entry.
    A reader that finds nothing to read returns None and is left out."""
    out = {}
    for entry in entries:
        value = load_module(cell.home, kind, entry["name"]).read(window)
        if value is not None:
            out[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    return out


def describe(walls: list) -> dict:
    """Median, and the highest percentile that leaves ten samples beyond
    it, of the window's round walls -- for the log, not the result."""
    ordered = sorted(walls)
    n = len(ordered)
    out = {"samples": n, "median_s": statistics.median(ordered) if n else None}
    if n >= 20:
        q = 1.0 - 10.0 / n
        out[f"p{100 * q:.1f}_s"] = ordered[min(n - 1, int(q * n))]
    return out


# -- one run -------------------------------------------------------------------

def run(argv, t0: float, root: Path = ROOT) -> int:
    import argparse

    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument(
        "--rehearsal", action="store_true",
        help="control-flow check without a chip (rehearse.sh): toy shapes "
             "from the traffic file's 'rehearsal' block, kernels "
             "interpreted, no device metric printed")
    args = parser.parse_args(argv)

    cell = load_cell(root, args.workload)
    if args.rehearsal:
        cell.traffic = {**cell.traffic, **cell.traffic.get("rehearsal", {})}

    sys.path.insert(0, str(ROOT))  # the program, from this checkout
    sys.path.insert(0, str(cell.home))
    import sda_tpu  # noqa: F401  (turns on x64 before jax is used)
    import jax

    from sda_tpu import obs
    from sda_tpu.utils.backend import arm_compile_cache

    stages = {"imports_s": time.perf_counter() - t0}
    devices = jax.devices()
    stages["backend_s"] = time.perf_counter() - t0 - stages["imports_s"]
    platform = devices[0].platform
    if len(devices) < cell.chips or (platform != "tpu" and not args.rehearsal):
        log(f"{cell.name} needs {cell.chips} TPU chip(s); JAX found "
            f"{len(devices)} x {platform} ({devices[0].device_kind})")
        return 3
    devices = devices[:cell.chips]

    # the program's own placement rule: $JAX_COMPILATION_CACHE_DIR, else
    # <checkout>/.jax_compile_cache -- a fixed path inside the checkout.
    # Every program is cached, however fast it compiled.
    cache_dir = arm_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = CompileCounter()

    driver = load_module(cell.home, "drivers", cell.config["driver"])
    state = driver.setup(cell, args.seed, devices, args.rehearsal)
    try:
        setup_cache = compiles.snapshot()
        window = Window(facts=state.facts, chips=cell.chips,
                        device_kind=devices[0].device_kind, setup_s=0.0,
                        setup_cache=setup_cache)
        trace_dir = cell.home / "out" / f"trace-{cell.name}"
        max_rounds = None
        spans = SpanLog(keep_intervals=bool(args.trace))
        if args.trace:
            import shutil

            shutil.rmtree(trace_dir, ignore_errors=True)
            trace_dir.mkdir(parents=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 0   # see SpanLog
            max_rounds = int(cell.traffic["trace_rounds"])
            jax.profiler.start_trace(str(trace_dir), profiler_options=options)
        obs.set_span_sink(spans)
        window.setup_s = time.perf_counter() - t0
        stages["driver_s"] = window.setup_s - stages["imports_s"] - stages["backend_s"]
        try:
            run_window(state, args.seconds, max_rounds, window, spans)
            inexact = state.finish()
        finally:
            obs.set_span_sink(None)
            if args.trace:
                jax.profiler.stop_trace()
        window.compiles_in_window = compiles.requests - setup_cache["requests"]
        window.spans = spans.seconds
        window.memory_peak_bytes, window.memory_limit_bytes = memory(devices)
    finally:
        state.close()

    log(f"{cell.name}: set-up {window.setup_s:.2f} s {stages}, cache {cache_dir} "
        f"{setup_cache}, rounds {describe(window.round_walls)}, "
        f"compiles in window {window.compiles_in_window}, memory "
        f"{devices[0].memory_stats()}")
    if args.trace:
        import reduce as trace_reduce

        window.trace = trace_reduce.reduce_run(
            trace_dir, len(devices), spans.intervals)
        metrics = read_metrics(cell, cell.per_layer, "layers", window)
    else:
        metrics = read_metrics(cell, cell.end_to_end, "end_to_end", window)
    print(json.dumps(result_line(window, inexact, metrics, platform,
                                 len(jax.devices()), args.rehearsal)),
          flush=True)
    return 0


def result_line(window: Window, inexact: int, metrics: dict, platform: str,
                device_count: int, rehearsal: bool) -> dict:
    """The object on the last line of standard output: the contract's
    keys and no others. A rehearsal proves control flow on the CPU, so it
    says ``"rehearsal": true``, names the metrics it could read and
    prints none of them."""
    failed = window.raised + inexact
    result = {"correct": failed == 0, "attempted": window.attempted,
              "failed": failed}
    if rehearsal:
        return {**result, "rehearsal": True, "metrics": {},
                "read": sorted(metrics),
                "device": {"platform": platform, "count": device_count}}
    device = {"platform": platform, "kind": window.device_kind,
              "count": device_count,
              "memory_peak_bytes": window.memory_peak_bytes}
    result.update(metrics=metrics, device=device)
    if window.trace is not None:
        device["busy_s"] = window.trace.busy_s
        device["window_s"] = window.trace.window_s
        result["breakdown"] = window.trace.breakdown()
    return result
