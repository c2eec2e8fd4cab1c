"""Per-phase timing and JAX profiler hooks.

The reference has no tracing or profiling at all (SURVEY.md §5.1 — no
timers, spans, or metrics anywhere in /root/reference). Here every protocol
phase (participant mask/share/encrypt, clerk decrypt/combine/encrypt,
recipient reconstruct/unmask, server snapshot steps, the pod's round) runs
under ``timed_phase``: ONE ``obs.span`` whose measured duration also
accumulates in a process-global registry (``phase_report()`` returns the
stats; ``bench`` and tests read it). One span layer, one clock pair: the
span's epoch start puts the phase on a device trace's clock (through
``obs.set_span_sink``; ``benchmarks/chip/reduce/`` labels the device's idle
gaps with it), its ``perf_counter`` duration is the phase's seconds.

A phase costs one span (two ``perf_counter`` calls, ids, a deque append)
+ one dict update — noise next to any device math, safe to leave on
permanently.
"""

from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass, field
from typing import Dict, Iterator, Optional

from ..obs import trace as _trace


@dataclass
class PhaseStat:
    count: int = 0
    total_s: float = 0.0
    min_s: float = field(default=float("inf"))
    max_s: float = 0.0

    @property
    def mean_s(self) -> float:
        return self.total_s / self.count if self.count else 0.0

    def add(self, seconds: float) -> None:
        self.count += 1
        self.total_s += seconds
        self.min_s = min(self.min_s, seconds)
        self.max_s = max(self.max_s, seconds)

    def to_obj(self) -> Dict[str, float]:
        return {
            "count": self.count,
            "total_s": self.total_s,
            "mean_s": self.mean_s,
            "min_s": self.min_s,
            "max_s": self.max_s,
        }


_lock = threading.Lock()
_stats: Dict[str, PhaseStat] = {}


@contextlib.contextmanager
def timed_phase(name: str, *,
                parent: Optional[_trace.SpanContext] = None,
                ) -> Iterator[_trace.Span]:
    """Time a protocol phase: one span in the distributed-tracing layer
    (``sda_tpu.obs``), so the phase joins the round's causal timeline --
    parented to ``parent`` when given, else to whatever span is active on
    this thread (an HTTP server span, a client role span, ...) -- whose
    measured duration also lands in the phase registry."""
    span_ = None
    try:
        with _trace.span(name, parent=parent) as span_:
            yield span_
    finally:
        if span_ is not None:  # closed by now, also on an exception
            with _lock:
                stat = _stats.get(name)
                if stat is None:
                    stat = _stats[name] = PhaseStat()
                stat.add(span_.duration_s)


def phase_report() -> Dict[str, Dict[str, float]]:
    """Snapshot of all phase stats since the last reset, keyed by phase."""
    with _lock:
        return {name: stat.to_obj() for name, stat in sorted(_stats.items())}


def reset_phase_report() -> None:
    with _lock:
        _stats.clear()


@contextlib.contextmanager
def profile_trace(logdir: str) -> Iterator[None]:
    """Capture a JAX/XLA profiler trace (the device's ops under their
    ``sda.*`` named scopes, and the profiler's own host timeline) into
    ``logdir`` for TensorBoard/XProf: an operator's device trace. The
    program's phases are not in it; they are ``obs`` spans on the epoch
    clock, which ``benchmarks/chip/reduce/`` lays beside the device ops."""
    import jax.profiler

    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
