"""Profiling: spans at the layer boundaries, per-cycle phase timings,
device tracing.

The reference has NO tracing/profiling at all (SURVEY.md §5.1 — only klog
prints in the loop, minisched/minisched.go:33-87).  This module supplies
the missing layer, as ONE primitive:

``span(name, **ids)`` — a context manager, usable from any thread, with
or without an engine, always on.  On entry and exit it reads the wall
clock and the calling thread's own CPU clock (``_cpu_clock``: a reading
under 50 us old is used again), and it

* opens a ``jax.profiler.TraceAnnotation(name, **ids)``, so a profiler
  trace (``device_trace`` below, the benchmark's ``--trace 1``) shows
  the host's layers on the same clock as the device's programs — the
  idle gaps between programs get a name.  Inactive (no trace running) it
  costs under a microsecond; the import of ``jax.profiler`` is deferred
  to the first span and touches no backend;
* observes two histograms of ``observability/hist``: ``<name>_s`` (wall)
  and ``<name>_cpu_s`` (the thread's CPU seconds).  Wall less CPU is the
  time the thread did not run: under one interpreter lock that is the
  wait for it — or for the device, a socket, a queue, which is why those
  waits are spans of their own (``sched.wave_fetch``,
  ``sched.loop_handoff_wait``, ``sched.queue_pop_wait``).

Span names are registered by the module that opens them
(``register_spans`` / ``register_phases`` at import), so ``/metrics``
shows every one with count 0 from boot.  ``CycleMetrics.timed(phase)`` is
this primitive plus the per-engine aggregate the benches and tests read
(``sched.metrics.snapshot()``); its span is ``sched.<phase>`` except for
the phases in ``_PHASE_SPANS``.  Spans are per batch, per wave, per call —
never per pod or per watch event; the per-pod story is the trace ring's
(``observability/trace``).
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Any, Dict, Iterator, Optional

from minisched_tpu.observability import hist

#: the engine phases whose span is not ``sched.<phase>``: the per-engine
#: aggregate keeps the phase keys the benches read, the span carries the
#: name of the layer boundary it stands at
_PHASE_SPANS: Dict[str, str] = {
    "wave_pipeline_build": "sched.wave_build",
    "commit": "sched.wave_commit",
    "wave_pipeline_stall": "sched.wave_stall",
    "loop_pop": "sched.loop_handoff_wait",
    "pipeline_pop": "sched.queue_pop_wait",
}

_annotation_cls: Any = None


def _annotation() -> Any:
    """``jax.profiler.TraceAnnotation``, imported at the first span:
    importing this module (or the engine) must not import JAX, and the
    import itself initialises no backend."""
    global _annotation_cls
    if _annotation_cls is None:
        from jax.profiler import TraceAnnotation

        _annotation_cls = TraceAnnotation
    return _annotation_cls


def span_name(phase: str) -> str:
    return _PHASE_SPANS.get(phase) or "sched." + phase


def register_spans(*names: str) -> None:
    """Both histograms of each span exist from now on (count 0)."""
    hist.register(*[n + sfx for n in names for sfx in ("_s", "_cpu_s")])


def register_phases(*phases: str, cpu: bool = True) -> None:
    """``register_spans`` by engine phase.  ``cpu=False`` for a phase that
    is only ever ``CycleMetrics.observe``d (a duration taken elsewhere:
    no span, so no CPU clock)."""
    names = [span_name(p) for p in phases]
    if cpu:
        register_spans(*names)
    else:
        hist.register(*[n + "_s" for n in names])


#: the thread CPU clock is a system call (6 us a read on the benchmark's
#: host, where the wall clock takes 0.1): spans open and close in runs
#: (``sched.wave`` > ``wave_evaluate`` > ``wave_device`` > ``wave_dispatch``
#: enter within microseconds), so a reading this young on the same thread
#: is used again.  A span's CPU time is off by at most twice this.
_CPU_READ_REUSE_S = 50e-6
_thread = threading.local()

#: span name -> its (wall, CPU) histograms, kept while the registry's
#: generation stands: two registry look-ups a span are a quarter of its cost
_histograms: Dict[str, Any] = {}
_histograms_of = -1


def _cpu_clock(now: float) -> float:
    if now - getattr(_thread, "read_at", -1.0) > _CPU_READ_REUSE_S:
        _thread.read_at = now
        _thread.cpu = time.thread_time()
    return _thread.cpu


def _pair(name: str) -> Any:
    global _histograms_of
    if _histograms_of != hist.GLOBAL.generation:  # a reset replaced them all
        _histograms.clear()
        _histograms_of = hist.GLOBAL.generation
    pair = _histograms.get(name)
    if pair is None:
        pair = _histograms[name] = (
            hist.GLOBAL.child(name + "_s"), hist.GLOBAL.child(name + "_cpu_s")
        )
    return pair


class Span:
    """One span; ``wall_s`` / ``cpu_s`` hold its two clocks after exit."""

    __slots__ = ("name", "wall_s", "cpu_s", "_ann", "_t0", "_c0")

    def __init__(self, name: str, ids: Dict[str, Any]):
        self.name = name
        self.wall_s = 0.0
        self.cpu_s = 0.0
        self._ann = _annotation()(name, **ids)

    def set(self, **ids: Any) -> None:
        """Ids known only inside the span (a body's item count)."""
        self._ann.set_metadata(**ids)

    def __enter__(self) -> "Span":
        self._ann.__enter__()
        self._t0 = now = time.monotonic()
        self._c0 = _cpu_clock(now)
        return self

    def __exit__(self, *exc: Any) -> bool:
        now = time.monotonic()
        self.wall_s = now - self._t0
        self.cpu_s = _cpu_clock(now) - self._c0
        self._ann.__exit__(*exc)
        wall, cpu = _pair(self.name)
        wall.observe(self.wall_s)
        cpu.observe(self.cpu_s)
        return False


def span(name: str, **ids: Any) -> Span:
    return Span(name, ids)


class _NoSpan:
    """Stands where a span would, and does nothing."""

    wall_s = 0.0
    cpu_s = 0.0

    def set(self, **ids: Any) -> None:
        pass

    def __enter__(self) -> "_NoSpan":
        return self

    def __exit__(self, *exc: Any) -> bool:
        return False


NO_SPAN = _NoSpan()


def no_span(name: str, **ids: Any) -> _NoSpan:
    """``span``'s signature, for a call site that opens spans for some of
    its traffic only."""
    return NO_SPAN


class PhaseStats:
    __slots__ = ("count", "total_s", "max_s")

    def __init__(self):
        self.count = 0
        self.total_s = 0.0
        self.max_s = 0.0

    def observe(self, dt: float) -> None:
        self.count += 1
        self.total_s += dt
        self.max_s = max(self.max_s, dt)

    @property
    def mean_s(self) -> float:
        return self.total_s / self.count if self.count else 0.0


class _PhaseSpan(Span):
    """A span that also lands in its engine's aggregate."""

    __slots__ = ("_metrics", "_phase")

    def __init__(self, metrics: "CycleMetrics", phase: str, ids: Dict[str, Any]):
        Span.__init__(self, span_name(phase), ids)
        self._metrics = metrics
        self._phase = phase

    def __exit__(self, *exc: Any) -> bool:
        Span.__exit__(self, *exc)
        self._metrics._aggregate(self._phase, self.wall_s)
        return False


class CycleMetrics:
    """Per-phase wall-clock aggregates for the scheduling loop.

    Every engine has one by default; ``timed(phase, **ids)`` opens the
    phase's span (module docstring) and adds its wall time here.
    """

    def __init__(self):
        self._mu = threading.Lock()
        self._phases: Dict[str, PhaseStats] = {}

    def _aggregate(self, phase: str, dt: float) -> None:
        with self._mu:
            self._phases.setdefault(phase, PhaseStats()).observe(dt)

    def observe(self, phase: str, dt: float) -> None:
        """A duration taken elsewhere (no span was open around it): the
        aggregate and the phase's wall histogram."""
        self._aggregate(phase, dt)
        hist.observe(span_name(phase) + "_s", dt)

    def timed(self, phase: str, **ids: Any) -> Span:
        return _PhaseSpan(self, phase, ids)

    def snapshot(self) -> Dict[str, Dict[str, float]]:
        with self._mu:
            return {
                name: {
                    "count": s.count,
                    "total_s": s.total_s,
                    "mean_s": s.mean_s,
                    "max_s": s.max_s,
                }
                for name, s in self._phases.items()
            }

    def report(self) -> str:
        lines = []
        for name, s in sorted(self.snapshot().items()):
            lines.append(
                f"{name}: n={s['count']} mean={s['mean_s']*1e3:.2f}ms "
                f"max={s['max_s']*1e3:.2f}ms total={s['total_s']:.3f}s"
            )
        return "\n".join(lines)


class NullMetrics:
    """No-op stand-in so the engine can call ``metrics.timed(...)``
    unconditionally (assign a real CycleMetrics to start collecting)."""

    def observe(self, phase: str, dt: float) -> None:
        pass

    def timed(self, phase: str, **ids: Any) -> _NoSpan:
        return NO_SPAN

    def snapshot(self) -> Dict[str, Dict[str, float]]:
        return {}

    def report(self) -> str:
        return ""


NULL_METRICS = NullMetrics()


@contextlib.contextmanager
def device_trace(log_dir: Optional[str]) -> Iterator[None]:
    """JAX profiler trace around device work (no-op when log_dir is None).
    View with TensorBoard / xprof.

    Export happens on context exit and serializes every event of the
    traced span — for a full engine run (compiles included) that takes
    ~10-30s after shutdown; keep the process alive until the trace
    directory is populated."""
    if not log_dir:
        yield
        return
    import jax

    with jax.profiler.trace(log_dir):
        yield
