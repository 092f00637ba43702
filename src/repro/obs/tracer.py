"""Span-based tracer + metrics registry for the resident runtime.

The CHT-MPI paper demonstrates its load-balancing claims with per-process
execution timelines and work/communication statistics gathered by the
runtime itself; the original Chunks-and-Tasks programming-model paper makes
task/chunk accounting a first-class runtime service.  This module is that
service for the XLA-mesh reproduction:

* :class:`Tracer` records **nested spans** (phase -> iteration -> collective
  -> kernel dispatch / plan build / symbolic descent / rebalance migration)
  on one host timeline, each with a wall-clock interval, a category, free
  args, and — on leaf dispatch spans — a **per-worker cost attribution**
  vector (:attr:`Span.worker_costs`) measured from the executed plan.  An
  SPMD step's wall time is set by its slowest worker, so the exporters
  derive one *track per worker* whose busy interval inside each step is the
  worker's measured share of the step cost — the paper's utilization
  timeline, reproduced from runtime measurements.
* **Counters and gauges** are registered once on the tracer's metrics
  registry (``plan_hits`` / ``plan_misses`` / ``tasks_executed`` /
  ``tasks_before_exchange`` (fused tasks laid out ahead of the exchange) /
  ``recv_bytes`` / ``recv_padded_bytes`` (what the SPMD program receives,
  padding included) / ``send_bytes`` / ``migrated_bytes`` /
  ``norm_fetch_bytes`` / ``host_fetches`` (blocking device-to-host reads),
  plus ``plans_verified`` / ``verify_violations``
  from the static verifier at plan-cache admission) and emitted uniformly:
  live as Chrome counter events, and at run end as the flat dict
  (:func:`run_metrics`) the driver stats dataclasses wrap.
* **Structured analysis events**: the plan verifier
  (:mod:`repro.analysis`) reports each violation as a
  ``plan_verify_violation`` instant in category ``"analysis"`` carrying
  the check id and task/round provenance — query them with
  :meth:`Tracer.instants_of`.
* **On the device trace's clock**: every span is also written into the JAX
  profiler's trace as a ``jax.profiler.TraceAnnotation`` named
  ``repro.<span name>``, so a profiler capture shows the library's host
  work beside the device operations.  With no profiler running the
  annotation is the profiler's own no-op.  A span measures host time: a
  ``dispatch`` span covers the enqueue of an executable, and the device
  time is read from the device trace.
* **Compiles**: while a span is open, each XLA compile (a persistent-cache
  load included) adds to the ``compiles`` / ``compile_s`` counters and
  records a ``compile`` instant under the innermost span, carrying that
  span's name and plan ``kind``.  Compiles outside the library's spans are
  not counted.
* :data:`NULL_TRACER` is the disabled tracer every un-instrumented call
  path sees: all methods are allocation-free no-ops, it is falsy, and it
  records nothing — tracing off costs a few attribute lookups per
  operation and cannot perturb numerics.

The tracer rides on the plan cache (``SymbolicCache.tracer``), which is
already threaded through every resident collective and driver — enable
tracing by constructing ``PlanCache(tracer=Tracer())`` or by passing
``tracer=`` to a driver, and read it back anywhere via :func:`tracer_of`.
:meth:`Tracer.close` unregisters its compile listener.
"""

from __future__ import annotations

import time
import weakref
from typing import Any

import jax
from jax.profiler import TraceAnnotation

__all__ = [
    "Counter",
    "Gauge",
    "Span",
    "Tracer",
    "NullTracer",
    "NULL_TRACER",
    "tracer_of",
    "run_metrics",
]

#: prefix of the library's span names in a profiler trace
SPAN_PREFIX = "repro."
#: the jax.monitoring duration event of an XLA compile or persistent-cache load
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Counter:
    """Monotonic counter registered once on a tracer's metrics registry."""

    __slots__ = ("name", "value", "_tracer")

    def __init__(self, name: str, tracer: "Tracer | None" = None):
        self.name = name
        self.value = 0.0
        self._tracer = tracer

    def add(self, v: float = 1.0) -> None:
        self.value += v
        tr = self._tracer
        if tr is not None and tr.enabled:
            tr._counter_events.append((tr._clock(), self.name, self.value))


class Gauge:
    """Last-value gauge registered once on a tracer's metrics registry."""

    __slots__ = ("name", "value", "_tracer")

    def __init__(self, name: str, tracer: "Tracer | None" = None):
        self.name = name
        self.value = 0.0
        self._tracer = tracer

    def set(self, v: float) -> None:
        self.value = float(v)
        tr = self._tracer
        if tr is not None and tr.enabled:
            tr._counter_events.append((tr._clock(), self.name, self.value))


class Span:
    """One recorded interval on the host timeline.

    ``parent`` is the index of the enclosing span in ``tracer.spans`` (or
    -1); ``worker_costs``, when set by the instrumentation, is a ``[P]``
    non-negative vector of measured per-worker cost shares of this span
    (executed tasks + exchange bytes in task-equivalent units) — the
    exporters turn it into per-worker busy intervals.
    """

    __slots__ = ("name", "cat", "t0", "t1", "parent", "args", "worker_costs")

    def __init__(self, name: str, cat: str, t0: float, parent: int, args: dict):
        self.name = name
        self.cat = cat
        self.t0 = t0
        self.t1 = t0
        self.parent = parent
        self.args = args
        self.worker_costs = None

    @property
    def dur(self) -> float:
        return self.t1 - self.t0


class _SpanHandle:
    """Context manager closing one span; yields the span for annotation."""

    __slots__ = ("_tracer", "_span", "_scope")

    def __init__(self, tracer: "Tracer", span: Span):
        self._tracer = tracer
        self._span = span
        self._scope = TraceAnnotation(SPAN_PREFIX + span.name)

    def __enter__(self) -> Span:
        self._scope.__enter__()
        return self._span

    def __exit__(self, *exc) -> None:
        tr = self._tracer
        self._span.t1 = tr._clock()
        tr._stack.pop()
        self._scope.__exit__(*exc)
        return None


def _compile_listener(ref: "weakref.ref[Tracer]"):
    """A ``jax.monitoring`` duration listener that holds its tracer weakly,
    so a tracer that is dropped without :meth:`Tracer.close` is not kept
    alive by JAX's listener list."""

    def on_duration(event: str, secs: float, **kw) -> None:
        tr = ref()
        if tr is not None and event == COMPILE_EVENT:
            tr._on_compile(secs, kw.get("fun_name", ""))

    return on_duration


class Tracer:
    """Records nested spans, instants, and registered counters/gauges.

    Spans measure host time on ``clock`` and are mirrored into the profiler
    trace (module docstring).  Construction registers one ``jax.monitoring``
    listener that counts compiles under open spans; :meth:`close` removes it.
    """

    enabled = True

    def __init__(self, *, clock=time.perf_counter):
        self._clock = clock
        self.origin = clock()
        self.spans: list[Span] = []
        self.instants: list[tuple[str, str, float, int, dict]] = []
        self._stack: list[int] = []
        self._counters: dict[str, Counter] = {}
        self._gauges: dict[str, Gauge] = {}
        self._counter_events: list[tuple[float, str, float]] = []
        self._listener = _compile_listener(weakref.ref(self))
        jax.monitoring.register_event_duration_secs_listener(self._listener)

    def close(self) -> None:
        """Unregister the compile listener; spans and counters stay readable."""
        if self._listener is not None:
            jax.monitoring.unregister_event_duration_listener(self._listener)
            self._listener = None

    def _on_compile(self, secs: float, fun: str) -> None:
        if not self._stack:
            return
        sp = self.spans[self._stack[-1]]
        self.counter("compiles").add()
        self.counter("compile_s").add(secs)
        self.instant("compile", cat="compile", span=sp.name,
                     kind=sp.args.get("kind"), fun=fun, secs=secs)

    def __bool__(self) -> bool:
        return True

    # -- spans ---------------------------------------------------------------
    def span(self, name: str, cat: str = "", **args: Any) -> _SpanHandle:
        """Open a nested span; use as ``with tracer.span(...) as sp``."""
        parent = self._stack[-1] if self._stack else -1
        sp = Span(name, cat, self._clock(), parent, args)
        self._stack.append(len(self.spans))
        self.spans.append(sp)
        return _SpanHandle(self, sp)

    def instant(self, name: str, cat: str = "", **args: Any) -> None:
        """Zero-duration marker attached to the current span."""
        parent = self._stack[-1] if self._stack else -1
        self.instants.append((name, cat, self._clock(), parent, args))

    def instants_of(self, name: str, cat: str | None = None) -> list[dict]:
        """The recorded args dicts of matching instants, in record order
        (e.g. the verifier's ``plan_verify_violation`` analysis events)."""
        return [args for (n, c, _, _, args) in self.instants
                if n == name and (cat is None or c == cat)]

    # -- metrics registry ----------------------------------------------------
    def counter(self, name: str) -> Counter:
        c = self._counters.get(name)
        if c is None:
            c = self._counters[name] = Counter(name, self)
        return c

    def gauge(self, name: str) -> Gauge:
        g = self._gauges.get(name)
        if g is None:
            g = self._gauges[name] = Gauge(name, self)
        return g

    def metrics_flat(self) -> dict:
        """Flat dict of every registered counter/gauge plus span counts."""
        out: dict = {name: c.value for name, c in sorted(self._counters.items())}
        out.update({name: g.value for name, g in sorted(self._gauges.items())})
        out["spans_recorded"] = len(self.spans)
        return out


class _NullHandle:
    """Reusable no-op span context; also quacks like a Span for annotation."""

    __slots__ = ()
    worker_costs = None

    @property
    def args(self) -> dict:  # a fresh throwaway dict: mutations vanish
        return {}

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None

    def __setattr__(self, name, value):  # annotations on a null span vanish
        pass


class _NullMetric:
    __slots__ = ()
    value = 0.0

    def add(self, v: float = 1.0) -> None:
        pass

    def set(self, v: float) -> None:
        pass


_NULL_HANDLE = _NullHandle()
_NULL_METRIC = _NullMetric()


class NullTracer:
    """The disabled tracer: falsy, allocation-free, records nothing."""

    enabled = False
    spans: tuple = ()
    instants: tuple = ()

    def __bool__(self) -> bool:
        return False

    def span(self, name: str, cat: str = "", **args: Any) -> _NullHandle:
        return _NULL_HANDLE

    def instant(self, name: str, cat: str = "", **args: Any) -> None:
        pass

    def instants_of(self, name: str, cat: str | None = None) -> list:
        return []

    def counter(self, name: str) -> _NullMetric:
        return _NULL_METRIC

    def gauge(self, name: str) -> _NullMetric:
        return _NULL_METRIC

    def metrics_flat(self) -> dict:
        return {}


NULL_TRACER = NullTracer()


def tracer_of(cache) -> Tracer | NullTracer:
    """The tracer threaded through the runtime rides on the plan cache."""
    if cache is None:
        return NULL_TRACER
    tr = getattr(cache, "tracer", None)
    return tr if tr is not None else NULL_TRACER


def run_metrics(cache=None, tracer=None) -> dict:
    """The unified flat metrics dict the driver stats dataclasses wrap.

    Cache counters (hits / misses / hit_rate / build_s / symbolic_s /
    by_kind) merged with every counter and gauge registered on the tracer
    (tasks_executed, recv/send bytes, migrated bytes, norm-fetch bytes, span
    counts).  With tracing disabled this is exactly ``cache.stats()`` — the
    pre-tracer behaviour — so existing consumers keep working unchanged.
    """
    tr = tracer if tracer is not None else tracer_of(cache)
    out: dict = dict(cache.stats()) if cache is not None else {}
    out.update(tr.metrics_flat())
    return out
