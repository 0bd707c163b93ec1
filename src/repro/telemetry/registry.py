"""Lock-light metrics registry + cold-start spans on the profiler's clock.

Design constraints, in order:

1. **Cheap on the hot path.**  Counters/gauges take one tiny leaf lock
   for the update only; histograms bisect fixed bucket edges under their
   own leaf lock.  No registry lock is ever held while calling out, so
   the static lock-graph analysis sees pure leaves (no ordering edges).
2. **Disable == no-op.**  :meth:`MetricsRegistry.disable` flips one
   boolean checked before any work; the scalability benchmark's
   telemetry-overhead A/B toggles it.
3. **StageTimings stays the stage-seconds sink (REP005).**  A span
   (:meth:`MetricsRegistry.span`) is a ``jax.profiler.TraceAnnotation``:
   the profiler stamps it, on the clock of the device ops it writes into
   the same ``.xplane.pb``, and keeps it only while a profiler session
   runs.  The registry never computes a stage duration itself.
4. **No direct ``time.*`` reads.**  Emitters pass their own injected
   clock's values in; a span reads no Python clock (outside a profiler
   session it is one inactive C++ ``TraceMe``).
"""
from __future__ import annotations

import bisect
import math
import threading

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "TELEMETRY",
]

# Default histogram edges (seconds): 100us .. ~26s, x2 per bucket.
DEFAULT_EDGES = tuple(1e-4 * 2.0 ** i for i in range(19))


class Counter:
    """Monotonic counter."""

    __slots__ = ("_mu", "_n")

    def __init__(self) -> None:
        self._mu = threading.Lock()
        self._n = 0

    def inc(self, n: int = 1) -> None:
        with self._mu:
            self._n += n

    @property
    def value(self) -> int:
        with self._mu:
            return self._n

    def snapshot(self):
        return self.value


class Gauge:
    """Last-write-wins scalar."""

    __slots__ = ("_mu", "_v")

    def __init__(self) -> None:
        self._mu = threading.Lock()
        self._v = 0.0

    def set(self, v: float) -> None:
        with self._mu:
            self._v = float(v)

    @property
    def value(self) -> float:
        with self._mu:
            return self._v

    def snapshot(self):
        return self.value


class Histogram:
    """Fixed-bucket histogram; ``edges[i]`` is the inclusive upper bound
    of bucket ``i``, with one implicit overflow bucket at the end."""

    __slots__ = ("edges", "_mu", "_buckets", "_count", "_sum", "_min", "_max")

    def __init__(self, edges=DEFAULT_EDGES) -> None:
        self.edges = tuple(float(e) for e in edges)
        if list(self.edges) != sorted(self.edges):
            raise ValueError("histogram edges must be sorted ascending")
        self._mu = threading.Lock()
        self._buckets = [0] * (len(self.edges) + 1)
        self._count = 0
        self._sum = 0.0
        self._min = None
        self._max = None

    def observe(self, v: float) -> None:
        v = float(v)
        i = bisect.bisect_left(self.edges, v)
        with self._mu:
            self._buckets[i] += 1
            self._count += 1
            self._sum += v
            if self._min is None or v < self._min:
                self._min = v
            if self._max is None or v > self._max:
                self._max = v

    @property
    def count(self) -> int:
        with self._mu:
            return self._count

    def percentile(self, q: float) -> float | None:
        """Bucket-resolution percentile (upper edge of the bucket holding
        the ``q``-th percentile, ``q`` in [0, 100]); None when empty."""
        with self._mu:
            if self._count == 0:
                return None
            rank = min(self._count,
                       max(1, math.ceil(q / 100.0 * self._count)))
            seen = 0
            for i, n in enumerate(self._buckets):
                seen += n
                if seen >= rank:
                    if i < len(self.edges):
                        return self.edges[i]
                    return self._max
            return self._max

    def snapshot(self) -> dict:
        with self._mu:
            return {
                "count": self._count,
                "sum": self._sum,
                "min": self._min,
                "max": self._max,
                "buckets": list(self._buckets),
                "edges": list(self.edges),
            }


class _Noop:
    """Stand-in returned by a disabled registry; swallows everything."""

    __slots__ = ()

    def inc(self, n: int = 1) -> None:
        pass

    def set(self, v: float) -> None:
        pass

    def observe(self, v: float) -> None:
        pass


_NOOP = _Noop()


class _NullSpan:
    """The span of a disabled registry: enters, exits (never swallowing an
    exception) and drops its metadata."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set_metadata(self, **attrs) -> None:
        pass


_NULL_SPAN = _NullSpan()
_annotation = None       # jax.profiler.TraceAnnotation, imported on first use


class MetricsRegistry:
    """Process-wide named metrics + profiler spans.

    The creation lock (``_mu``) guards only the name->metric maps;
    per-metric updates take the metric's own leaf lock.  All public
    methods are safe from any thread.
    """

    def __init__(self, *, enabled: bool = True) -> None:
        self._mu = threading.Lock()
        self._counters: dict[str, Counter] = {}
        self._gauges: dict[str, Gauge] = {}
        self._histograms: dict[str, Histogram] = {}
        self.enabled = bool(enabled)

    # -- toggles --------------------------------------------------------

    def enable(self) -> None:
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    # -- metric accessors ----------------------------------------------

    def counter(self, name: str) -> Counter:
        if not self.enabled:
            return _NOOP  # type: ignore[return-value]
        c = self._counters.get(name)
        if c is None:
            with self._mu:
                c = self._counters.setdefault(name, Counter())
        return c

    def gauge(self, name: str) -> Gauge:
        if not self.enabled:
            return _NOOP  # type: ignore[return-value]
        g = self._gauges.get(name)
        if g is None:
            with self._mu:
                g = self._gauges.setdefault(name, Gauge())
        return g

    def histogram(self, name: str, edges=DEFAULT_EDGES) -> Histogram:
        if not self.enabled:
            return _NOOP  # type: ignore[return-value]
        h = self._histograms.get(name)
        if h is None:
            with self._mu:
                h = self._histograms.setdefault(name, Histogram(edges))
        return h

    # -- convenience emitters ------------------------------------------

    def inc(self, name: str, n: int = 1) -> None:
        self.counter(name).inc(n)

    def set_gauge(self, name: str, v: float) -> None:
        self.gauge(name).set(v)

    def observe(self, name: str, v: float) -> None:
        self.histogram(name).observe(v)

    # -- spans ----------------------------------------------------------

    def span(self, name: str, **attrs):
        """Context manager: one event named ``name`` on the profiler's
        timeline, from enter to exit, with ``attrs`` as its stats (more
        may be added on the way with ``set_metadata``).  It nests under
        the span open on the same thread.  A disabled registry returns
        one shared null span."""
        if not self.enabled:
            return _NULL_SPAN
        global _annotation
        if _annotation is None:
            from jax.profiler import TraceAnnotation
            _annotation = TraceAnnotation
        return _annotation(name, **attrs)

    # -- export ---------------------------------------------------------

    def collect(self) -> dict:
        """Stable-keyed snapshot of every metric (spans live in the
        profiler's trace, not here)."""
        with self._mu:
            counters = dict(self._counters)
            gauges = dict(self._gauges)
            hists = dict(self._histograms)
        return {
            "enabled": self.enabled,
            "counters": {k: counters[k].snapshot() for k in sorted(counters)},
            "gauges": {k: gauges[k].snapshot() for k in sorted(gauges)},
            "histograms": {k: hists[k].snapshot() for k in sorted(hists)},
        }

    def reset(self) -> None:
        """Drop every metric (benchmark arm isolation)."""
        with self._mu:
            self._counters.clear()
            self._gauges.clear()
            self._histograms.clear()


#: Process-wide default registry.  Emitters take ``registry=None`` and
#: fall back to this, mirroring the module-level WS_CACHE convention.
TELEMETRY = MetricsRegistry()
