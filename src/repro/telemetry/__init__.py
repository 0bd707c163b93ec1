"""Fleet control room: process-wide metrics registry, cold-start spans on
the profiler's timeline, and a periodic stats snapshotter.

Three tiers (see README "Control room"):

  emitters -> MetricsRegistry -> StatsSnapshotter -> results/telemetry/*.jsonl
                                                       -> scripts/control_room.py (dashboard)
                                                       -> scripts/bench_compare.py --history (CI gate)

* :class:`MetricsRegistry` — lock-light counters / gauges / fixed-bucket
  histograms plus :meth:`~MetricsRegistry.span`, a
  ``jax.profiler.TraceAnnotation`` that puts a cold-start stage on the
  profiler's timeline beside the device ops.  A process-wide default lives at
  :data:`repro.telemetry.TELEMETRY`; emitters take ``registry=None`` and
  fall back to it, and :meth:`MetricsRegistry.disable` turns every
  emission into a no-op (the overhead A/B in the scalability benchmark).
* :class:`StatsSnapshotter` — samples every registered ``stats()``
  surface on a configurable interval into a JSON-lines time series.
  The clock is injected, so tests drive :meth:`StatsSnapshotter.sample`
  sleep-free; the background thread follows the REP004 convention
  (daemon + stop event + joined in :meth:`StatsSnapshotter.stop`).
* :mod:`repro.telemetry.schema` — the one documented stat-key schema
  (canonical names, legacy aliases, per-sample invariants).
"""
from .registry import (  # noqa: F401
    TELEMETRY,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
)
from .schema import LEGACY_ALIASES, SAMPLE_KEYS, canonicalize  # noqa: F401
from .snapshot import StatsSnapshotter, TelemetryConfig  # noqa: F401

__all__ = [
    "TELEMETRY",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "StatsSnapshotter",
    "TelemetryConfig",
    "LEGACY_ALIASES",
    "SAMPLE_KEYS",
    "canonicalize",
]
