"""paddle_tpu.observability — always-on runtime metrics + structured
tracing.

Two cooperating layers (see the module docstrings for design notes):

- :mod:`~paddle_tpu.observability.metrics` — a process-wide
  ``MetricsRegistry`` of named Counter/Gauge/Histogram instruments with
  Prometheus-text and JSON exporters and a ``snapshot()``/
  ``diff_snapshots()`` API for benches.  The serving engine, TrainStep,
  the Pallas decode-attention routing gate and the kernel tuner record
  into the default registry.
- :mod:`~paddle_tpu.observability.spans` — ``span(name, **attrs)``
  ranges, live under a ``jax.profiler`` session or the ``Profiler``:
  written into the profiler's own trace (one clock with the device)
  and into ``runtime.HostTracer``'s buffer (``recorded()``);
  ``merge_chrome_traces`` writes host lanes into one Perfetto-loadable
  file.
- :mod:`~paddle_tpu.observability.flightrec` — the per-request
  ``FlightRecorder``: a bounded ring of structured lifecycle events
  the serving engine emits, with ``timeline()``/``explain()`` queries,
  a JSON export ``tools/explain_request.py`` reads, and per-request
  Perfetto lanes that ride ``merge_chrome_traces``.
- :mod:`~paddle_tpu.observability.fleet` — the FLEET plane over the
  router: ``stitch_flight_records`` correlates per-replica recorders
  into one cross-replica record (fleet ``explain()``, one Perfetto
  lane per replica), ``merge_registry_snapshots`` federates
  per-replica registries under a ``replica=`` label, and
  ``SLOBurnRateMonitor`` turns the ``serving.slo.*`` counters into
  windowed burn rates and replay-deterministic ``ALERT_KINDS``
  alerts.
- :mod:`~paddle_tpu.observability.timeseries` — the
  ``TimeSeriesRecorder``: bounded step-indexed instrument samples
  with windowed aggregates (rates, per-window hwm, histogram-delta
  quantiles) and JSON export.

The reference analogue is ``paddle/fluid/platform/profiler`` plus its
benchmark/stat utilities; here the metrics side is pull-model (scrape
or snapshot) so hot paths never block on an exporter.
"""

from .metrics import (  # noqa: F401
    Counter, Gauge, Histogram, MetricsRegistry, DEFAULT_BUCKETS, NAME_RE,
    diff_snapshots, get_registry,
)
from .spans import (  # noqa: F401
    format_span_name, instant, merge_chrome_traces, parse_span_name,
    recorded, span,
)
from .flightrec import (  # noqa: F401
    EVENT_KINDS, FlightEvent, FlightRecord, FlightRecorder,
    explain_events, load_flight_record,
)
from .fleet import (  # noqa: F401
    ALERT_KINDS, SLOBurnRateMonitor, StitchedEvent, StitchedRecord,
    merge_registry_snapshots, stitch_flight_records,
)
from .timeseries import TimeSeriesRecorder  # noqa: F401

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "DEFAULT_BUCKETS",
    "NAME_RE", "diff_snapshots", "get_registry",
    "span", "instant", "format_span_name", "parse_span_name",
    "merge_chrome_traces", "recorded",
    "EVENT_KINDS", "FlightEvent", "FlightRecord", "FlightRecorder",
    "explain_events", "load_flight_record",
    "ALERT_KINDS", "SLOBurnRateMonitor", "StitchedEvent",
    "StitchedRecord", "merge_registry_snapshots",
    "stitch_flight_records", "TimeSeriesRecorder",
]
