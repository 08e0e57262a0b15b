"""defer_tpu.obs — metrics & telemetry for the serving/pipeline runtimes.

Split from `utils/profiling.py` on purpose: profiling captures device
*traces* (one-shot, heavyweight, opt-in), obs counts and times
*always-on* host-side events (near-free per sample, pull-based export):
counters, gauges and histograms in `metrics.py`, spans in `spans.py`.
See ARCHITECTURE.md "Observability".
"""

from defer_tpu.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    counter_deltas,
    get_registry,
    log_buckets,
)
from defer_tpu.obs import metrics as _metrics
from defer_tpu.obs import spans
from defer_tpu.obs.export import PeriodicDumper, prometheus_text
from defer_tpu.obs.serving import (
    DisaggMetrics,
    FleetMetrics,
    FleetStats,
    ServerStats,
    ServingMetrics,
)


def reset() -> None:
    """Zero the process registry in place and clear the span log
    (test isolation)."""
    _metrics.reset()
    spans.reset()


__all__ = [
    "Counter",
    "DisaggMetrics",
    "FleetMetrics",
    "FleetStats",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "PeriodicDumper",
    "ServerStats",
    "ServingMetrics",
    "counter_deltas",
    "get_registry",
    "log_buckets",
    "prometheus_text",
    "reset",
    "spans",
]
