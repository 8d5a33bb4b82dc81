"""repro.obs — unified observability for the serving stack.

One :class:`Observability` bundle carries the three pieces:

- ``registry`` — :class:`~repro.obs.registry.MetricsRegistry`
  (counters / gauges / histograms with labels; JSON snapshot +
  Prometheus text exposition).
- ``tracer`` — :class:`~repro.obs.tracer.Tracer` (per-request and
  per-dispatch events, TTFT / inter-token latency histograms stamped on
  delivery, the host spans' counters, Chrome trace / Perfetto export).
  ``None`` when tracing is disabled.  The host spans themselves
  (``repro.obs.tracer.span``) are always on.
- ``device_metrics`` flag — when True the engine threads a packed
  int32 :class:`~repro.obs.device.DeviceMetricsSpec` block through the
  compiled step and drains it only at flush boundaries.

See README.md in this directory for the metric namespace.
"""
from __future__ import annotations

from repro.obs.device import SCALE, DeviceMetricsSpec
from repro.obs.quality import (DriftDetector, EwmaDetector,
                               PageHinkleyDetector,
                               inject_coefficient_drift)
from repro.obs.registry import (DEFAULT_LATENCY_BUCKETS, Counter, Gauge,
                                Histogram, MetricsRegistry)
from repro.obs.server import MetricsServer
from repro.obs.tracer import Tracer, validate_chrome_trace

__all__ = ["Observability", "MetricsRegistry", "Counter", "Gauge",
           "Histogram", "Tracer", "DeviceMetricsSpec", "SCALE",
           "DEFAULT_LATENCY_BUCKETS", "validate_chrome_trace",
           "DriftDetector", "EwmaDetector", "PageHinkleyDetector",
           "inject_coefficient_drift", "MetricsServer"]


class Observability:
    """Bundle handed to :class:`repro.serving.engine.Engine` (and the
    benchmark scenarios) tying registry + tracer + device-metrics
    toggle together.  Multiple engines may share one bundle — series
    are disambiguated by labels (layout, group, shard)."""

    def __init__(self, registry: MetricsRegistry = None, *,
                 device_metrics: bool = True, tracing: bool = True):
        self.registry = registry if registry is not None \
            else MetricsRegistry()
        self.device_metrics = device_metrics
        self.tracer = Tracer(self.registry) if tracing else None

    def snapshot(self) -> dict:
        out = {"metrics": self.registry.snapshot()}
        if self.tracer is not None:
            out["tracing"] = self.tracer.summary()
        return out

    def write_metrics_json(self, path: str) -> None:
        import json
        with open(path, "w") as f:
            json.dump(self.snapshot(), f, indent=1)

    def write_trace(self, path: str) -> None:
        assert self.tracer is not None, "tracing disabled"
        self.tracer.write_chrome_trace(path)
