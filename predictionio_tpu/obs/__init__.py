"""Observability: metrics, tracing, structured logs, flight recorder, SLO.

The subsystem every later perf PR leans on — counters/gauges/log-bucketed
histograms (metrics.py), context-manager spans with a recent-trace ring
(tracing.py), request-id-correlated JSON-lines logging with an in-process
ring (logging.py), a flight recorder for the slowest/errored requests
(flight.py), rolling-window SLO tracking with burn rates + health routes
(slo.py), on-demand jax.profiler capture (profiler.py), online model-quality
monitoring — prediction log, feedback joins, drift detection (quality.py) —
device-efficiency attribution — XLA cost/roofline capture, recompile-storm
detection, wave-timeline splits (device.py)
— HTTP exposition for all of it (http.py), a sniffer plugin proving the
plugin seams can consume the registry (plugin.py), and the watch loop that
turns it all into autonomous detection: a declarative alert rules engine
(alerts.py) whose firing transitions snapshot forensic incident bundles to
disk before the bounded rings rotate the evidence away (incident.py).
Dependency-free; the process-global default registry is ``REGISTRY``.
"""

from predictionio_tpu.obs.alerts import (
    AlertEvaluator,
    AlertRule,
    default_rule_pack,
    resolve_rules,
)
from predictionio_tpu.obs.costs import (
    CostLedger,
    RequestCost,
    current_cost,
    default_ledger,
    note_storage_read,
    request_cost,
)
from predictionio_tpu.obs.device import (
    DEVICE_EFFICIENCY,
    RECOMPILES,
    DevicePeaks,
    EfficiencyTracker,
    RecompileTracker,
    device_peaks,
    device_snapshot,
    jit_cost_analysis,
    wave_stage,
    wave_timeline,
)
from predictionio_tpu.obs.flight import FLIGHT, FlightRecorder, annotate
from predictionio_tpu.obs.incident import IncidentRecorder, load_bundle
from predictionio_tpu.obs.logging import (
    REQUEST_ID_HEADER,
    JsonLineFormatter,
    LogRing,
    configure_logging,
    get_log_ring,
    get_request_id,
    new_request_id,
    reset_request_context,
    set_request_context,
)
from predictionio_tpu.obs.metrics import (
    LATENCY_BUCKETS,
    REGISTRY,
    SIZE_BUCKETS,
    STAGE_BUCKETS,
    TRAIN_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsHistory,
    MetricsRegistry,
    default_registry,
    quantile_from_buckets,
)
from predictionio_tpu.obs.profiler import PROFILER, sample_runtime_gauges
from predictionio_tpu.obs.quality import (
    DriftDetector,
    HistogramSketch,
    QualityMonitor,
    default_quality,
)
from predictionio_tpu.obs.slo import SLOTracker
from predictionio_tpu.obs.tracing import (
    Span,
    clear_traces,
    current_span,
    install_jax_compile_listener,
    observe_span,
    recent_traces,
    trace,
)

__all__ = [
    "AlertEvaluator",
    "AlertRule",
    "DEVICE_EFFICIENCY",
    "DevicePeaks",
    "EfficiencyTracker",
    "FLIGHT",
    "FlightRecorder",
    "IncidentRecorder",
    "JsonLineFormatter",
    "LATENCY_BUCKETS",
    "LogRing",
    "PROFILER",
    "REGISTRY",
    "REQUEST_ID_HEADER",
    "SIZE_BUCKETS",
    "SLOTracker",
    "STAGE_BUCKETS",
    "TRAIN_BUCKETS",
    "CostLedger",
    "Counter",
    "DriftDetector",
    "Gauge",
    "Histogram",
    "HistogramSketch",
    "MetricsHistory",
    "MetricsRegistry",
    "QualityMonitor",
    "RECOMPILES",
    "RecompileTracker",
    "RequestCost",
    "Span",
    "annotate",
    "clear_traces",
    "configure_logging",
    "current_cost",
    "current_span",
    "default_ledger",
    "default_quality",
    "default_registry",
    "default_rule_pack",
    "load_bundle",
    "resolve_rules",
    "device_peaks",
    "device_snapshot",
    "jit_cost_analysis",
    "get_log_ring",
    "get_request_id",
    "install_jax_compile_listener",
    "new_request_id",
    "note_storage_read",
    "observe_span",
    "request_cost",
    "quantile_from_buckets",
    "recent_traces",
    "reset_request_context",
    "sample_runtime_gauges",
    "set_request_context",
    "trace",
    "wave_stage",
    "wave_timeline",
]
