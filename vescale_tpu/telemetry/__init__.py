"""vescale_tpu.telemetry — unified runtime telemetry.

Three observability signals, one pipeline (docs/observability.md):

  1. **Metrics registry** (registry.py): counters / gauges / rolling-window
     histograms fed per-step by the train step, pipe engine,
     DistributedOptimizer and checkpoint layer.
  2. **Compile-time step reports** (step_report.py): one JSON per compiled
     program — FLOPs, peak HBM, argument/output/temp bytes, collective
     counts (shared counter with debug/comm_mode).
  3. **Exporters** (exporters.py): per-step JSONL stream, Prometheus text
     exposition, human-readable dashboard — plus a **straggler detector**
     (straggler.py) over the ndtimeline streamer's cross-rank spans.
  4. **Memory tracking** (memtrack.py + memory_report.py): live HBM gauges
     (host-RSS fallback), owner-tagged live-array census, leak detection,
     AOT-budget drift, and the OOM **flight recorder** (forensic JSON dump
     on RESOURCE_EXHAUSTED or via ``dump_now()``).
  5. **Distributed trace timeline + cost calibration** (trace.py +
     calibrate.py): cross-rank clock-offset estimation, merged Perfetto
     traces with per-step critical paths and pipeline bubble fraction, and
     the measured collective-cost table (``collective_calibration.json``)
     that re-prices the redistribution planner, the quant-edge competition
     and ``simulate_schedule`` from wall-clock data
     (``VESCALE_COST_CALIBRATION``).
  6. **Plan-vs-reality cost auditing** (costaudit.py): a bounded
     prediction ledger every priced plan records into, a per-step
     predicted-vs-measured join publishing ``cost_model_*`` divergence
     gauges + the ``cost-model-drift`` rule, online calibration harvest
     (measured spans fold back into the table, digest rotation re-plans),
     per-layer roofline attribution and the what-if mesh scorer
     (``VESCALE_COSTAUDIT``).

Gating contract (same as ndtimeline): a run that never calls
``telemetry.init()`` pays zero overhead — no registry, no locks, no files,
no tag registry (the memtrack hooks are no-op function references).
"""

from . import calibrate, costaudit, memtrack, ops_server, trace
from .api import (
    count,
    dashboard,
    get_registry,
    get_state,
    init,
    is_active,
    observe,
    prometheus_dump,
    record_event,
    record_step,
    set_gauge,
    shutdown,
    write_step_report,
)
from .exporters import JsonlExporter, parse_prometheus_text, prometheus_text
from .hoststat import host_sched_delta, host_sched_stats
from .memory_report import compare_with_aot, device_memory_stats
from .memtrack import dump_now, flight_recorder, tagged
from .registry import Counter, Gauge, Histogram, MetricsRegistry
from .step_report import build_step_report, read_step_report
from .straggler import StragglerDetector

__all__ = [
    "init",
    "shutdown",
    "is_active",
    "get_state",
    "get_registry",
    "record_step",
    "record_event",
    "observe",
    "count",
    "set_gauge",
    "write_step_report",
    "prometheus_dump",
    "dashboard",
    "MetricsRegistry",
    "Counter",
    "Gauge",
    "Histogram",
    "JsonlExporter",
    "prometheus_text",
    "parse_prometheus_text",
    "build_step_report",
    "read_step_report",
    "StragglerDetector",
    "memtrack",
    "trace",
    "calibrate",
    "costaudit",
    "ops_server",
    "flight_recorder",
    "dump_now",
    "tagged",
    "compare_with_aot",
    "device_memory_stats",
    "host_sched_stats",
    "host_sched_delta",
]
