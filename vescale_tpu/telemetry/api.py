"""Telemetry global API — the zero-overhead gate.

Mirrors the ndtimeline activation contract (ndtimeline/api.py): the runtime
wiring (train step, pipe engine, optimizer, checkpoint) calls the helpers
here on every operation, and a run that never calls ``telemetry.init()``
must pay nothing — ``is_active()`` is a single module-global check, no
registry, no ring buffers, no locks, no files are ever created.

    from vescale_tpu import telemetry

    telemetry.init(out_dir="/tmp/run0")        # flip the gate
    ... train ...                              # steps stream to steps.jsonl
    print(telemetry.dashboard())               # human summary
    telemetry.prometheus_dump()                # prometheus text exposition
    telemetry.shutdown()

Per-step records land in ``<out_dir>/steps.jsonl`` (one JSON object per
step); ``write_step_report`` drops compile-time program reports next to
them.  All helpers are no-ops (returning None) while dormant.
"""

from __future__ import annotations

import os
import time
from typing import Any, Callable, Dict, Optional

from . import alerts as _alerts
from . import costaudit as _costaudit
from . import memtrack as _memtrack
from . import timeseries as _timeseries
from .exporters import JsonlExporter, dashboard as _dashboard, prometheus_text
from .registry import MetricsRegistry

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
]


class TelemetryState:
    """Everything a live telemetry run owns.  Exists ONLY between ``init``
    and ``shutdown`` — its absence IS the off state."""

    def __init__(
        self,
        out_dir: Optional[str],
        rank: int,
        window: int,
        jsonl: bool,
    ):
        self.out_dir = out_dir
        self.rank = rank
        self.registry = MetricsRegistry(default_window=window)
        self.step = 0
        self.jsonl: Optional[JsonlExporter] = None
        self.memtrack = None  # set by init() when memory tracking is on
        self.timeseries = None  # set by init() when the history store is on
        self.alerts = None  # set by init() when the alert engine is on
        self.costaudit = None  # set by init() when cost auditing is on
        self.last_step_report: Optional[Dict] = None  # flight-recorder feed
        if jsonl and out_dir is not None:
            os.makedirs(out_dir, exist_ok=True)
            self.jsonl = JsonlExporter(os.path.join(out_dir, "steps.jsonl"))


_STATE: Optional[TelemetryState] = None


def init(
    out_dir: Optional[str] = None,
    rank: int = 0,
    window: int = 1024,
    jsonl: bool = True,
    memtrack: bool = True,
    memtrack_interval: int = 1,
    memtrack_history: int = 16,
    memtrack_leak_steps: int = 5,
    timeseries: Optional[bool] = None,
    timeseries_cadence_s: Optional[float] = None,
    alerts: Optional[bool] = None,
    costaudit: Optional[bool] = None,
) -> TelemetryState:
    """Activate telemetry.  ``out_dir=None`` keeps everything in-memory
    (registry only — no JSONL stream, no report files).  Re-initializing
    while active closes the previous state's stream first (its registry is
    discarded).

    ``memtrack`` (default on) also activates memory tracking (memtrack.py):
    live HBM gauges + tagged live-array census sampled every
    ``memtrack_interval`` steps, a ``memtrack_history``-deep sample ring for
    the OOM flight recorder, and a leak warning after
    ``memtrack_leak_steps`` consecutive steps of monotonic untagged
    growth.

    ``timeseries``/``alerts`` (default: the ``VESCALE_TIMESERIES`` /
    ``VESCALE_ALERTS`` knobs, both on) also activate the metric history
    store (timeseries.py) and the SLO alert engine (alerts.py) — the
    engine evaluates over the store, so ``alerts`` implies nothing
    without ``timeseries`` except manual (code-raised) alerts.

    ``costaudit`` (default: ``VESCALE_COSTAUDIT``, on) also activates the
    plan-vs-reality cost auditor (costaudit.py): a prediction ledger every
    priced plan records into, a per-step predicted-vs-measured join
    publishing ``cost_model_*`` divergence gauges and the
    ``cost-model-drift`` rule, and the online calibration harvest feeding
    measured spans back into the active CalibrationTable."""
    global _STATE
    if _STATE is not None:
        shutdown()
    from ..analysis import envreg

    _STATE = TelemetryState(out_dir, rank, window, jsonl)
    if memtrack:
        _STATE.memtrack = _memtrack.activate(
            history=memtrack_history,
            leak_steps=memtrack_leak_steps,
            census_interval=memtrack_interval,
        )
    if timeseries is None:
        timeseries = envreg.get_bool("VESCALE_TIMESERIES")
    if alerts is None:
        alerts = envreg.get_bool("VESCALE_ALERTS")
    if timeseries:
        _STATE.timeseries = _timeseries.activate(
            _STATE.registry,
            cadence_s=(
                timeseries_cadence_s
                if timeseries_cadence_s is not None
                else envreg.get_float("VESCALE_TIMESERIES_CADENCE_S")
            ),
            base_len=envreg.get_int("VESCALE_TIMESERIES_BASE_LEN"),
            tier_factor=envreg.get_int("VESCALE_TIMESERIES_TIER_FACTOR"),
            tiers=envreg.get_int("VESCALE_TIMESERIES_TIERS"),
        )
    if alerts:
        _STATE.alerts = _alerts.activate(
            store=_STATE.timeseries,
            history=envreg.get_int("VESCALE_ALERTS_HISTORY"),
            min_eval_interval_s=envreg.get_float("VESCALE_ALERTS_EVAL_INTERVAL_S"),
        )
    if costaudit is None:
        costaudit = envreg.get_bool("VESCALE_COSTAUDIT")
    if costaudit:
        # after alerts: activation arms the cost-model-drift rule on the
        # live engine when there is one
        _STATE.costaudit = _costaudit.activate(_STATE.registry)
    return _STATE


def shutdown() -> None:
    """Deactivate and release the gate; flushes/closes the JSONL stream
    and restores the memtrack no-op hooks."""
    global _STATE
    if _STATE is not None and _STATE.jsonl is not None:
        _STATE.jsonl.close()
    _costaudit.deactivate()
    _memtrack.deactivate()
    _alerts.deactivate()
    _timeseries.deactivate()
    _STATE = None


def is_active() -> bool:
    return _STATE is not None


def get_state() -> Optional[TelemetryState]:
    return _STATE


def get_registry() -> Optional[MetricsRegistry]:
    return _STATE.registry if _STATE is not None else None


# ------------------------------------------------------------- hot helpers
# Each is a one-branch no-op while dormant: the runtime wiring calls these
# unconditionally and un-instrumented runs must not allocate or lock.

def record_step(metrics: Dict[str, Any], kind: str = "train") -> None:
    """Ingest one step's metrics (the train.py feed; the serve loop's
    decode steps pass ``kind="serve"``).

    Train conventions: ``step_time_s`` feeds the step-time histogram,
    ``tokens`` the throughput counters, scalar floats become gauges.  The
    full record (plus ``step``/``rank``/``ts``) appends to steps.jsonl.

    ``kind="serve"`` skips the train_* registry conventions (the serve
    loop feeds its own ``serve_*`` metrics directly) but keeps everything
    structural: the step counter, the memory sample and the per-step
    ``spans`` rollup — so a decode step's spans land on a steps.jsonl line
    of their OWN step instead of smearing onto a stale training step."""
    st = _STATE
    if st is None:
        return
    st.step = int(metrics.get("step", st.step + 1))
    reg = st.registry
    if kind == "train":
        reg.counter("train_steps_total").inc()
        if "step_time_s" in metrics:
            reg.histogram("train_step_time_seconds").observe(metrics["step_time_s"])
        if "tokens" in metrics:
            reg.counter("train_tokens_total").inc(metrics["tokens"])
        if "tokens_per_sec" in metrics:
            reg.gauge("train_tokens_per_sec").set(metrics["tokens_per_sec"])
        for key, gname in (
            ("loss", "train_loss"),
            ("grad_norm", "train_grad_norm"),
            ("loss_scale", "train_loss_scale"),
            ("skip_count", "train_skipped_steps"),
        ):
            if key in metrics and metrics[key] is not None:
                reg.gauge(gname).set(float(metrics[key]))
        if metrics.get("overflow"):
            reg.counter("train_overflow_steps_total").inc()
    mem = None
    if st.memtrack is not None:
        # per-step memory sample: device gauges, tagged census, leak check
        # (None on census-interval skip steps — the jsonl line just omits it)
        mem = st.memtrack.on_step(st.step, reg)
    # the step boundary IS the sampling/evaluation boundary: the cost
    # auditor joins predicted-vs-measured and publishes its divergence
    # gauges FIRST so the history sample taken right after (and the
    # cost-model-drift rule evaluating over it) sees this step's numbers;
    # the store keeps at most one sample per cadence and the engine
    # rate-limits itself, so a kHz decode loop pays three no-op-ish calls
    # per step (dormant runs pay the no-op hook references — the memtrack
    # contract)
    audit = _costaudit.audit_step(kind)
    _timeseries.sample(kind)
    _alerts.evaluate()
    if st.jsonl is not None:
        rec = {"step": st.step, "rank": st.rank, "ts": time.time(), **metrics}
        if kind != "train":
            rec["kind"] = kind
        if mem is not None:
            rec["memory"] = mem
        spans = _step_spans()
        if spans is not None:
            rec["spans"] = spans
        if audit is not None:
            rec["cost_audit"] = audit
        st.jsonl.emit(rec)


def _step_spans():
    """Per-metric span rollup of the step being recorded, when the
    ndtimeline profiler is live — the ``spans`` object of a steps.jsonl
    line (``{metric: {count, total_ms}}``).  None (and zero cost) when the
    profiler is dormant; the manager's ring is PEEKED, never drained, so
    the flush a handler expects still sees every span."""
    from ..ndtimeline.api import is_active as _nd_active

    if not _nd_active():
        return None
    from .trace import step_span_summary

    return step_span_summary()


def record_event(kind: str, **fields) -> None:
    """Append a non-step EVENT line to steps.jsonl (recovery events:
    restarts, rollbacks, preemptions, quarantines — the resilience loop's
    feed).  Events carry ``{"event": kind, "step": <current>, ...fields}``
    so a dashboard tailing the stream can interleave them with step
    records.  No-op while dormant or without an out_dir stream."""
    st = _STATE
    if st is None or st.jsonl is None:
        return
    st.jsonl.emit(
        {"event": kind, "step": st.step, "rank": st.rank, "ts": time.time(), **fields}
    )


def observe(name: str, value: float) -> None:
    if _STATE is not None:
        _STATE.registry.histogram(name).observe(value)


def count(name: str, n: float = 1) -> None:
    if _STATE is not None:
        _STATE.registry.counter(name).inc(n)


def set_gauge(name: str, value: float) -> None:
    if _STATE is not None:
        _STATE.registry.gauge(name).set(value)


# ----------------------------------------------------------------- outputs
def write_step_report(
    name: str, fn: Callable, *args, aot_report=None, **kwargs
) -> Optional[Dict]:
    """Build a compile-time step report (see step_report.py) and — when an
    ``out_dir`` is configured — persist it as ``<out_dir>/<name>_report.json``.
    No-op while dormant.

    ``aot_report``: path to (or loaded dict of) an ahead-of-time
    compile report of the same program — the report gains an ``aot_drift`` section
    diffing the compiled step's memory footprint against the AOT budget,
    and drift beyond 10% warns (see memory_report.compare_with_aot)."""
    st = _STATE
    if st is None:
        return None
    from .step_report import build_step_report, write_step_report as _write

    report = build_step_report(fn, *args, name=name, aot_report=aot_report, **kwargs)
    st.last_step_report = report  # flight-recorder forensics feed
    if st.out_dir is not None:
        _write(report, os.path.join(st.out_dir, f"{name}_report.json"))
    if report.get("flops") is not None:
        st.registry.gauge(f"step_report_{name}_flops").set(report["flops"])
    if report.get("peak_bytes") is not None:
        st.registry.gauge(f"step_report_{name}_peak_bytes").set(report["peak_bytes"])
    drift = report.get("aot_drift")
    if drift is not None:
        # the AOT memory budget is a priced plan too: ledger it so the
        # train path always has joined predictions (instant join — both
        # sides are known at compile time)
        pid = _costaudit.record_prediction(
            "aot_memory", predicted_bytes=drift["aot_bytes"], unit="bytes",
            detail={"name": name, "source": drift["aot_source"]},
        )
        _costaudit.record_measurement(pid, measured_bytes=drift["measured_bytes"])
        st.registry.gauge(f"step_report_{name}_aot_drift_frac").set(drift["drift_frac"])
        if drift["exceeds_tolerance"]:
            # the AOT-drift watcher routes through the alert engine (ONE
            # lifecycle for every watcher); with the engine off this
            # degrades to the legacy one-shot warning
            _alerts.raise_alert(
                f"aot-drift-{name}",
                message=(
                    f"step report {name!r}: compiled memory footprint "
                    f"{drift['measured_bytes']:.3e} B drifts "
                    f"{drift['drift_frac'] * 100:+.1f}% from the AOT budget "
                    f"{drift['aot_bytes']:.3e} B ({drift['aot_source']}) — "
                    "beyond the 10% tolerance; re-derive the AOT report or "
                    "find the regression."
                ),
                severity="warning",
                value=drift["drift_frac"],
            )
        else:
            _alerts.resolve(f"aot-drift-{name}")
    return report


def prometheus_dump(path: Optional[str] = None) -> Optional[str]:
    """Prometheus text exposition of the live registry; writes to ``path``
    (default ``<out_dir>/metrics.prom``) when an out_dir is configured.
    Returns the text, or None while dormant."""
    st = _STATE
    if st is None:
        return None
    text = prometheus_text(st.registry)
    target = path or (os.path.join(st.out_dir, "metrics.prom") if st.out_dir else None)
    if target is not None:
        os.makedirs(os.path.dirname(target) or ".", exist_ok=True)
        with open(target, "w") as f:
            f.write(text)
    return text


def dashboard(title: str = "vescale_tpu telemetry") -> Optional[str]:
    return _dashboard(_STATE.registry, title) if _STATE is not None else None
