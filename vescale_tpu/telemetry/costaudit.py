"""Plan-vs-reality cost auditing — the predict→measure→recalibrate loop.

Every planner in the stack prices decisions in microseconds — the
redistribution Dijkstra (redistribute_plan.py), the VSC127/128 quant-edge
competition, ``simulate_schedule``'s stage costs, the serve loop's retry
hints and the AOT memory budget — but a price nobody checks against a
measured run mis-ranks plans silently forever.  This module closes the
loop the measured-cost planning literature (Mesh-TensorFlow,
arXiv:1811.02084; "On Optimizing the Communication of Model Parallelism",
arXiv:2211.05322) assumes but never instruments:

  * **Prediction ledger** — :func:`record_prediction` appends a structured
    prediction (plan id, predicted µs/bytes, cost-model digest, unit) to a
    bounded ring; :func:`record_measurement` joins the measured outcome by
    plan id and folds the divergence ratio ``max(m/p, p/m)`` into per-kind
    decayed running means.
  * **Per-step auditor** — :func:`audit_step` (called by
    ``telemetry.record_step`` before the timeseries sample) publishes the
    divergence ratios as ``cost_model_*`` registry gauges — which the
    history store and the ``cost-model-drift`` alert rule
    (:func:`costaudit_rule_pack`) then see for free — and returns the
    joined summary that lands as the ``cost_audit`` field of a steps.jsonl
    line.
  * **Online calibration** — the auditor continuously harvests tagged span
    streams (the :data:`calibrate.SPAN_TAGS` contract: the calibrate
    sweep, the instrumented redistribute hops, the serve decode/prefill
    spans) into the active :class:`~.calibrate.CalibrationTable` with a
    decayed running mean and cadenced atomic persistence.  The digest in
    the planner's cache key makes re-planning automatic on rotation, so
    measured drift self-heals instead of warning.
  * **Per-layer roofline attribution** — :func:`layer_attribution` maps
    HLO op metadata (``op_name`` scopes) onto per-fused-region FLOPs/bytes
    estimates, classifies each layer compute- vs memory-bound against the
    device roofline, and :func:`attach_roofline_tracks` renders the result
    as Perfetto counter tracks.
  * **What-if scorer** — :func:`score_candidates` re-prices candidate
    (dp, tp, pp) meshes against the live audited table with per-bucket
    audit-backed confidence (``python -m vescale_tpu.analysis whatif``).

Gating contract (memtrack-style): ``record_prediction`` /
``record_measurement`` / ``audit_step`` / ``harvest`` are module-level
no-op function references while dormant — a run that never activates the
auditor pays one attribute load per call site and allocates nothing.
``telemetry.init()`` activates (``VESCALE_COSTAUDIT``), ``shutdown()``
restores the no-ops.
"""

from __future__ import annotations

import json
import os
import re
import threading
import time
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Sequence, Tuple

__all__ = [
    "record_prediction",
    "record_measurement",
    "audit_step",
    "harvest",
    "activate",
    "deactivate",
    "is_active",
    "get_auditor",
    "audit_summary",
    "costaudit_rule_pack",
    "CostAudit",
    "layer_attribution",
    "roofline_counter_events",
    "attach_roofline_tracks",
    "device_mem_gbps",
    "mesh_candidates",
    "score_candidates",
    "PLAN_ID_TAG",
]

# span tag naming the prediction a measured span belongs to (rides next to
# the calibrate SPAN_TAGS contract on instrumented redistribute hops)
PLAN_ID_TAG = "plan_id"


# ------------------------------------------------------- dormant no-op hooks
# Named module-level functions (never lambdas — VSC203): the planners call
# these unconditionally and an un-audited run must pay only the attribute
# load.  activate()/deactivate() swap the module attributes, memtrack-style.

def _noop_record_prediction(kind, predicted_us=None, predicted_bytes=None,
                            digest=None, unit="us", detail=None):
    return None


def _noop_record_measurement(plan_id, measured_us=None, measured_bytes=None):
    return None


def _noop_audit_step(kind=None):
    return None


def _noop_harvest(spans=None):
    return 0


record_prediction = _noop_record_prediction
record_measurement = _noop_record_measurement
audit_step = _noop_audit_step
harvest = _noop_harvest


# plan ids are PROCESS-monotonic, not per-auditor: plans cached in the
# planner outlive telemetry init/shutdown cycles, and a stale id must fall
# off the new ledger as unknown — never collide with a fresh prediction
_ID_LOCK = threading.Lock()
_NEXT_ID = 1


def _new_id() -> int:
    global _NEXT_ID
    with _ID_LOCK:
        i = _NEXT_ID
        _NEXT_ID += 1
        return i


class CostAudit:
    """The live auditor: bounded prediction ledger + divergence aggregates
    + the online-calibration harvester.  Exists only between
    :func:`activate` and :func:`deactivate` — its absence IS the off
    state."""

    def __init__(self, registry, *, depth: int = 256, threshold: float = 3.0,
                 decay: float = 0.25, cadence_s: float = 30.0,
                 harvest_spans: bool = True):
        self.registry = registry
        self.depth = max(1, int(depth))
        self.threshold = float(threshold)
        self.decay = float(decay)
        self.cadence_s = float(cadence_s)
        self.harvest_spans = bool(harvest_spans)
        self._lock = threading.Lock()
        self._ledger: "OrderedDict[int, Dict]" = OrderedDict()
        self._predicted = 0
        self._matched = 0
        self._divergence: Optional[float] = None          # decayed mean ratio
        self._by_kind: Dict[str, Dict[str, Any]] = {}
        self._bucket_div: Dict[Tuple[str, int, int], Dict[str, float]] = {}
        self._harvested = 0
        self._harvest_hwm = 0.0      # span-start high-water mark (no re-ingest)
        self._last_persist = time.monotonic()
        self._digest_rotations = 0

    # -------------------------------------------------------------- ledger
    def record_prediction(self, kind: str, predicted_us: Optional[float] = None,
                          predicted_bytes: Optional[float] = None,
                          digest: Optional[str] = None, unit: str = "us",
                          detail: Optional[Dict] = None) -> int:
        """Append one priced decision; returns the plan id the producer
        threads through its spans/measurement."""
        pid = _new_id()
        with self._lock:
            self._ledger[pid] = {
                "plan_id": pid,
                "kind": str(kind),
                "predicted_us": None if predicted_us is None else float(predicted_us),
                "predicted_bytes": None if predicted_bytes is None else float(predicted_bytes),
                "digest": digest,
                "unit": str(unit),
                "detail": detail,
                "ts": time.time(),
                "measured_us": None,
                "measured_bytes": None,
                "divergence": None,
            }
            while len(self._ledger) > self.depth:
                self._ledger.popitem(last=False)
            self._predicted += 1
            k = self._by_kind.setdefault(
                str(kind), {"predictions": 0, "matched": 0, "divergence": None}
            )
            k["predictions"] += 1
        if self.registry is not None:
            self.registry.counter("cost_model_predictions_total").inc()
        return pid

    def record_measurement(self, plan_id, measured_us: Optional[float] = None,
                           measured_bytes: Optional[float] = None) -> Optional[float]:
        """Join a measured outcome to its prediction.  Returns the
        divergence ratio ``max(m/p, p/m)`` when both sides are µs-priced
        and positive, else None.  Unknown/expired plan ids are ignored —
        the ring is bounded and the producer may outlive it."""
        if plan_id is None:
            return None
        ratio = None
        with self._lock:
            rec = self._ledger.get(plan_id)
            if rec is None:
                return None
            first = rec["measured_us"] is None and rec["measured_bytes"] is None
            rec["measured_us"] = None if measured_us is None else float(measured_us)
            rec["measured_bytes"] = (
                None if measured_bytes is None else float(measured_bytes)
            )
            if first:
                self._matched += 1
                self._by_kind[rec["kind"]]["matched"] += 1
            p, m = rec["predicted_us"], rec["measured_us"]
            if rec["unit"] == "bytes":  # byte-denominated (AOT memory budget)
                p, m = rec["predicted_bytes"], rec["measured_bytes"]
            if rec["unit"] in ("us", "bytes") and p and m and p > 0 and m > 0:
                ratio = max(m / p, p / m)
                rec["divergence"] = ratio
                self._divergence = self._fold(self._divergence, ratio)
                k = self._by_kind[rec["kind"]]
                k["divergence"] = self._fold(k["divergence"], ratio)
        if self.registry is not None:
            self.registry.counter("cost_model_matched_total").inc()
        return ratio

    def _fold(self, mean: Optional[float], ratio: float) -> float:
        """Decayed running mean of divergence ratios (same decay constant
        the calibration harvest uses)."""
        if mean is None:
            return float(ratio)
        a = min(1.0, max(0.0, self.decay))
        return mean + a * (ratio - mean)

    # ------------------------------------------------------------- auditor
    def audit_step(self, kind: Optional[str] = None) -> Optional[Dict]:
        """The per-step join: harvest fresh tagged spans, publish the
        divergence gauges (which the timeseries sample taken right after
        and the ``cost-model-drift`` rule read), and return the summary
        dict for the steps.jsonl ``cost_audit`` field — None when nothing
        has ever been priced or harvested (the jsonl line stays
        bit-identical to an un-audited run)."""
        if self.harvest_spans:
            self.harvest(None)
        with self._lock:
            predicted, matched = self._predicted, self._matched
            overall = self._divergence
            by_kind = {
                k: dict(v) for k, v in self._by_kind.items()
            }
            harvested = self._harvested
        if predicted == 0 and harvested == 0:
            return None
        reg = self.registry
        if reg is not None:
            if overall is not None:
                reg.gauge("cost_model_divergence").set(overall)
            for k, v in by_kind.items():
                if v["divergence"] is not None:
                    reg.gauge(f"cost_model_divergence_{k}").set(v["divergence"])
            reg.gauge("cost_model_unmatched").set(predicted - matched)
        out: Dict[str, Any] = {
            "predictions": predicted,
            "matched": matched,
            "divergence": overall,
            "harvested_spans": harvested,
        }
        if by_kind:
            out["by_kind"] = by_kind
        return out

    # -------------------------------------------------- online calibration
    def harvest(self, spans=None) -> int:
        """Fold tagged spans into the active CalibrationTable with the
        decayed running mean, note per-bucket divergence against the
        table's prior estimate, and persist atomically on cadence to the
        ``VESCALE_COST_CALIBRATION`` path.  ``spans=None`` peeks the live
        ndtimeline ring (high-water-marked by span start time, so repeated
        peeks never double-ingest).  Returns samples absorbed."""
        from ..ndtimeline import api as _nd
        from . import calibrate as _cal

        if spans is None:
            if not _nd.is_active():
                return 0
            spans = _nd.get_manager().tail(4096)
        fresh = []
        for s in spans:
            tags = getattr(s, "tags", None) or {}
            if not all(t in tags for t in _cal.SPAN_TAGS):
                continue
            start = float(getattr(s, "start", 0.0) or 0.0)
            if start <= self._harvest_hwm:
                continue
            fresh.append((start, s, tags))
        if not fresh:
            return 0
        hwm = max(f[0] for f in fresh)
        table = _cal.active_table()
        if table is None:
            self._harvest_hwm = hwm
            return 0
        old_digest = table.digest() if len(table) else None
        n = 0
        for _, s, tags in fresh:
            try:
                op = str(tags["collective_op"])
                ax = int(tags["axis_size"])
                nb = int(tags["bytes"])
                dur = float(s.duration)
            except (TypeError, ValueError):
                continue
            prior = table.lookup_us(op, ax, nb)
            table.add_sample(op, ax, nb, dur, decay=self.decay)
            us = dur * 1e6
            if prior and prior > 0 and us > 0:
                self._note_bucket(op, ax, nb, max(us / prior, prior / us))
            n += 1
        self._harvest_hwm = hwm
        if n == 0:
            return 0
        with self._lock:
            self._harvested += n
        reg = self.registry
        if reg is not None:
            reg.counter("cost_model_harvested_spans_total").inc(n)
        if old_digest is not None and table.digest() != old_digest:
            self._digest_rotations += 1
            if reg is not None:
                reg.counter("cost_model_digest_rotations_total").inc()
        self._maybe_persist(table)
        return n

    def _note_bucket(self, op: str, axis_size: int, nbytes: int, ratio: float) -> None:
        from .calibrate import _bucket

        key = (op, int(axis_size), _bucket(nbytes))
        cell = self._bucket_div.get(key)
        if cell is None:
            self._bucket_div[key] = {"ratio": float(ratio), "samples": 1}
        else:
            cell["ratio"] = self._fold(cell["ratio"], ratio)
            cell["samples"] += 1

    def _maybe_persist(self, table) -> None:
        from ..analysis import envreg

        path = envreg.get_str("VESCALE_COST_CALIBRATION")
        if not path:
            return
        now = time.monotonic()
        if now - self._last_persist < self.cadence_s:
            return
        try:
            table.save(path)  # atomic (tmp + os.replace) since the audit PR
            self._last_persist = now
            if self.registry is not None:
                self.registry.counter("cost_model_table_persists_total").inc()
        except OSError:
            pass  # a read-only path must not fail a step

    def persist_now(self, path: Optional[str] = None) -> Optional[str]:
        """Cadence-bypassing persist (shutdown flush / test hook)."""
        from . import calibrate as _cal
        from ..analysis import envreg

        table = _cal.active_table()
        target = path or envreg.get_str("VESCALE_COST_CALIBRATION")
        if table is None or not target:
            return None
        try:
            table.save(target)
        except OSError:
            return None
        self._last_persist = time.monotonic()
        return target

    # ------------------------------------------------------------ readouts
    def bucket_divergence(self) -> Dict[Tuple[str, int, int], Dict[str, float]]:
        """Audit history per (op, axis_size, byte bucket) — the what-if
        scorer's confidence input."""
        with self._lock:
            return {k: dict(v) for k, v in self._bucket_div.items()}

    def ledger(self) -> List[Dict]:
        with self._lock:
            return [dict(r) for r in self._ledger.values()]

    def summary(self) -> Dict[str, Any]:
        """The ``audit`` block: predicted-vs-measured rollup for the
        run's own plans."""
        with self._lock:
            return {
                "predictions": self._predicted,
                "matched": self._matched,
                "divergence": self._divergence,
                "by_kind": {k: dict(v) for k, v in self._by_kind.items()},
                "harvested_spans": self._harvested,
                "digest_rotations": self._digest_rotations,
                "ledger_depth": len(self._ledger),
            }


# ------------------------------------------------------------- activation
_AUDIT: Optional[CostAudit] = None


def _active_record_prediction(kind, predicted_us=None, predicted_bytes=None,
                              digest=None, unit="us", detail=None):
    a = _AUDIT
    if a is None:
        return None
    return a.record_prediction(kind, predicted_us=predicted_us,
                               predicted_bytes=predicted_bytes, digest=digest,
                               unit=unit, detail=detail)


def _active_record_measurement(plan_id, measured_us=None, measured_bytes=None):
    a = _AUDIT
    if a is None:
        return None
    return a.record_measurement(plan_id, measured_us=measured_us,
                                measured_bytes=measured_bytes)


def _active_audit_step(kind=None):
    a = _AUDIT
    if a is None:
        return None
    return a.audit_step(kind)


def _active_harvest(spans=None):
    a = _AUDIT
    if a is None:
        return 0
    return a.harvest(spans)


def costaudit_rule_pack(threshold: float = 3.0) -> List:
    """The ``cost-model-drift`` rule: sustained predicted-vs-measured
    divergence beyond ``threshold`` (a ratio — 3.0 means the cost model is
    off by 3x in either direction) over the gauge the auditor publishes
    every step.  Self-healing context rides in the message: online
    recalibration rotates the digest, so a firing rule that later resolves
    means the table corrected itself."""
    from .alerts import ThresholdRule

    return [
        ThresholdRule(
            "cost-model-drift", "cost_model_divergence", ">", float(threshold),
            window_s=60.0, reducer="last", for_s=0.0, severity="warning",
            message=(
                "cost model predictions diverge from measured outcomes by "
                f"more than {threshold:g}x (decayed mean of max(m/p, p/m)); "
                "online recalibration is folding measured spans back into "
                "the calibration table — sustained firing means the spans "
                "the planner prices are not the spans it produces"
            ),
        )
    ]


def activate(registry=None, *, depth: Optional[int] = None,
             threshold: Optional[float] = None, decay: Optional[float] = None,
             cadence_s: Optional[float] = None,
             harvest_spans: Optional[bool] = None) -> CostAudit:
    """Swap the live hooks in (telemetry.init's job; knobs default to the
    ``VESCALE_COSTAUDIT_*`` envreg family) and arm the drift rule when the
    alert engine is live."""
    global _AUDIT, record_prediction, record_measurement, audit_step, harvest
    from ..analysis import envreg

    a = CostAudit(
        registry,
        depth=depth if depth is not None else envreg.get_int("VESCALE_COSTAUDIT_DEPTH"),
        threshold=(threshold if threshold is not None
                   else envreg.get_float("VESCALE_COSTAUDIT_THRESHOLD")),
        decay=decay if decay is not None else envreg.get_float("VESCALE_COSTAUDIT_DECAY"),
        cadence_s=(cadence_s if cadence_s is not None
                   else envreg.get_float("VESCALE_COSTAUDIT_CADENCE_S")),
        harvest_spans=(harvest_spans if harvest_spans is not None
                       else envreg.get_bool("VESCALE_COSTAUDIT_HARVEST")),
    )
    _AUDIT = a
    record_prediction = _active_record_prediction
    record_measurement = _active_record_measurement
    audit_step = _active_audit_step
    harvest = _active_harvest
    from . import alerts as _alerts

    eng = _alerts.get_engine()
    if eng is not None:
        eng.arm_pack("costaudit", costaudit_rule_pack(a.threshold))
    return a


def deactivate() -> None:
    """Restore the dormant no-op hooks (telemetry.shutdown's job)."""
    global _AUDIT, record_prediction, record_measurement, audit_step, harvest
    _AUDIT = None
    record_prediction = _noop_record_prediction
    record_measurement = _noop_record_measurement
    audit_step = _noop_audit_step
    harvest = _noop_harvest


def is_active() -> bool:
    return _AUDIT is not None


def get_auditor() -> Optional[CostAudit]:
    return _AUDIT


def audit_summary() -> Optional[Dict]:
    """Module-level summary (the audit block); None while dormant."""
    a = _AUDIT
    return a.summary() if a is not None else None


# ----------------------------------------------- per-layer roofline model
# HLO-text parsing: one instruction per line, `%name = dtype[dims]... opcode(
# %operand, ...)`, layer names recovered from metadata op_name scopes.  An
# ESTIMATE by construction (fused-computation bodies contribute their own
# shapes, so bytes overcount vs XLA's exact accounting) — attribution, not
# accounting.

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s4": 1, "u4": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "f32": 4, "s32": 4, "u32": 4,
    "f64": 8, "s64": 8, "u64": 8, "c64": 8,
    "c128": 16,
}
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([A-Za-z0-9_.\-]+)\s*=\s*([a-z0-9]+)\[([0-9,]*)\]\S*\s+"
    r"([a-z0-9\-]+)\("
)
_OPNAME_RE = re.compile(r'op_name="([^"]+)"')
_OPERAND_RE = re.compile(r"%([A-Za-z0-9_.\-]+)")
_WRAPPER_SEG_RE = re.compile(r"^[\w.\-]+\(.*\)$")

# opcodes whose flops we model (2 * lhs_elems * out_last_dim — exact for a
# plain matmul, an attribution-grade estimate for batched/convolved forms)
_MATMUL_OPCODES = ("dot", "convolution")


def device_mem_gbps(device) -> float:
    """HBM bandwidth (GB/s) of one chip — the roofline's memory roof, from
    the same table as the compute peak (``calibrate.DEVICE_PEAKS``)."""
    from . import calibrate as _cal

    return float(_cal.device_peaks(device)["hbm_gbps"])


def _layer_of(op_name: str) -> str:
    """Layer key from an HLO op_name scope path: drop wrapper frames
    (``jit(step)``, ``jvp(...)``, ``transpose(...)``), keep the first two
    model-scope segments above the op itself."""
    segs = [p for p in op_name.split("/") if p and not _WRAPPER_SEG_RE.match(p)]
    if not segs:
        return "<unattributed>"
    head = segs[:-1] or segs
    return "/".join(head[:2])


def layer_attribution(hlo_text: str, device=None, peak_flops: Optional[float] = None,
                      mem_gbps: Optional[float] = None) -> Dict[str, Any]:
    """Per-layer FLOPs/bytes attribution of an HLO module, classified
    compute- vs memory-bound against the device roofline.

    Returns ``{"layers": [{layer, flops, bytes, ops, intensity, bound,
    est_us}...] (est_us-descending), "ridge_flops_per_byte", "peak_flops",
    "mem_gbps", "total_flops", "total_bytes"}``."""
    if peak_flops is None or mem_gbps is None:
        if device is None:
            import jax

            device = jax.devices()[0]
        from . import calibrate as _cal

        if peak_flops is None:
            peak_flops = _cal.device_peak_flops(device)
        if mem_gbps is None:
            mem_gbps = device_mem_gbps(device)
    bw = float(mem_gbps) * 1e9
    ridge = float(peak_flops) / bw

    shapes: Dict[str, Tuple[int, int]] = {}  # name -> (elems, bytes)
    parsed = []
    for line in hlo_text.splitlines():
        m = _INSTR_RE.match(line)
        if m is None:
            continue
        name, dtype, dims, opcode = m.groups()
        if dtype not in _DTYPE_BYTES:
            continue  # tuple/token/opaque results: no payload to attribute
        elems = 1
        for d in dims.split(","):
            if d:
                elems *= int(d)
        out_bytes = elems * _DTYPE_BYTES[dtype]
        shapes[name] = (elems, out_bytes)
        op_name_m = _OPNAME_RE.search(line)
        rest = line[m.end():]
        operands = [o for o in _OPERAND_RE.findall(rest.split("),", 1)[0])
                    if o != name]
        parsed.append((name, dims, opcode, elems, out_bytes,
                       op_name_m.group(1) if op_name_m else None, operands))

    per_layer: Dict[str, Dict[str, float]] = {}
    for name, dims, opcode, elems, out_bytes, op_name, operands in parsed:
        if op_name is None:
            continue  # parameters/infra ops without a model scope
        layer = _layer_of(op_name)
        acc = per_layer.setdefault(layer, {"flops": 0.0, "bytes": 0.0, "ops": 0})
        nbytes = float(out_bytes)
        for o in operands:
            sh = shapes.get(o)
            if sh is not None:
                nbytes += sh[1]
        flops = 0.0
        if opcode in _MATMUL_OPCODES and operands:
            lhs = shapes.get(operands[0])
            if lhs is not None:
                last = int(dims.split(",")[-1]) if dims else 1
                flops = 2.0 * lhs[0] * max(1, last)
        acc["flops"] += flops
        acc["bytes"] += nbytes
        acc["ops"] += 1

    layers = []
    total_flops = total_bytes = 0.0
    for layer, acc in per_layer.items():
        total_flops += acc["flops"]
        total_bytes += acc["bytes"]
        intensity = acc["flops"] / acc["bytes"] if acc["bytes"] else 0.0
        est_us = max(acc["flops"] / peak_flops, acc["bytes"] / bw) * 1e6
        layers.append({
            "layer": layer,
            "flops": acc["flops"],
            "bytes": acc["bytes"],
            "ops": int(acc["ops"]),
            "intensity": intensity,
            "bound": "compute" if intensity > ridge else "memory",
            "est_us": est_us,
        })
    layers.sort(key=lambda r: (-r["est_us"], r["layer"]))
    return {
        "layers": layers,
        "ridge_flops_per_byte": ridge,
        "peak_flops": float(peak_flops),
        "mem_gbps": float(mem_gbps),
        "total_flops": total_flops,
        "total_bytes": total_bytes,
    }


def roofline_counter_events(attribution: Dict, pid: int = 0,
                            ts0: float = 0.0) -> List[Dict]:
    """Chrome-trace ``C`` (counter) events rendering the attribution as
    per-layer roofline tracks: one ``roofline:<layer>`` counter per layer,
    laid out sequentially by estimated time so the track reads as a
    time-weighted layer walk."""
    evs = []
    ts = float(ts0)
    for lay in attribution.get("layers", ()):
        evs.append({
            "ph": "C", "pid": int(pid), "ts": ts,
            "name": f"roofline:{lay['layer']}",
            "args": {
                "est_us": round(lay["est_us"], 3),
                "flops_per_byte": round(lay["intensity"], 3),
                "bound": 1.0 if lay["bound"] == "compute" else 0.0,
            },
        })
        ts += max(1.0, lay["est_us"])
    return evs


def attach_roofline_tracks(perfetto_path: str, attribution: Dict,
                           pid: int = 0) -> int:
    """Append the roofline counter tracks to an existing Perfetto JSON
    trace (atomically), starting after its last event.  Returns the number
    of counter events added."""
    with open(perfetto_path) as f:
        data = json.load(f)
    evs = data.setdefault("traceEvents", [])
    ts0 = 0.0
    for e in evs:
        if isinstance(e, dict):
            ts0 = max(ts0, float(e.get("ts", 0) or 0) + float(e.get("dur", 0) or 0))
    added = roofline_counter_events(attribution, pid=pid, ts0=ts0)
    evs.extend(added)
    tmp = perfetto_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(data, f)
    os.replace(tmp, perfetto_path)
    return len(added)


# -------------------------------------------------------- what-if scoring
def mesh_candidates(num_devices: int) -> List[Tuple[int, int, int]]:
    """Every (dp, tp, pp) factorization of ``num_devices``."""
    out = []
    n = max(1, int(num_devices))
    for dp in range(1, n + 1):
        if n % dp:
            continue
        rest = n // dp
        for tp in range(1, rest + 1):
            if rest % tp:
                continue
            out.append((dp, tp, rest // tp))
    return out


def score_candidates(candidates: Sequence[Tuple[int, int, int]], *,
                     params_bytes: float, activation_bytes: float,
                     flops_per_step: float, table=None, device=None,
                     auditor: Optional[CostAudit] = None) -> List[Dict]:
    """Re-price candidate (dp, tp, pp) meshes against the live audited
    table: per-candidate predicted step time (compute + the collective
    terms its layout implies) with audit-backed confidence — the decayed
    divergence history of exactly the cost buckets the candidate depends
    on.  Analytic-fallback terms score low confidence (0.25), measured-
    but-never-audited buckets medium (0.5), audited buckets ``1/ratio``.
    Returns the candidates ranked by predicted step time."""
    from . import calibrate as _cal
    from .. import collectives as C

    if table is None:
        table = _cal.active_table()
    if auditor is None:
        auditor = _AUDIT
    if device is None:
        import jax

        device = jax.devices()[0]
    peak = _cal.device_peak_flops(device)
    bdiv = auditor.bucket_divergence() if auditor is not None else {}
    usable = table is not None and len(table) > 0
    results = []
    for dp, tp, pp in candidates:
        world = max(1, dp * tp * pp)
        compute_us = float(flops_per_step) / world / peak * 1e6
        terms: List[Tuple[str, int, float]] = []
        if dp > 1:  # data-parallel gradient reduction over the dp axis
            terms.append(("all_reduce", dp, float(params_bytes) / max(1, tp * pp)))
        if tp > 1:  # tensor-parallel activation gather + grad scatter
            shard = float(activation_bytes) / tp
            terms.append(("all_gather", tp, shard))
            terms.append(("reduce_scatter", tp, shard))
        if pp > 1:  # stage-boundary p2p per microbatch wave
            terms.append(("ppermute", pp, float(activation_bytes)))
        comm_us = 0.0
        notes = []
        scores = []
        for op, ax, nb in terms:
            us = table.lookup_us(op, ax, int(nb)) if usable else None
            if us is None:
                us = C.analytic_cost_us(op, nb / 1e9, ax)
                source, score = "analytic", 0.25
            else:
                key = (op, ax, _cal._bucket(int(nb)))
                d = bdiv.get(key)
                if d is None:
                    source, score = "measured", 0.5
                else:
                    source = "audited"
                    score = max(0.0, min(1.0, 1.0 / max(1.0, d["ratio"])))
            comm_us += us
            scores.append(score)
            notes.append({"op": op, "axis_size": ax, "bytes": int(nb),
                          "us": us, "source": source})
        results.append({
            "mesh": {"dp": dp, "tp": tp, "pp": pp},
            "predicted_step_us": compute_us + comm_us,
            "compute_us": compute_us,
            "comm_us": comm_us,
            "confidence": sum(scores) / len(scores) if scores else 1.0,
            "terms": notes,
        })
    results.sort(key=lambda r: r["predicted_step_us"])
    return results
