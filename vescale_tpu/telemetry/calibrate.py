"""Measured collective-cost calibration — replace guessed bandwidth factors
with wall-clock data.

Every planning decision in the framework is priced by a cost model: the
redistribution planner's Dijkstra weights (redistribute_plan.py), the
VSC127/128 quant-vs-dense edge competition, ``simulate_schedule``'s stage
costs, and shardcheck's VSC101 materialization pricing all bottom out in the
bandwidth-factor functions of ``collectives.py`` — constants tuned for a TPU
ICI link that have never been checked against a measured step.  Mesh-
TensorFlow (arXiv:1811.02084) and "On Optimizing the Communication of Model
Parallelism" (arXiv:2211.05322) both frame layout search as optimization
over a communication cost model; a cost model nobody has measured cannot
anchor a search.

This module is the measurement half:

  * :class:`CalibrationTable` — per ``(op, mesh-axis size, byte bucket)``
    measured wall-times (microseconds), plus the mesh it was measured on,
    a matmul-throughput sample (FLOPs -> us conversion for stage costs) and
    a content digest so perf records can name the cost model that priced
    them.
  * :func:`calibrate` — a targeted sweep: run each collective over each
    mesh axis at a ladder of byte buckets, ``block_until_ready``-timed,
    recording ndtimeline spans tagged with the measurement (so the sweep
    itself is trace-visible and :meth:`CalibrationTable.ingest_spans` can
    harvest ANY span stream carrying the same tag contract).
  * ``collective_calibration.json`` persistence (:meth:`save` /
    :func:`load_table`).
  * The consumption contract: ``VESCALE_COST_CALIBRATION=<path>`` (or
    :func:`set_active`) arms calibrated mode; :func:`collective_cost_us`
    answers lookups with log-log interpolation between byte buckets and
    returns ``None`` — after a ONE-TIME warning per (op, axis size) — when
    a bucket is missing, so every caller keeps its analytic fallback.  A
    table measured on a different mesh shape is STALE: it warns once and
    behaves as absent.  An EMPTY table (or no table) leaves every consumer
    bit-identical to the analytic model — calibration can only be additive.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import math
import os
import threading
import time
import warnings
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = [
    "CalibrationTable",
    "calibrate",
    "load_table",
    "set_active",
    "reset_active",
    "active_table",
    "table_for",
    "collective_cost_us",
    "table_cost_us",
    "compute_cost_us",
    "active_digest",
    "hop_latency_us",
    "DEVICE_PEAKS",
    "device_peaks",
    "device_peak_flops",
    "clear_warned",
    "TABLE_FILENAME",
    "CALIBRATION_OPS",
]

TABLE_FILENAME = "collective_calibration.json"
FORMAT_VERSION = 1

# the ops the sweep measures — the vocabulary of the planner's edge kinds
# (collective_permute prices as all_to_all's wire pattern; ppermute is the
# p2p hop simulate_schedule's comm term reads)
CALIBRATION_OPS = ("all_reduce", "all_gather", "reduce_scatter", "all_to_all", "ppermute")

# span tag contract: any span carrying these tags is a calibration sample
# (the sweep emits them; a runtime wrapper may too)
SPAN_TAGS = ("collective_op", "axis_size", "bytes")

# flat per-hop dispatch/launch overhead in calibrated (us-denominated) mode —
# the analytic model's _HOP_LATENCY analog.  Overridable per table
# (meta["launch_us"], measured by the sweep's smallest bucket residual).
DEFAULT_LAUNCH_US = 2.0


def _bucket(nbytes: int) -> int:
    """Canonical byte bucket: the power of two at or below ``nbytes``
    (bucket 1 for anything sub-byte).  Buckets key measurements; lookups
    interpolate between them in log-log space."""
    n = max(1, int(nbytes))
    return 1 << (n.bit_length() - 1)


@dataclasses.dataclass
class CalibrationTable:
    """Measured ``(op, axis_size, byte bucket) -> wall microseconds``.

    ``entries`` values are ``{"us": float, "samples": int}`` running means —
    harvesting more spans refines, never replaces, a bucket.  ``meta`` holds
    the provenance the staleness check reads: the mesh (dim names + shape)
    the measurements ran on, the platform, and optional ``matmul_gflops``
    (device compute throughput, for FLOPs -> us stage-cost conversion)."""

    entries: Dict[Tuple[str, int, int], Dict[str, float]] = dataclasses.field(
        default_factory=dict
    )
    meta: Dict = dataclasses.field(default_factory=dict)
    # memoized content hash — digest() is consulted on EVERY plan-cache
    # lookup (_cal_key), so it must not re-serialize the table each time
    _digest: Optional[str] = dataclasses.field(default=None, repr=False, compare=False)

    # ------------------------------------------------------------- build
    def add_sample(self, op: str, axis_size: int, nbytes: int, seconds: float,
                   decay: Optional[float] = None) -> None:
        """Fold one measurement into its bucket.  Default: the plain
        running mean (every sample weighs ``1/n`` — the sweep's batch
        semantics).  ``decay``: a fixed EWMA weight for the ONLINE harvest
        (costaudit.py) — recent wall-clock outweighs history, so a table
        skewed by stale measurements converges back to reality instead of
        averaging it away."""
        key = (str(op), int(axis_size), _bucket(nbytes))
        cell = self.entries.get(key)
        us = float(seconds) * 1e6
        self._digest = None  # content changed: drop the memoized hash
        if cell is None:
            self.entries[key] = {"us": us, "samples": 1}
        elif decay is not None:
            a = min(1.0, max(0.0, float(decay)))
            cell["us"] += a * (us - cell["us"])
            cell["samples"] += 1
        else:
            n = cell["samples"] + 1
            cell["us"] += (us - cell["us"]) / n
            cell["samples"] = n

    def ingest_spans(self, spans, decay: Optional[float] = None) -> int:
        """Harvest calibration samples from a span stream: any span whose
        tags carry ``collective_op``/``axis_size``/``bytes`` (the sweep's
        own spans, or runtime instrumentation honoring the contract).
        ``decay`` forwards to :meth:`add_sample` (the online harvest's
        EWMA weight).  Returns the number of samples absorbed."""
        n = 0
        for s in spans:
            tags = getattr(s, "tags", None) or {}
            if not all(t in tags for t in SPAN_TAGS):
                continue
            try:
                self.add_sample(
                    tags["collective_op"], int(tags["axis_size"]),
                    int(tags["bytes"]), float(s.duration), decay=decay,
                )
                n += 1
            except (TypeError, ValueError):
                continue
        return n

    # ``harvest`` is the contract name the audit layer and docs use for
    # span-stream ingestion; same semantics as ingest_spans
    harvest = ingest_spans

    # ------------------------------------------------------------ lookup
    def lookup_us(self, op: str, axis_size: int, nbytes: int) -> Optional[float]:
        """Measured wall time for ``op`` over a mesh axis of ``axis_size``
        moving ``nbytes``: log-log interpolation between measured byte
        buckets, per-byte-rate extrapolation beyond the measured range,
        ``None`` when this (op, axis size) has no buckets at all."""
        pts = sorted(
            (k[2], v["us"])
            for k, v in self.entries.items()
            if k[0] == op and k[1] == int(axis_size)
        )
        if not pts:
            return None
        n = max(1, int(nbytes))
        if len(pts) == 1 or n <= pts[0][0]:
            b, us = pts[0]
            return us * (n / b) if n != b else us
        if n >= pts[-1][0]:
            b, us = pts[-1]
            return us * (n / b) if n != b else us
        for (b0, u0), (b1, u1) in zip(pts, pts[1:]):
            if b0 <= n <= b1:
                if b0 == b1:
                    return u0
                t = (math.log(n) - math.log(b0)) / (math.log(b1) - math.log(b0))
                return math.exp(math.log(u0) * (1 - t) + math.log(u1) * t)
        return pts[-1][1]  # unreachable; defensive

    def op_estimate_us(self, op: str) -> Optional[float]:
        """Sample-weighted mean wall time over EVERY bucket of ``op`` —
        the coarse single-number seed for consumers that know the op but
        not the payload (the serve scheduler's audited ``retry_after_s``
        seed reads ``serve_decode``).  None when the op was never
        measured."""
        total = weight = 0.0
        for k, v in self.entries.items():
            if k[0] == op:
                total += v["us"] * v["samples"]
                weight += v["samples"]
        return total / weight if weight else None

    def matches_mesh(self, mesh) -> bool:
        """Staleness check: the table speaks for the mesh it measured.
        Compares dim names + shape (a ``DeviceMesh`` or anything exposing
        ``mesh_dim_names``/``shape``); a table without mesh provenance
        matches nothing."""
        want = self.meta.get("mesh")
        if not want:
            return False
        try:
            return tuple(want.get("dim_names", ())) == tuple(mesh.mesh_dim_names) and tuple(
                want.get("shape", ())
            ) == tuple(mesh.shape)
        except AttributeError:
            return False

    def __len__(self) -> int:
        return len(self.entries)

    # ------------------------------------------------------- persistence
    def to_json(self) -> Dict:
        return {
            "format": FORMAT_VERSION,
            "meta": self.meta,
            "entries": [
                {"op": k[0], "axis_size": k[1], "bucket_bytes": k[2], **v}
                for k, v in sorted(self.entries.items())
            ],
        }

    @classmethod
    def from_json(cls, data: Dict) -> "CalibrationTable":
        if int(data.get("format", 0)) != FORMAT_VERSION:
            raise ValueError(
                f"calibration table format {data.get('format')!r} unsupported "
                f"(this build reads format {FORMAT_VERSION})"
            )
        t = cls(meta=dict(data.get("meta") or {}))
        for e in data.get("entries", ()):
            t.entries[(str(e["op"]), int(e["axis_size"]), int(e["bucket_bytes"]))] = {
                "us": float(e["us"]),
                "samples": int(e.get("samples", 1)),
            }
        return t

    def digest(self) -> str:
        """Stable short content hash — plan-cache keys record it, so a
        plan names the cost model that priced it.
        Memoized until the next ``add_sample``/``ingest_spans``."""
        if self._digest is None:
            blob = json.dumps(self.to_json(), sort_keys=True).encode()
            self._digest = hashlib.sha256(blob).hexdigest()[:12]
        return self._digest

    def save(self, path: str) -> str:
        """Atomic persist (tmp + rename): the online harvest rewrites the
        table on a cadence while planners may re-read it mid-write via the
        ``VESCALE_COST_CALIBRATION`` mtime reload — a torn read must be
        impossible."""
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        data = self.to_json()
        data["digest"] = self.digest()
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(data, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
        return path

    def launch_us(self) -> float:
        return float(self.meta.get("launch_us", DEFAULT_LAUNCH_US))


def load_table(path: str) -> CalibrationTable:
    with open(path) as f:
        return CalibrationTable.from_json(json.load(f))


# --------------------------------------------------------------- sweep
def _timed(fn, *args) -> float:
    import jax

    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    return time.perf_counter() - t0


def calibrate(
    mesh,
    ops: Sequence[str] = CALIBRATION_OPS,
    byte_buckets: Sequence[int] = (1 << 12, 1 << 16, 1 << 20),
    reps: int = 3,
    measure_matmul: bool = True,
) -> CalibrationTable:
    """Targeted measurement sweep: for each mesh axis, each op and each byte
    bucket, run the collective ``reps`` times (after one untimed warmup that
    eats the compile) and record the median wall time.  Every measured rep
    also emits an ndtimeline span tagged with the sample (when the profiler
    is active), so the sweep shows up on the trace timeline and
    ``ingest_spans`` can re-harvest it from a raw span dump.

    All processes of a multi-process mesh must call this together (the
    collectives are, well, collective)."""
    import numpy as np
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from .. import collectives as C
    from ..ndtimeline.api import ndtimeit

    table = CalibrationTable(
        meta={
            "mesh": {
                "dim_names": list(mesh.mesh_dim_names),
                "shape": list(mesh.shape),
            },
            "platform": jax.devices()[0].platform,
            "created": time.strftime("%Y-%m-%dT%H:%M:%S"),
            "launch_us": DEFAULT_LAUNCH_US,
        }
    )

    def run(op: str, dim: int, x):
        if op == "all_reduce":
            return C.mesh_all_reduce(x, mesh, mesh_dim=dim, stacked=False)
        if op == "all_gather":
            return C.mesh_all_gather(x, mesh, mesh_dim=dim, stacked=False)
        if op == "reduce_scatter":
            return C.mesh_reduce_scatter(x, mesh, mesh_dim=dim)
        if op == "all_to_all":
            return C.mesh_all_to_all(x, mesh, mesh_dim=dim)
        if op == "ppermute":
            return C.mesh_ppermute(x, mesh, mesh_dim=dim)
        raise ValueError(f"unknown calibration op {op!r}")

    def make_input(op: str, dim: int, nbytes: int):
        # GLOBAL arrays by construction (make_array_from_callback over the
        # mesh sharding) so the sweep runs unchanged on a process-spanning
        # mesh — jnp.ones would build process-local arrays there
        ax = mesh.dim_name(dim)
        n = int(mesh.shape[dim])
        elems = max(1, int(nbytes) // 4)  # f32 payloads
        if op in ("reduce_scatter", "all_to_all", "ppermute"):
            # stacked convention: dim0 carries per-rank operands, and
            # chunking needs divisibility by n
            per = max(n, (elems // n) * n)
            shape, spec = (n, per), P(ax)
        else:
            shape, spec = (elems,), P()
        sh = NamedSharding(mesh.jax_mesh, spec)

        def cb(idx):
            return np.ones(
                [len(range(*sl.indices(shape[i]))) for i, sl in enumerate(idx)],
                np.float32,
            )

        return jax.make_array_from_callback(shape, sh, cb)

    for dim in range(len(mesh.shape)):
        n = int(mesh.shape[dim])
        if n <= 1:
            continue
        for op in ops:
            for nbytes in byte_buckets:
                x = make_input(op, dim, int(nbytes))
                _timed(run, op, dim, x)  # warmup: compile + first dispatch
                samples = []
                for _ in range(max(1, int(reps))):
                    with ndtimeit(
                        "calibrate-collective",
                        tags={"collective_op": op, "axis_size": n, "bytes": int(nbytes)},
                    ):
                        samples.append(_timed(run, op, dim, x))
                table.add_sample(op, n, int(nbytes), float(np.median(samples)))

    if measure_matmul:
        # device compute throughput sample: FLOPs -> us conversion for
        # calibrated stage costs (pipe/schedules.estimate_stage_costs)
        import jax.numpy as jnp

        k = 256
        a = jnp.ones((k, k), jnp.float32)
        mm = jax.jit(lambda a: a @ a)
        _timed(mm, a)
        dt = float(np.median([_timed(mm, a) for _ in range(max(1, int(reps)))]))
        flops = 2.0 * k * k * k
        table.meta["matmul_gflops"] = flops / dt / 1e9
    return table


# ------------------------------------------------------- active table gate
_LOCK = threading.Lock()
_ACTIVE: Optional[CalibrationTable] = None          # programmatic override
_ACTIVE_EXPLICIT = False
_LOADED: Dict[str, Tuple[float, Optional[CalibrationTable]]] = {}  # path -> (mtime, table)
_WARNED: set = set()  # one-time fallback warnings, keyed by reason


def set_active(table: Optional[CalibrationTable]) -> None:
    """Programmatically arm (or, with ``None``, disarm) calibrated mode for
    this process, overriding ``VESCALE_COST_CALIBRATION``.  Call
    ``reset_active()`` to return control to the env knob."""
    global _ACTIVE, _ACTIVE_EXPLICIT
    with _LOCK:
        _ACTIVE = table
        _ACTIVE_EXPLICIT = True


def reset_active() -> None:
    global _ACTIVE, _ACTIVE_EXPLICIT
    with _LOCK:
        _ACTIVE = None
        _ACTIVE_EXPLICIT = False
        _LOADED.clear()
        _WARNED.clear()


def clear_warned() -> None:
    """Re-arm the one-time fallback warnings (test hook)."""
    with _LOCK:
        _WARNED.clear()


def _warn_once(key: str, message: str) -> None:
    """Stale/missing-table signals route through the alert engine when it
    is live (rule ``calibration-<reason>``: one lifecycle, /alerts
    visibility); the dormant path keeps the legacy per-key one-shot
    warning so analytic fallbacks stay visible without telemetry."""
    with _LOCK:
        if key in _WARNED:
            return
        _WARNED.add(key)
    from . import alerts as _alerts

    if _alerts.is_active():
        _alerts.raise_alert(
            f"calibration-{key.split(':', 1)[0]}", message=message,
            severity="warning",
        )
        return
    # dormant-engine legacy fallback; live runs route through the
    # telemetry.alerts branch above
    warnings.warn(message, stacklevel=3)  # vescale-lint: disable=VSC207


def active_table() -> Optional[CalibrationTable]:
    """The armed calibration table, or None (analytic mode).  Resolution:
    an explicit :func:`set_active` wins; else ``VESCALE_COST_CALIBRATION``
    names a JSON path, loaded lazily and re-read when its mtime changes
    (live env semantics, envreg contract).  An unreadable path warns once
    and behaves as absent — a typo'd knob must not crash planning."""
    with _LOCK:
        if _ACTIVE_EXPLICIT:
            return _ACTIVE
    from ..analysis import envreg

    path = envreg.get_str("VESCALE_COST_CALIBRATION")
    if not path:
        return None
    try:
        mtime = os.path.getmtime(path)
    except OSError:
        _warn_once(
            f"missing:{path}",
            f"VESCALE_COST_CALIBRATION={path!r}: table not readable — "
            "falling back to the analytic cost model",
        )
        return None
    with _LOCK:
        cached = _LOADED.get(path)
        if cached is not None and cached[0] == mtime:
            return cached[1]
    try:
        table = load_table(path)
    except (OSError, ValueError, KeyError) as e:
        _warn_once(
            f"unparseable:{path}",
            f"VESCALE_COST_CALIBRATION={path!r}: failed to load ({e}) — "
            "falling back to the analytic cost model",
        )
        table = None
    with _LOCK:
        _LOADED[path] = (mtime, table)
    return table


def table_for(mesh) -> Optional[CalibrationTable]:
    """The armed NON-EMPTY table when it speaks for ``mesh`` (or when no
    mesh is given), else None.  A stale table — measured on a different
    mesh shape, or on a different BACKEND than the one now running — warns
    once and resolves to None, so every consumer degrades to its analytic
    model identically.  The platform check covers mesh-less consumers
    (the ``collectives.py`` cost functions keep their signatures): gloo-CPU
    wall times must never silently price a TPU plan."""
    t = active_table()
    if t is None or len(t) == 0:
        return None
    want_platform = t.meta.get("platform")
    if want_platform:
        import jax

        have = jax.devices()[0].platform
        if have != want_platform:
            _warn_once(
                f"platform:{t.digest()}",
                f"VESCALE_COST_CALIBRATION: table was measured on platform "
                f"{want_platform!r} but this process runs on {have!r} — "
                "stale table; falling back to the analytic cost model "
                "(re-run telemetry.calibrate.calibrate() on this backend)",
            )
            return None
    if mesh is not None and not t.matches_mesh(mesh):
        _warn_once(
            f"stale:{t.digest()}",
            "VESCALE_COST_CALIBRATION: table was measured on mesh "
            f"{t.meta.get('mesh')} but is being consulted for {mesh!r} — "
            "stale table; falling back to the analytic cost model "
            "(re-run telemetry.calibrate.calibrate() on this mesh)",
        )
        return None
    return t


def active_digest() -> Optional[str]:
    """Digest of the armed NON-EMPTY table, else None.  The signature the
    planner's and the pipe schedule chooser's cache keys embed: an empty table is
    cost-model-identical to no table and must key identically."""
    t = active_table()
    if t is None or len(t) == 0:
        return None
    return t.digest()


def table_cost_us(
    table: Optional[CalibrationTable], op: str, axis_size: int, nbytes: float
) -> Optional[float]:
    """Measured lookup against an ALREADY-RESOLVED table — the planner's
    hot path resolves the table once per edge set and must not pay the
    env-read + mtime-stat + platform-probe of :func:`table_for` again per
    wire op.  Same one-time missing-bucket warning as
    :func:`collective_cost_us`.  ``nbytes`` is the per-rank OPERAND
    payload (the sweep's own key), never ring-scaled wire bytes."""
    if table is None or int(axis_size) <= 1:
        return None
    us = table.lookup_us(op, int(axis_size), int(nbytes))
    if us is None:
        _warn_once(
            f"bucket:{op}:{axis_size}",
            f"cost calibration: no measured bucket for op={op!r} over a mesh "
            f"axis of {axis_size} — using the analytic model for this op "
            "(extend the calibrate() sweep to cover it)",
        )
        return None
    return us


def collective_cost_us(
    op: str, axis_size: int, nbytes: float, mesh=None
) -> Optional[float]:
    """Measured cost of one collective in microseconds, or None (caller
    falls back to its analytic model).  ``mesh`` (when the caller has one)
    arms the mesh-shape staleness check on top of the always-on platform
    check; a stale table warns once and is treated as absent."""
    if int(axis_size) <= 1:
        return None
    return table_cost_us(table_for(mesh), op, axis_size, nbytes)


# assumed elementwise-pass bandwidth for pricing quantize/dequantize compute
# in calibrated (us-denominated) mode; deliberately conservative so a quant
# hop must win on WIRE time, as in the analytic model
_COMPUTE_GBPS = 10.0


def compute_cost_us(nbytes: float) -> float:
    """Calibrated-mode price of an elementwise pass touching ``nbytes``
    (quantize/dequantize terms of the planner's quant edge)."""
    return float(nbytes) / 1e9 / _COMPUTE_GBPS * 1e6


def hop_latency_us() -> float:
    """Per-hop dispatch overhead in calibrated mode (the analytic model's
    flat ``_HOP_LATENCY`` byte term, re-denominated in microseconds)."""
    t = active_table()
    return t.launch_us() if t is not None else DEFAULT_LAUNCH_US


# Published peaks of one chip, keyed by the ``device_kind`` string jax
# reports for it.  A device that is not here has no peak: callers get an
# error, never a default.
DEVICE_PEAKS: Dict[str, Dict[str, object]] = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "hbm_gbps": 819.0,
        "source": 'Google Cloud documentation, "TPU v5e"',
    },
}


def device_peaks(device) -> Dict[str, object]:
    """The :data:`DEVICE_PEAKS` row of ``device`` (anything with a
    ``device_kind``); an unknown device raises."""
    kind = getattr(device, "device_kind", None)
    try:
        return DEVICE_PEAKS[kind]
    except KeyError:
        raise ValueError(
            f"no published peak for device_kind {kind!r} (known: {sorted(DEVICE_PEAKS)}); "
            "add it to telemetry.calibrate.DEVICE_PEAKS with its source"
        ) from None


def device_peak_flops(device) -> float:
    """Peak (bf16 matmul) FLOP/s of one accelerator chip — the MFU
    denominator of the cost audit's roofline and the serve MFU gauge."""
    return float(device_peaks(device)["bf16_flops"])
