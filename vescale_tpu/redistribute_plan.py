"""Multi-hop redistribution planner — kill the logical-materializing fallback.

``redistribute()`` covers single-hop placement transitions with per-shard
kernels (transfer.py); composite transitions — axis-swap cycles,
Partial/reshard combinations, interleave changes differing on several mesh
dims, cross-mesh moves — used to drop to the pack∘unpack fallback
(redistribute.py) that can materialize the full logical tensor on every
rank.  This module decomposes such a transition into a short sequence of
per-shard primitive hops instead, the approach of "Memory-efficient array
redistribution through portable collective communication" (arXiv:2112.01075);
the cost model choosing among candidate sequences follows "On Optimizing the
Communication of Model Parallelism" (arXiv:2211.05322).

Search: bounded Dijkstra (default ≤3 hops, ``VESCALE_REDISTRIBUTE_MAX_HOPS``)
over a placement lattice spanned per mesh dim by
``placements.transition_candidates`` — the endpoints, plain-Shard
relaxations of interleaves, and Replicate.  Edges are exactly the moves the
per-shard engine already implements:

  dense        transfer.transition_fn      (_plan_ops feasibility, no trace)
  ragged       transfer.ragged_transition_fn   (all-gather-v / all-to-all-v)
  interleaved  transfer.interleaved_transition_fn  (piece-exchange ppermute)
  reshard      plain unpadded same-mesh respec (GSPMD device-to-device)
  device_put   the cross-mesh bridge between plain unpadded specs

Memory contract: every INTERMEDIATE spec's per-shard bytes must stay within
``VESCALE_REDISTRIBUTE_MEM_FACTOR`` (default 4) × the larger endpoint shard —
a plan through full replication is rejected unless an endpoint is itself
logical-size.  Cost: per-hop bytes moved × a per-byte collective weight
(all-to-all < reduce-scatter < all-gather on a torus) + a flat latency term
so equal-byte plans prefer fewer hops.

Plans (and declines, with their reason) are memoized per
``(src_spec, dst_spec)`` in an LRU cache holding the already-jitted hop fns:
a repeated boundary transition pays zero re-planning and zero retracing.
Telemetry (when active): counters ``redistribute.plan_hits`` /
``plan_misses`` / ``hops``, gauge+counter ``redistribute.bytes_moved`` —
fed from ``plan_comm_summary``, the same accounting
``debug.comm_mode.CommDebugMode.attribute_plan`` reads, so the two views
agree by construction.
"""

from __future__ import annotations

import dataclasses
import heapq
import itertools
import threading
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

import jax

from .analysis import envreg
from .placements import transition_candidates
from .spec import DArraySpec

__all__ = [
    "PlanHop",
    "RedistributePlan",
    "Decline",
    "plan_redistribute",
    "decline_reason",
    "decline_finding",
    "quant_single_hop_plan",
    "quant_outcome",
    "quant_decline_finding",
    "plan_comm_summary",
    "can_redistribute_per_shard",
    "clear_plan_cache",
    "plan_cache_stats",
]


@dataclasses.dataclass(frozen=True)
class Decline:
    """A structured planner decline: a stable ``VSC12x`` code from the
    shared findings vocabulary (analysis/findings.py) + the human reason.
    Replaces the free-form reason strings: ``_warn_fallback`` and
    shardcheck's VSC106 both key on ``code``."""

    code: str  # "VSC120".."VSC126"
    message: str

    def __str__(self) -> str:
        return f"[{self.code}] {self.message}"

    def finding(self):
        from .analysis.findings import CODES, Finding

        return Finding(CODES[self.code], self.message)

# per-byte cost weights on a torus: all-to-all keeps each link at 1/n of the
# payload, reduce-scatter streams the ring once, all-gather delivers (n-1)/n
# of the OUTPUT to every device, all-reduce ~ reduce-scatter + all-gather.
# "reshard" (GSPMD-chosen) and the cross-mesh device_put sit between: they
# move at most one destination shard per device but the compiler/runtime
# picks the pattern, so they are costed conservatively.
_WEIGHTS = {
    "all_to_all": 1.0,
    "collective_permute": 1.0,
    "reduce_scatter": 2.0,
    "all_gather": 4.0,
    "all_reduce": 6.0,
    "reshard": 2.0,
    "device_put": 2.0,
}
# flat per-hop latency term (in cost units of bytes): at equal bytes moved,
# fewer hops win — each hop is a separate dispatch + collective launch
_HOP_LATENCY = 64 * 1024

# quantized (int8) hop pricing: the tagged logical collectives of
# transfer.quant_plan_info map onto the wire PATTERN they actually execute
# (quantized all-reduce gathers packed payloads; quantized reduce-scatter
# is an all-to-all exchange), and the quantize/dequantize elementwise
# passes are charged at one cost unit per tensor byte they touch — so a
# quantized hop wins only when the ~4x payload shrink beats the compute it
# adds: DP-grade grad reductions on small mesh dims win, big-fan-in
# reductions (the gather-based algorithm is O(n) wire AND O(n) dequant)
# and pure layout moves decline.
_QWEIGHTS = {
    "all_reduce:int8": 4.0,      # gather pattern
    "all_gather:int8": 4.0,
    "reduce_scatter:int8": 1.0,  # all-to-all pattern
    "all_to_all:int8": 1.0,
}
_QUANT_COMPUTE_WEIGHT = 1.0  # cost units per tensor byte quantized/dequantized

# ---------------------------------------------------------- calibrated mode
# With a measured collective-cost table armed (VESCALE_COST_CALIBRATION,
# telemetry/calibrate.py) the WHOLE search re-denominates from bytes x
# weight into measured microseconds: every wire op prices at the table's
# interpolated wall time for its (op, mesh-dim size, byte) point, ops with
# no measured bucket fall back to the ANALYTIC microsecond model
# (collectives.analytic_cost_us — same unit, so one Dijkstra never compares
# bytes against us), and the flat hop-latency term becomes the measured
# launch overhead.  Without a table — or with an empty or stale one — every
# branch below takes the legacy path and costs are bit-identical to the
# byte-weight model.  _CAL_OP maps an edge's wire kind to the measured op
# vocabulary + a conservatism factor (reshard/device_put let the
# runtime/GSPMD pick the pattern, so they price at 2x the measured
# all-to-all, mirroring their 2.0 byte weight); the quantized tags map to
# the wire PATTERN they execute (module comment above _QWEIGHTS).
_CAL_OP = {
    "all_to_all": ("all_to_all", 1.0),
    "collective_permute": ("all_to_all", 1.0),
    "reduce_scatter": ("reduce_scatter", 1.0),
    "all_gather": ("all_gather", 1.0),
    "all_reduce": ("all_reduce", 1.0),
    "reshard": ("all_to_all", 2.0),
    "device_put": ("all_to_all", 2.0),
    "all_reduce:int8": ("all_gather", 1.0),
    "all_gather:int8": ("all_gather", 1.0),
    "reduce_scatter:int8": ("all_to_all", 1.0),
    "all_to_all:int8": ("all_to_all", 1.0),
}


def _cal_table(mesh):
    """The armed, non-empty, mesh-matching calibration table or None
    (stale tables warn once inside table_for and resolve to None)."""
    from .telemetry import calibrate as _cal

    return _cal.table_for(mesh)


def _cal_key():
    """Calibration signature for the plan caches: the armed non-empty
    table's digest, else None.  Arming, swapping or clearing the table
    must re-search, not re-serve plans priced under another cost model."""
    from .telemetry import calibrate as _cal

    return _cal.active_digest()


def _cal_wire_us(table, kind: str, nbytes: float, n: int) -> float:
    """Calibrated-mode price of one wire op against the ALREADY-RESOLVED
    table (no per-op env/mtime re-resolution on the Dijkstra hot path):
    measured (interpolated) wall microseconds, analytic microseconds when
    the bucket is missing.  ``nbytes`` is the per-rank OPERAND payload —
    the unit the sweep keys buckets by."""
    from . import collectives as C
    from .telemetry import calibrate as _cal

    op, scale = _CAL_OP[kind]
    us = _cal.table_cost_us(table, op, n, nbytes)
    if us is None:
        us = C.analytic_cost_us(op, float(nbytes) / 1e9, n)
    return us * scale


def _hop_lat(table) -> float:
    if table is None:
        return _HOP_LATENCY
    from .telemetry import calibrate as _cal

    return _cal.hop_latency_us()


def _edge_fanin(src: DArraySpec, dst: DArraySpec) -> int:
    """Fan-in for edges whose per-dim wire ops aren't enumerated (ragged /
    interleaved / reshard): the largest mesh dim the transition actually
    changes, else the largest mesh dim."""
    ns = [
        src.mesh.shape[i]
        for i, (s, d) in enumerate(zip(src.placements, dst.placements))
        if s != d
    ]
    return max(ns) if ns else max(src.mesh.shape)


def _mem_factor() -> float:
    return envreg.get_float("VESCALE_REDISTRIBUTE_MEM_FACTOR")


def _max_hops() -> int:
    return envreg.get_int("VESCALE_REDISTRIBUTE_MAX_HOPS")


def _quant_sig():
    """The quant-hop knob tuple, part of every cache key (None = gate off):
    flipping VESCALE_REDISTRIBUTE_QUANT or a compression knob must
    re-search, not re-serve a cached plan built under other settings."""
    if not envreg.get_bool("VESCALE_REDISTRIBUTE_QUANT"):
        return None
    from .quant.blockscale import DEFAULT_BLOCK

    block = envreg.get_int("VESCALE_GRAD_COMPRESS_BLOCK") or DEFAULT_BLOCK
    rounding = "stochastic" if envreg.get_bool("VESCALE_GRAD_COMPRESS_SR") else "nearest"
    seed = envreg.get_int("VESCALE_GRAD_COMPRESS_SEED") or 0
    return (int(block), rounding, int(seed))


@dataclasses.dataclass
class PlanHop:
    """One primitive per-shard move of a multi-hop plan."""

    kind: str  # "dense" | "ragged" | "interleaved" | "reshard" | "device_put" | "quant"
    src: DArraySpec
    dst: DArraySpec
    fn: object  # physical(src) -> physical(dst); None for reshard/device_put
    collectives: Dict[str, int]  # expected collective kinds (static view)
    bytes_moved: int  # per-device bytes on the wire (cost-model estimate)
    cost: float
    bytes_raw: int = 0  # unquantized bytes the same wire ops would move
    #                     (quant hops only; feeds grad_compress_bytes_saved)

    def apply(self, x):
        if self.kind == "reshard":
            from .darray import _apply_sharding

            return _apply_sharding(x, self.dst)
        if self.kind == "device_put":
            return jax.device_put(x, self.dst.named_sharding())
        return self.fn(x)


@dataclasses.dataclass
class RedistributePlan:
    src: DArraySpec
    dst: DArraySpec
    hops: Tuple[PlanHop, ...]
    # cost-audit ledger id of the prediction this plan's price recorded
    # (telemetry/costaudit.py); None when the auditor was dormant at
    # planning time
    plan_id: Optional[int] = None

    @property
    def bytes_moved(self) -> int:
        return sum(h.bytes_moved for h in self.hops)

    @property
    def total_cost(self) -> float:
        return sum(h.cost for h in self.hops)

    def execute(self, physical):
        """Run the hop chain on a physical(src) array; feeds the telemetry
        plan counters/gauge from the SAME summary comm_mode attribution
        reads (plan_comm_summary) so the two views cannot diverge.  With
        the cost auditor live and a ledgered price, the chain runs
        measured instead: per-hop synchronized spans tagged with the
        calibrate harvest contract, and the wall time joined back to the
        prediction."""
        from . import telemetry as _tel
        from .telemetry import costaudit as _ca

        x = physical
        if self.plan_id is not None and _ca.is_active():
            x = self._execute_measured(x, _ca)
        else:
            for hop in self.hops:
                x = hop.apply(x)
        if _tel.is_active():
            summary = plan_comm_summary(self)
            _tel.count("redistribute.hops", len(self.hops))
            _tel.count("redistribute.bytes_moved_total", summary["bytes_moved"])
            _tel.set_gauge("redistribute.bytes_moved", summary["bytes_moved"])
            qhops = [h for h in self.hops if h.kind == "quant"]
            if qhops:
                _tel.count("redistribute.quant_hops", len(qhops))
                _tel.count(
                    "grad_compress_bytes_saved_total",
                    sum(max(0, h.bytes_raw - h.bytes_moved) for h in qhops),
                )
        return x

    def _execute_measured(self, x, _ca):
        """Audited hop chain: each hop runs synchronized inside an
        ndtimeline span carrying the calibrate SPAN_TAGS contract (so the
        online harvest folds the measured wall time back into the table)
        plus the plan id; the chain total joins the ledger.  The per-hop
        ``block_until_ready`` is the price of honest wall times — audited
        mode opts into it; the dormant path is untouched."""
        import time as _time

        from .ndtimeline.api import ndtimeit

        t0 = _time.perf_counter()
        for hop in self.hops:
            op = None
            if hop.collectives:
                wire = max(hop.collectives.items(), key=lambda kv: kv[1])[0]
                op = _CAL_OP.get(wire, (wire, 1.0))[0]
            elif hop.kind in _CAL_OP:
                op = _CAL_OP[hop.kind][0]
            if op is None:  # slice/seed-only hop: no wire time to harvest
                x = hop.apply(x)
                continue
            sb, db = hop.src.per_shard_bytes(), hop.dst.per_shard_bytes()
            # per-rank OPERAND payload, matching the bucket the planner's
            # measured lookup reads (a gather is keyed by its source shard)
            payload = sb if op in ("all_gather", "reduce_scatter") else max(sb, db)
            with ndtimeit(
                "redistribute-hop",
                tags={
                    "collective_op": op,
                    "axis_size": _edge_fanin(hop.src, hop.dst),
                    "bytes": int(payload),
                    "plan_id": self.plan_id,
                },
            ):
                x = jax.block_until_ready(hop.apply(x))
        _ca.record_measurement(
            self.plan_id, measured_us=(_time.perf_counter() - t0) * 1e6
        )
        return x


def plan_comm_summary(plan: RedistributePlan) -> Dict:
    """Per-hop collective/bytes attribution of a plan — the single source
    both the telemetry bytes-moved gauge (RedistributePlan.execute) and
    CommDebugMode.attribute_plan read."""
    hops = []
    collectives: Dict[str, int] = {}
    for i, h in enumerate(plan.hops):
        for k, v in h.collectives.items():
            collectives[k] = collectives.get(k, 0) + v
        hops.append(
            {
                "hop": i,
                "kind": h.kind,
                "src": [str(p) for p in h.src.placements],
                "dst": [str(p) for p in h.dst.placements],
                "collectives": dict(h.collectives),
                "bytes_moved": h.bytes_moved,
            }
        )
    return {
        "hops": hops,
        "n_hops": len(hops),
        "bytes_moved": sum(h.bytes_moved for h in plan.hops),
        "collectives": collectives,
    }


# ------------------------------------------------------------ edge builders
def _dense_edge(src: DArraySpec, dst: DArraySpec, build: bool) -> Optional[PlanHop]:
    from .transfer import _plan_ops, transition_fn

    ops = _plan_ops(src, dst)
    if ops is None:
        return None
    colls: Dict[str, int] = {}
    bytes_m = 0
    cost = 0.0
    table = _cal_table(src.mesh)
    sb, db = src.per_shard_bytes(), dst.per_shard_bytes()
    for op in ops:
        kind, i = op[0], op[1]
        n = src.mesh.shape[i]
        f = (n - 1) / max(1, n)
        # b: ring-scaled wire-byte estimate (legacy cost + telemetry);
        # payload: the PER-RANK operand bytes the op moves — the
        # calibration table is keyed by the sweep's per-rank input size
        # (a gather's contribution is the SOURCE shard, not the gathered
        # output), so the measured lookup and its analytic-us fallback
        # must see that payload or reduce/gather ops get double-scaled
        if kind == "reduce":
            b, c, payload = 2 * f * max(sb, db), "all_reduce", max(sb, db)
        elif kind == "reduce_scatter":
            b, c, payload = f * sb, "reduce_scatter", sb
        elif kind == "gather":
            b, c, payload = f * db, "all_gather", sb
        elif kind == "move":
            b, c, payload = f * max(sb, db), "all_to_all", max(sb, db)
        else:  # slice / seed: local index math, no wire traffic
            continue
        colls[c] = colls.get(c, 0) + 1
        bytes_m += int(b)
        cost += _WEIGHTS[c] * b if table is None else _cal_wire_us(table, c, payload, n)
    fn = transition_fn(src, dst) if build else None
    return PlanHop("dense", src, dst, fn, colls, bytes_m, cost + _hop_lat(table))


def _ragged_edge(src: DArraySpec, dst: DArraySpec, build: bool) -> Optional[PlanHop]:
    if not (src.has_ragged() or dst.has_ragged()):
        return None
    from .transfer import ragged_transition_fn

    fn = ragged_transition_fn(src, dst)  # lru-cached; construction, no trace
    if fn is None:
        return None
    sb, db = src.per_shard_bytes(), dst.per_shard_bytes()
    if src.has_ragged() and dst.is_replicated():
        colls, b, kind = {"all_gather": 1}, db, "all_gather"
    elif src.is_replicated() and dst.has_ragged():
        colls, b, kind = {}, 0, None  # slice-v: local, no comm
    else:  # all-to-all-v as ppermute rounds
        colls, b, kind = {"collective_permute": 1}, max(sb, db), "collective_permute"
    table = _cal_table(src.mesh)
    if kind is None:
        wire = 0.0
    elif table is None:
        wire = _WEIGHTS["all_to_all" if kind == "collective_permute" else kind] * b
    else:
        # measured lookup at the per-rank contribution (the gather-v's
        # operand is the SOURCE ragged shard, not the gathered output)
        payload = sb if kind == "all_gather" else b
        wire = _cal_wire_us(table, kind, payload, _edge_fanin(src, dst))
    return PlanHop(
        "ragged", src, dst, fn if build else None, colls, int(b), wire + _hop_lat(table)
    )


def _interleaved_edge(src: DArraySpec, dst: DArraySpec, build: bool) -> Optional[PlanHop]:
    if not (src.layout().interleaves or dst.layout().interleaves):
        return None
    from .transfer import interleaved_transition_fn

    fn = interleaved_transition_fn(src, dst)
    if fn is None:
        return None
    b = max(src.per_shard_bytes(), dst.per_shard_bytes())
    table = _cal_table(src.mesh)
    wire = (
        _WEIGHTS["all_to_all"] * b
        if table is None
        else _cal_wire_us(table, "collective_permute", b, _edge_fanin(src, dst))
    )
    return PlanHop(
        "interleaved",
        src,
        dst,
        fn if build else None,
        {"collective_permute": 1},
        int(b),
        wire + _hop_lat(table),
    )


def _reshard_edge(src: DArraySpec, dst: DArraySpec) -> Optional[PlanHop]:
    """Plain unpadded same-mesh respec: physical==logical on both sides, so
    the runtime/GSPMD reshard is itself per-shard (the `trivial` path of
    redistribute.py).  This is the edge that reaches nested-Shard endpoints
    no explicit kernel produces."""
    if src.mesh != dst.mesh:
        return None
    for s in (src, dst):
        if (
            s.has_partial()
            or s.has_ragged()
            or s.layout().interleaves
            or s.layout().any_padded
        ):
            return None
    b = max(src.per_shard_bytes(), dst.per_shard_bytes())
    table = _cal_table(src.mesh)
    wire = (
        _WEIGHTS["reshard"] * b
        if table is None
        else _cal_wire_us(table, "reshard", b, _edge_fanin(src, dst))
    )
    return PlanHop(
        "reshard", src, dst, None, {"reshard": 1}, int(b), wire + _hop_lat(table)
    )


def _quant_edge(src: DArraySpec, dst: DArraySpec, build: bool) -> Optional[PlanHop]:
    """The LOSSY quantize->move->dequantize hop (gated by
    VESCALE_REDISTRIBUTE_QUANT): the same static plan as the dense edge,
    but every wire collective carries a block-scaled int8 payload
    (transfer.quant_transition_fn).  Cost charges the packed bytes at the
    wire pattern's weight plus a quantize/dequantize compute term on the
    raw bytes — the hop competes with the dense edge and is taken only
    where it wins."""
    sig = _quant_sig()
    if sig is None:
        return None
    from .transfer import quant_plan_info, quant_transition_fn

    block, rounding, _seed = sig
    info = quant_plan_info(src, dst, block)
    if info is None:
        return None
    _ops, colls, q_bytes, raw_bytes, compute_bytes, wire_detail = info
    table = _cal_table(src.mesh)
    if table is None:
        cost = _QUANT_COMPUTE_WEIGHT * compute_bytes
        for tag, q_op_bytes, _n, _p in wire_detail:  # each op's OWN bytes at its weight
            cost += _QWEIGHTS[tag] * q_op_bytes
    else:
        # measured mode: the PACKED PAYLOAD priced at the wire pattern's
        # measured wall time (per op, at its own fan-in — the table is
        # keyed by operand payload, not ring-scaled wire bytes), and
        # quantize/dequantize compute at the calibrated elementwise rate —
        # same us denomination the competing dense edge uses, so the
        # competition stays fair
        from .telemetry import calibrate as _cal

        cost = _cal.compute_cost_us(compute_bytes)
        for tag, _q, n, payload in wire_detail:
            cost += _cal_wire_us(table, tag, payload, n)
    fn = None
    if build:
        base = quant_transition_fn(src, dst, block, rounding)
        if rounding == "stochastic":
            # the key is a RUNTIME argument of the cached kernel: each
            # execution draws fresh (replayable) noise instead of reusing
            # one baked mask forever
            from .collectives import next_sr_key

            def fn(x, _base=base):
                return _base(x, next_sr_key())
        else:
            fn = base
    return PlanHop(
        "quant", src, dst, fn, colls, int(q_bytes), cost + _hop_lat(table), int(raw_bytes)
    )


def _edge(src: DArraySpec, dst: DArraySpec, build: bool = False) -> Optional[PlanHop]:
    """The cheapest feasible primitive hop src -> dst, or None.  With the
    quant gate on, the quantized variant competes with the dense edge on
    cost; every other kind keeps its priority order."""
    dense = _dense_edge(src, dst, build)
    quant = _quant_edge(src, dst, build)
    if dense is not None and quant is not None:
        return quant if quant.cost < dense.cost else dense
    if dense is not None or quant is not None:
        return dense if dense is not None else quant
    return (
        _ragged_edge(src, dst, build)
        or _interleaved_edge(src, dst, build)
        or _reshard_edge(src, dst)
    )


# ------------------------------------------------------------------ search
def _candidate_specs(src: DArraySpec, dst: DArraySpec) -> List[DArraySpec]:
    per_dim = [
        transition_candidates(sp, dp)
        for sp, dp in zip(src.placements, dst.placements)
    ]
    out: List[DArraySpec] = []
    for combo in itertools.product(*per_dim):
        spec = DArraySpec(src.mesh, combo, src.meta)
        try:
            spec.layout()  # composition validity (ragged/interleave rules)
        except ValueError:
            continue
        out.append(spec)
    return out


def _search_same_mesh(
    src: DArraySpec, dst: DArraySpec
) -> Tuple[Optional[List[PlanHop]], Optional[Decline]]:
    """Bounded Dijkstra src -> dst over the candidate lattice.  Returns
    (hops, None) or (None, structured decline)."""
    nodes = _candidate_specs(src, dst)
    if dst not in nodes:
        nodes.append(dst)
    budget = _mem_factor() * max(src.per_shard_bytes(), dst.per_shard_bytes())
    node_bytes = {n: n.per_shard_bytes() for n in nodes}  # once, not per pop
    max_hops = _max_hops()
    over_budget = False

    # best is keyed by (spec, hop count): a cheap-but-deep route must not
    # shadow a costlier shallow one that still has hop budget to reach dst
    best: Dict[Tuple[DArraySpec, int], float] = {(src, 0): 0.0}
    tie = itertools.count()
    heap: List[Tuple[float, int, int, DArraySpec, List[PlanHop]]] = [
        (0.0, 0, next(tie), src, [])
    ]
    edge_cache: Dict[Tuple[DArraySpec, DArraySpec], Optional[PlanHop]] = {}
    while heap:
        cost, hops, _, spec, path = heapq.heappop(heap)
        if spec == dst:
            return path, None
        if hops >= max_hops or cost > best.get((spec, hops), float("inf")):
            continue
        for nxt in nodes:
            if nxt == spec:
                continue
            if nxt != dst and node_bytes[nxt] > budget:
                over_budget = True
                continue
            key = (spec, nxt)
            if key not in edge_cache:
                edge_cache[key] = _edge(spec, nxt)
            e = edge_cache[key]
            if e is None:
                continue
            c = cost + e.cost
            if c < min(
                best.get((nxt, h), float("inf")) for h in range(hops + 2)
            ):
                best[(nxt, hops + 1)] = c
                heapq.heappush(heap, (c, hops + 1, next(tie), nxt, path + [e]))
    if over_budget:
        return None, Decline("VSC120", (
            "every candidate path needs an intermediate above the per-shard "
            f"memory budget ({_mem_factor():g}x the larger endpoint shard; "
            "raise VESCALE_REDISTRIBUTE_MEM_FACTOR to trade memory for locality)"
        ))
    return None, Decline(
        "VSC121",
        f"no per-shard hop sequence within {max_hops} hops over the candidate lattice",
    )


def _materialize(hops: List[PlanHop]) -> Tuple[PlanHop, ...]:
    """Re-fetch the (lru-cached) jitted kernels for the winning path only —
    losing search edges never build a fn."""
    out = []
    for h in hops:
        if h.kind in ("reshard", "device_put"):
            out.append(h)
            continue
        built = _edge(h.src, h.dst, build=True)
        out.append(built)
    return tuple(out)


def _unpadded_bridge(spec: DArraySpec) -> Optional[DArraySpec]:
    """A plain (no partial/interleave/ragged) UNPADDED spec reachable from
    ``spec`` on its own mesh, suitable as a cross-mesh device_put endpoint
    (physical==logical shard-wise).  Starts from the plain form; Shard dims
    whose extents pad are relaxed to Replicate — a padded physical layout
    must not be device_put into a differently-padded one."""
    from .placements import Replicate as R
    from .redistribute import _plain_placements

    base = _plain_placements(spec)
    if base is None:
        return None
    cand = DArraySpec(spec.mesh, base, spec.meta)
    if not cand.layout().any_padded:
        return cand
    out = list(base)
    for ax in cand.layout().body_axes:
        if ax.is_padded:
            for i in ax.mesh_dims:
                out[i] = R()
    cand = DArraySpec(spec.mesh, tuple(out), spec.meta)
    return None if cand.layout().any_padded else cand


def _plan_cross_mesh(
    src: DArraySpec, dst: DArraySpec
) -> Tuple[Optional[RedistributePlan], Optional[Decline]]:
    """Bridge meshes through plain unpadded specs: plan src -> plain on the
    source mesh, device_put the shards across, plan plain -> dst on the
    destination mesh (the reference CrossMeshRedistribute round-trips the
    LOGICAL value; this path never does)."""
    mid = _unpadded_bridge(src)
    dmid = _unpadded_bridge(dst)
    if mid is None or dmid is None:
        return None, Decline(
            "VSC122", "cross-mesh: a side has no plain unpadded per-shard bridge form"
        )
    budget = _mem_factor() * max(src.per_shard_bytes(), dst.per_shard_bytes())
    for s in (mid, dmid):
        if s not in (src, dst) and s.per_shard_bytes() > budget:
            return None, Decline("VSC123", (
                "cross-mesh: the unpadded bridge spec exceeds the per-shard "
                f"memory budget ({_mem_factor():g}x the larger endpoint shard; "
                "raise VESCALE_REDISTRIBUTE_MEM_FACTOR to trade memory for locality)"
            ))
    hops: List[PlanHop] = []
    if mid != src:
        sub, reason = _search_same_mesh(src, mid)
        if sub is None:
            return None, Decline(
                "VSC124", f"cross-mesh: source-side strip failed — {reason}"
            )
        hops.extend(sub)
    # calibrated pricing of the bridge follows the DESTINATION mesh's table
    # (each same-mesh sub-search already prices under its own mesh's table)
    table = _cal_table(dmid.mesh)
    db = dmid.per_shard_bytes()
    bridge_cost = (
        _WEIGHTS["device_put"] * db
        if table is None
        else _cal_wire_us(table, "device_put", db, max(dmid.mesh.shape))
    )
    hops.append(
        PlanHop(
            "device_put",
            mid,
            dmid,
            None,
            {"device_put": 1},
            int(db),
            bridge_cost + _hop_lat(table),
        )
    )
    if dmid != dst:
        sub, reason = _search_same_mesh(dmid, dst)
        if sub is None:
            return None, Decline(
                "VSC125", f"cross-mesh: destination-side dress failed — {reason}"
            )
        hops.extend(sub)
    return RedistributePlan(src, dst, _materialize(hops)), None


# ---------------------------------------------------------------- LRU cache
class _LRU:
    def __init__(self, maxsize: int):
        self.maxsize = maxsize
        self._d: OrderedDict = OrderedDict()
        self._lock = threading.Lock()

    def get(self, key):
        with self._lock:
            if key not in self._d:
                return None
            self._d.move_to_end(key)
            return self._d[key]

    def put(self, key, value) -> None:
        with self._lock:
            self._d[key] = value
            self._d.move_to_end(key)
            while len(self._d) > self.maxsize:
                self._d.popitem(last=False)

    def __contains__(self, key) -> bool:
        with self._lock:
            return key in self._d

    def clear(self) -> None:
        with self._lock:
            self._d.clear()

    def __len__(self) -> int:
        with self._lock:
            return len(self._d)


_PLANS = _LRU(512)
_DECLINES = _LRU(512)  # (src, dst, knobs) -> Decline
_QUANT_DECLINES = _LRU(512)  # (src, dst, knobs) -> Decline (VSC127)


def _record_quant_outcome(key, src: DArraySpec, dst: DArraySpec, plan) -> None:
    """With the quant gate ON, every planned pair gets a structured
    outcome: either the plan carries a quant hop, or a ``VSC127`` decline
    names WHY the quantized route was not taken (no silent fallback —
    the acceptance contract of the quant-hop feature)."""
    if any(h.kind == "quant" for h in (plan.hops if plan is not None else ())):
        return
    q = _quant_edge(src, dst, build=False)
    if q is None:
        reason = (
            "no quantizable wire plan for this pair (non-float dtype, "
            "non-sum/avg reduction, ragged/interleaved layout, or no wire op)"
        )
    else:
        d = _dense_edge(src, dst, build=False)
        if d is not None and d.cost <= q.cost:
            reason = (
                f"cost model: quantized hop costs {q.cost:.3g} vs {d.cost:.3g} "
                "unquantized (packed bytes + quantize/dequantize compute do "
                "not beat the dense wire pattern here)"
            )
        else:
            reason = "cost model prefers an unquantized multi-hop route"
    _QUANT_DECLINES.put(key, Decline("VSC127", reason))


def _record_plan_prediction(plan: RedistributePlan, kind: str = "redistribute"):
    """Ledger one priced plan with the cost auditor: µs-denominated under
    a calibrated table (``total_cost`` IS microseconds then), weighted-
    bytes otherwise — the auditor only computes divergence for µs plans,
    so the analytic mode stays audit-visible without fake units.  Returns
    the plan id (None while the auditor is dormant)."""
    from .telemetry import costaudit as _ca

    digest = _cal_key()
    return _ca.record_prediction(
        kind,
        predicted_us=plan.total_cost if digest is not None else None,
        predicted_bytes=plan.bytes_moved,
        digest=digest,
        unit="us" if digest is not None else "weighted_bytes",
        detail={"hops": len(plan.hops), "kinds": [h.kind for h in plan.hops]},
    )


def plan_redistribute(src: DArraySpec, dst: DArraySpec) -> Optional[RedistributePlan]:
    """A memoized multi-hop plan for src -> dst, or None (reason retrievable
    via ``decline_reason``).  Consulted by ``redistribute()`` only after the
    single-hop kernels decline."""
    from . import telemetry as _tel
    from .telemetry import costaudit as _ca

    # the knobs are part of the key: raising VESCALE_REDISTRIBUTE_MEM_FACTOR
    # after a budget decline (as the fallback warning instructs) must
    # re-search, not re-serve the cached decline — same for the quant gate
    key = (src, dst, _mem_factor(), _max_hops(), _quant_sig(), _cal_key())
    plan = _PLANS.get(key)
    if plan is not None:
        _tel.count("redistribute.plan_hits")
        if plan.plan_id is None and _ca.is_active():
            # planned while the auditor was dormant (or under a now-dead
            # auditor whose ring dropped it): re-ledger the cached price
            plan.plan_id = _record_plan_prediction(plan)
        return plan
    reason = _DECLINES.get(key)
    if reason is not None:
        return None
    _tel.count("redistribute.plan_misses")
    if src.mesh != dst.mesh:
        plan, reason = _plan_cross_mesh(src, dst)
    else:
        hops, reason = _search_same_mesh(src, dst)
        plan = RedistributePlan(src, dst, _materialize(hops)) if hops is not None else None
    if _quant_sig() is not None:
        _record_quant_outcome(key, src, dst, plan)
    if plan is None:
        _DECLINES.put(key, reason or Decline("VSC121", "unknown"))
        return None
    plan.plan_id = _record_plan_prediction(plan)
    _PLANS.put(key, plan)
    return plan


_NOT_CONSULTED = Decline("VSC126", "planner was not consulted for this pair")


def decline_finding(src: DArraySpec, dst: DArraySpec) -> Decline:
    """The structured decline for (src, dst): a ``VSC12x``-coded
    :class:`Decline` (VSC126 when the planner never saw the pair)."""
    d = _DECLINES.get((src, dst, _mem_factor(), _max_hops(), _quant_sig(), _cal_key()))
    return d if d is not None else _NOT_CONSULTED


def quant_single_hop_plan(src: DArraySpec, dst: DArraySpec) -> Optional[RedistributePlan]:
    """The gated quantized overlay for SINGLE-hop transitions: tiers 1-2 of
    ``redistribute()`` never reach the planner, so with
    ``VESCALE_REDISTRIBUTE_QUANT`` on the dispatch consults this first —
    a one-hop quantized plan when the cost model says int8 packing beats
    the unquantized kernel for this pair, else None with a ``VSC127``
    decline recorded (``quant_decline_finding``).  Memoized in the same
    plan cache, so repeats pay zero re-planning/retracing and
    ``execute()`` feeds the same telemetry counters as every plan."""
    sig = _quant_sig()
    if sig is None or src.mesh != dst.mesh or src == dst:
        return None
    key = (src, dst, _mem_factor(), _max_hops(), sig, _cal_key())
    plan = _PLANS.get(key)
    if plan is not None:
        from . import telemetry as _tel
        from .telemetry import costaudit as _ca

        _tel.count("redistribute.plan_hits")
        if plan.plan_id is None and _ca.is_active():
            plan.plan_id = _record_plan_prediction(plan, kind="redistribute_quant")
        return plan if any(h.kind == "quant" for h in plan.hops) else None
    if key in _QUANT_DECLINES:
        return None
    q = _quant_edge(src, dst, build=False)
    d = _dense_edge(src, dst, build=False)
    if q is not None and (d is None or q.cost < d.cost):
        plan = RedistributePlan(src, dst, (_quant_edge(src, dst, build=True),))
        plan.plan_id = _record_plan_prediction(plan, kind="redistribute_quant")
        _PLANS.put(key, plan)
        return plan
    _record_quant_outcome(key, src, dst, None)
    return None


def quant_outcome(src: DArraySpec, dst: DArraySpec):
    """Analysis-side view of the quant-hop decision for one pair WITHOUT
    building kernels: ``("taken", PlanHop)`` when the cost model picks the
    quantized hop, ``("declined", Decline)`` otherwise, or None when the
    gate is off / meshes differ.  shardcheck's ``check_transition``
    renders this as VSC128 / VSC127 findings."""
    sig = _quant_sig()
    if sig is None or src.mesh != dst.mesh or src == dst:
        return None
    q = _quant_edge(src, dst, build=False)
    d = _dense_edge(src, dst, build=False)
    if q is not None and (d is None or q.cost < d.cost):
        return ("taken", q)
    key = (src, dst, _mem_factor(), _max_hops(), sig, _cal_key())
    _record_quant_outcome(key, src, dst, None)
    return ("declined", _QUANT_DECLINES.get(key))


def quant_decline_finding(src: DArraySpec, dst: DArraySpec) -> Optional[Decline]:
    """Why the QUANTIZED hop was not taken for a planned (src, dst) under
    the current knobs: a ``VSC127`` :class:`Decline`, or None when the gate
    is off, the pair was never planned, or the plan DID take a quant hop.
    Surfaced through shardcheck's ``check_transition`` like every other
    planner outcome."""
    sig = _quant_sig()
    if sig is None:
        return None
    return _QUANT_DECLINES.get((src, dst, _mem_factor(), _max_hops(), sig, _cal_key()))


def decline_reason(src: DArraySpec, dst: DArraySpec) -> str:
    """Why the planner declined (src, dst) — for the fallback warning.
    Human-readable rendering of :func:`decline_finding` (``[VSC12x] why``)."""
    return str(decline_finding(src, dst))


def can_redistribute_per_shard(src: DArraySpec, dst: DArraySpec) -> bool:
    """True when ``redistribute(src -> dst)`` stays on per-shard paths (the
    trivial respec, a single-hop kernel, or a plan) — i.e. it will NOT hit
    the logical-materializing fallback.  Used by the checkpoint loader to
    decide whether a planner-backed per-shard load is available."""
    if src == dst or _reshard_edge(src, dst) is not None:
        return True
    if _edge(src, dst) is not None:
        return True
    return plan_redistribute(src, dst) is not None


def clear_plan_cache() -> None:
    _PLANS.clear()
    _DECLINES.clear()
    _QUANT_DECLINES.clear()


def plan_cache_stats() -> Dict[str, int]:
    return {
        "plans": len(_PLANS),
        "declines": len(_DECLINES),
        "quant_declines": len(_QUANT_DECLINES),
    }
